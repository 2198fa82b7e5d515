"""``masked_matmul_roofline``: the edge GEMM kernel's share of its roofline,
in percent.

Each edge forward calls the kernel once per conv or dense layer before the
split, in layer order, so the traced kernel events come in groups of
``len(costs.edge_gemms(cfg))``. The least time of one group is the sum,
over its GEMMs, of the larger of 2*M*K*N operations at the bf16 peak and
the GEMM's bytes (``costs.masked_matmul_bytes``) at the HBM peak. The share
is that least time, times the number of groups traced, over the kernel's
traced time. Which bound sets each GEMM is printed on standard error.
"""
import sys

from bench import costs

#: the kernel's device events: HLO custom calls named after the
#: ``masked_matmul`` entry (``jit_traced(..)/%masked_matmul.5``)
KERNEL_NAME = "/%masked_matmul"


def read(rec):
    tr = rec.get("trace")
    if tr is None:
        return None
    durs = [d for name, ev in tr.op_events.items() if KERNEL_NAME in name
            for d in ev]
    gemms = costs.edge_gemms(rec["cfg"])
    if not durs or not gemms:
        return None
    pk = rec["peaks"]
    floors = [costs.masked_matmul_floor_s(m, k, n, pk["bf16_flops_per_s"],
                                          pk["hbm_bytes_per_s"])
              for m, k, n in gemms]
    bounds = ", ".join(f"{m}x{k}x{n} {b}" for (m, k, n), (_, b)
                       in zip(gemms, floors))
    print(f"masked_matmul roofline bounds per GEMM: {bounds}",
          file=sys.stderr, flush=True)
    groups = len(durs) / len(gemms)
    return 100.0 * groups * sum(t for t, _ in floors) / sum(durs)
