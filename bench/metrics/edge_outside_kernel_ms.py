"""``edge_outside_kernel_ms``: device time of one edge forward outside the
``masked_matmul`` kernel, in ms: the ``jit_edge_*`` programs' device time
per execution (``device_time.py``, as ``edge_device_ms`` reads it) less the
kernel events' seconds inside those programs over the same executions.
What is left is the im2col patches, the weight dequant, the pools and the
copies around the GEMMs. Absent where the trace holds no named edge
program."""
from bench import device_time

#: the kernel's operations inside a module (``%masked_matmul.5``)
KERNEL_OP = "%masked_matmul"


def read(rec):
    tr = rec.get("trace")
    total_ms = device_time.per_execution_ms(tr, "edge")
    if total_ms is None:
        return None
    mods = device_time.half_modules(tr, "edge").values()
    runs = sum(max(ops.values())[1] for ops in mods)
    kernel_s = sum(s for ops in mods for op, (s, _) in ops.items()
                   if op.startswith(KERNEL_OP))
    return total_ms - 1e3 * kernel_s / runs
