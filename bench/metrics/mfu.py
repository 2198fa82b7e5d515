"""``mfu``: the whole model's share of the chip's bf16 peak. Operations of
one request at deployed widths, edge and cloud halves together
(``costs.request_flops``), times the requests answered per second, over
the published bf16 peak, in percent. A traced run reads the rate over the
part of its window before the trace began, which the profiler does not
slow."""
from bench import costs
from bench.metrics import req_per_s


def read(rec):
    rate = req_per_s.read(rec)
    if not rate:
        return None
    return (100.0 * costs.request_flops(rec["cfg"]) * rate
            / rec["peaks"]["bf16_flops_per_s"])
