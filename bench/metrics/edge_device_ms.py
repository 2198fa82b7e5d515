"""``edge_device_ms``: device time of one edge forward, in ms: the traced
seconds of the ``jit_edge_*`` programs over their executions
(``device_time.py``). Absent where the program does not name its halves."""
from bench import device_time


def read(rec):
    return device_time.per_execution_ms(rec.get("trace"), "edge")
