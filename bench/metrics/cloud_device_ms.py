"""``cloud_device_ms``: device time of one batched cloud call, in ms: the
traced seconds of the ``jit_cloud_*`` programs over their executions
(``device_time.py``). Beside ``cloud_call_ms``, the host's wall time of the
same call, it splits that call into device work and the rest. Absent where
the program does not name its halves."""
from bench import device_time


def read(rec):
    return device_time.per_execution_ms(rec.get("trace"), "cloud")
