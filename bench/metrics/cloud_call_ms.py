"""``cloud_call_ms``: host wall time of one batched cloud call, on average
(``LaneStats`` busy_s / batches), over the part of the window before the
trace began. It includes the transfers and any wait for the device behind
the edges' work on the same chip."""


def read(rec):
    lane = rec.get("lane") or {}
    if not lane.get("batches"):
        return None
    return 1e3 * lane["busy_s"] / lane["batches"]
