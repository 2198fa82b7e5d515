"""``batch_rows``: rows the dynamic batcher fused into one cloud call, on
average (``LaneStats`` rows / batches), over the part of the window before
the trace began."""


def read(rec):
    lane = rec.get("lane") or {}
    if not lane.get("batches"):
        return None
    return lane["rows"] / lane["batches"]
