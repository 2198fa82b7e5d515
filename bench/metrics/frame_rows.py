"""``frame_rows``: rows per data frame that reached the dynamic batcher, on
average (``LaneStats`` rows / frames), over the part of the window before
the trace began: how many requests an edge session sends as one frame."""


def read(rec):
    lane = rec.get("lane") or {}
    if not lane.get("frames"):
        return None
    return lane["rows"] / lane["frames"]
