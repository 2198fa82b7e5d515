"""``device_idle``: the share of the traced window in which no operation ran
on the device, in percent (1 - busy / window, busy averaged over chips)."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
