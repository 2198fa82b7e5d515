"""``req_per_s``: requests answered in the window over the time from the
window's start to the last answer (host clock, edge side)."""


def read(rec):
    fin = rec["finished"]
    if not fin:
        return None
    return len(fin) / (max(r.done for r in fin) - rec["t0"])
