"""``edge_ms``: median host time of the edge half of a request, from the
session result's ``t_edge`` (edge forward, ended by ``block_until_ready``),
over the requests answered before the trace began."""
import statistics


def read(rec):
    t = [r.t_edge for r in rec["finished"] if r.t_edge is not None]
    if not t:
        return None
    return 1e3 * statistics.median(t)
