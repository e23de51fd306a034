"""90th percentile wait from due time to admission (the engine's ADMIT
span), over the window's arrivals."""
from bench.stats import INF, nearest_rank


def read(run):
    waits = [r["admit"] - r["due"] if r["admit"] is not None else INF
             for r in run["requests"]]
    if all(r["admit"] is None for r in run["requests"]):
        return None
    return nearest_rank(waits, 0.90)
