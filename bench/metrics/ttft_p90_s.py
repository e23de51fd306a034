"""90th percentile time to first token over the window's arrivals, from
the time each was due; a request that failed is a miss."""
from bench.stats import nearest_rank, ttft


def read(run):
    return nearest_rank([ttft(r) for r in run["requests"]], 0.90)
