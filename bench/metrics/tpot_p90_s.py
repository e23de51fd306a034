"""90th percentile of each request's mean gap between output tokens; a
request that failed is a miss."""
from bench.stats import nearest_rank, tpot


def read(run):
    return nearest_rank([tpot(r) for r in run["requests"]], 0.90)
