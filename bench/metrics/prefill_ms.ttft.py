"""Host milliseconds per bucketed prefill over the window."""
from bench.stats import stage_ms


def read(run):
    return stage_ms(run, ("prefill",), "prefills")
