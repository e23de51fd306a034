"""Host milliseconds per fused decode step that stepped a slot."""
from bench.stats import stage_ms


def read(run):
    return stage_ms(run, ("decode",), "decode_host_syncs")
