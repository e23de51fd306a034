"""Model FLOPs of the prompts prefilled and questions encoded in the
window, over the window times the chip's peak (%)."""
from bench.stats import prefill_flops, window_mfu


def read(run):
    return window_mfu(run, prefill_flops(run, *run["window"]))
