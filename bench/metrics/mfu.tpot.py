"""Model FLOPs of the decode tokens served in the window, over the window
times the chip's peak (%)."""
from bench.stats import decode_flops, window_mfu


def read(run):
    return window_mfu(run, decode_flops(run, *run["window"]))
