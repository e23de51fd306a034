"""Device milliseconds of one bucketed prefill: the mean length of the
``jit_rago_prefill`` programs of the traced window."""
from bench import program_trace as pt


def read(run):
    return pt.program_ms(run, ("rago_prefill",), "rago_prefill")
