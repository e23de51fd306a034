"""Device milliseconds of one paged decode step: the mean length of the
``jit_rago_decode`` programs on the traced window's ``XLA Modules``
line."""
from bench import program_trace as pt


def read(run):
    return pt.program_ms(run, ("rago_decode",), "rago_decode")
