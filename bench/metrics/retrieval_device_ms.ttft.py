"""Device milliseconds of retrieval per search: the ``jit_rago_encode``
and ``jit_rago_search`` programs of the traced window over its searches
(one query each in a cell that retrieves once per request)."""
from bench import program_trace as pt


def read(run):
    return pt.program_ms(run, ("rago_encode", "rago_search"), "rago_search")
