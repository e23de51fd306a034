"""Device-idle milliseconds per decode step: the traced window's time
with no program on the device, over the ``jit_rago_decode`` programs in
it (where that time goes, by ``rago.*`` span: ``bench/program_trace.py``)."""
from bench import program_trace as pt


def read(run):
    return pt.per_decode(run, "idle_ms")
