"""Device milliseconds a decode step spends moving the KV page pool: the
operations of the ``jit_rago_decode`` programs under the ``kv_write``
scope that write, read or write back whole pages (the scatter, the layer
scan's per-layer slice and write-back), and the whole-pool copy after the
scan, which carries no scope; over the decode programs of the traced
window."""
from bench import program_trace as pt


def read(run):
    return pt.per_decode(run, "kv_pool_ms")
