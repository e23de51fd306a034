"""Host milliseconds per query of the engine's embed and retrieve stages
over the window (the embed stage only enqueues; its device time lands in
retrieve, so the two are summed)."""
from bench.stats import stage_ms


def read(run):
    return stage_ms(run, ("embed", "retrieve"), "retrieved_queries")
