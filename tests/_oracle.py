"""Teacher-forced ``tr.forward`` as the oracle for greedy token streams.

A greedy token passes when its logit, in a forward pass over the logical
sequence that produced it, lies within ``GAP`` of the best logit at that
position.  That is robust to bf16 near-ties: a bucketed prefill, a paged
gather or a chunked append may round differently from the monolithic
forward and flip a tie, but never by more than a few bf16 ulps.

Usage in test modules:  ``from _oracle import assert_follows_forward``
"""

import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tr

GAP = 0.02


def greedy_gaps(params, cfg, seq, at, chosen) -> np.ndarray:
    """Forward over ``seq`` (teacher-forced); for each i, how far the
    logit of ``chosen[i]`` at position ``at[i]`` sits below that
    position's best logit."""
    logits, _ = tr.forward(params, jnp.asarray(seq, jnp.int32)[None], cfg)
    lg = np.asarray(logits[0, :, :cfg.vocab_size], np.float32)[np.asarray(at)]
    return lg.max(-1) - lg[np.arange(len(at)), np.asarray(chosen)]


def spy_appends(engine) -> list:
    """Record every ``engine._append_tokens`` call as (rid, the slot's
    length before the append, the appended tokens)."""
    log = []
    real = engine._append_tokens

    def spy(slot, tokens):
        log.append((engine.active[slot].rid, int(engine.pool.lengths[slot]),
                    np.asarray(tokens, np.int32).copy()))
        real(slot, tokens)

    engine._append_tokens = spy
    return log


def logical_sequence(req, appends=()):
    """A served request's logical token sequence -- its prompt, then its
    output tokens fed back one decode step at a time, with each appended
    chunk at the length it was appended at -- and, per output token, the
    position whose logits chose it."""
    seq, at = list(req.prompt), [len(req.prompt) - 1]
    pending = sorted(((start, toks) for rid, start, toks in appends
                      if rid == req.rid), key=lambda a: a[0])
    for tok in req.output[:-1]:
        while pending and pending[0][0] == len(seq):
            seq.extend(pending.pop(0)[1])
        seq.append(tok)
        at.append(len(seq) - 1)
    assert not pending, f"request {req.rid}: appends past its last step"
    return seq, at


def assert_follows_forward(gen, reqs, appends=()) -> None:
    """Every output token of every request is the oracle's greedy choice
    within ``GAP`` (``gen``: the generative Component)."""
    for r in reqs:
        seq, at = logical_sequence(r, appends)
        gaps = greedy_gaps(gen.params, gen.cfg, seq, at, r.output)
        assert gaps.max() <= GAP, (r.rid, gaps)
