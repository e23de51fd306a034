"""RAG serving engine: executes a RAGSchema pipeline end-to-end on real JAX
models + the JAX retrieval engine.

Pipeline per request (stages optional per engine components/config,
mirroring Fig. 3):

  [rewrite] -> [multi-query fan-out] -> embed -> retrieve -> [rerank]
  -> [safety filter] -> prefill (question + docs) -> continuous-batched
  decode [-> iterative retrieval during decode (§5.3)]

The pre-prefill pipeline is not hard-coded: at construction the engine asks
the stage registry (``repro.core.stage_registry``) for StageExecutor
objects -- every registered StageSpec with an active ``make_executor`` for
this engine contributes one, in registry order.  The engine keeps only the
shared infrastructure (corpus + database embeddings, retrieval backend,
KV-cache pool, the slot-based decode loop) and the two decode-anchored
mechanisms (prefill, continuous batching); everything else is composable.

Hot-path design:

* Retrieval goes through a pluggable backend
  (``repro.retrieval.backend``): exact kNN or an IVF-PQ index built at
  construction, selected purely by ``EngineConfig.retrieval_backend``.
* KV state lives in a PAGED pool by default
  (``repro.serving.kv_cache.PagedKVCachePool``): fixed-size pages with a
  per-slot page table, content-addressed full pages shared across
  requests that retrieved the same documents, and page-granular export /
  import for disaggregated handoff.  ``paged=False`` (implied by
  ``fused_decode=False``) keeps the dense slot pool for parity testing.
* The decode step is fused: forward + argmax run inside ONE jitted call
  with the cache donated to XLA, so each token costs a single dispatch
  and a single (B,)-token device->host transfer.  On the paged pool,
  slots that are not stepping scatter their write out of bounds (dropped)
  instead of paying the dense path's whole-cache step-mask merge.
* Iteratively retrieved context AND chunked prompt prefill share one
  bucketed chunk-extend program (``tr.paged_chunk_extend``): one jitted
  forward per power-of-two chunk bucket writes the slot's pages directly.

The decode loop is slot-based (fixed shapes for XLA) with Orca-style
continuous batching, per :meth:`RAGEngine.tick`: every tick admits queued
requests into freed slots, advances chunk-prefilling slots by one prompt
chunk (``prefill_chunk``; prefill work interleaves with decode instead of
running ahead of it), dispatches due iterative retrievals and takes one
decode step -- finished or at-capacity sequences release their slot inside
the same tick.  Prompt lengths are bucketed to powers of two and each
bucket's prefill is jit-compiled once, so compile count is bounded by the
number of distinct buckets.

``metrics`` counts the transfers the hot path pays: ``host_syncs`` (the
device->host copies made by the engine's own primitives -- one per prefill
first-token fetch, one per stepping decode step, one per ``retrieve``
batch; executors' internal transfers are not counted), ``decode_host_syncs``
(the decode loop's share -- exactly one per stepping decode step when
fused), and ``cache_copy_bytes`` (bytes of whole-cache device copies spent
merging decode results -- zero when fused, two full caches per step
otherwise).
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stage_registry import REGISTRY
from repro.models import transformer as tr
from repro.retrieval.backend import (ExactBackend, FallbackBackend,
                                     make_backend)
from repro.serving.faults import EngineCrash, EngineHealth
from repro.serving.kv_cache import KVCachePool, PagedKVCachePool
from repro.serving.request import Request, State
from repro.serving.telemetry import (NULL_TRACER, MetricsRegistry,
                                     stage_kind)


def bucket_len(n: int, floor: int = 8) -> int:
    """Next power of two >= n (shared prefill / chunk-append bucketing)."""
    return int(2 ** np.ceil(np.log2(max(n, floor))))


@dataclass
class EngineConfig:
    decode_slots: int = 4
    s_max: int = 256
    retrieval_k: int = 2
    max_new_tokens: int = 16
    iterative_interval: int | None = None  # tokens between retrievals
    retrieval_batch: int = 1               # iterative batch size (§5.3)
    rewrite_tokens: int = 0                # >0 enables the rewriter stage
    rerank: bool = False
    rerank_candidates: int = 8
    eos_token: int | None = None
    fanout_queries: int = 1                # >1 enables multi-query fan-out
    fanout_tokens: int = 4                 # generated tokens per variant
    safety_threshold: float | None = None  # drop docs scoring below this
    # retrieval backend (repro.retrieval.backend)
    retrieval_backend: str = "exact"       # "exact" | "ivfpq"
    nprobe: int = 8                        # IVF lists probed per query
    use_pq_kernel: bool | None = None      # None = Pallas kernel on TPU only
    # graceful degradation: wrap the backend in a FallbackBackend chain
    # (primary -> exact scan -> no-context); bit-transparent without faults
    retrieval_fallback: bool = True
    # decode-step fusion (False keeps the pre-fusion path for parity tests)
    fused_decode: bool = True
    # decode attention implementation.  "auto" resolves at engine
    # construction: the Pallas paged kernel on TPU, the reference
    # gather+softmax path elsewhere.  "pallas" forces the kernel (interpret
    # mode off-TPU -- CPU CI runs it bit-gated), "splitk" the distributed
    # flash-decoding attention from repro.distributed.decode_attn.
    attn_impl: str = "auto"              # "auto" | "ref" | "pallas" | "splitk"
    attn_num_buffers: int = 2            # DMA staging buffers (2=double, 4=quad)
    # paged KV cache + continuous batching
    paged: bool = True                   # page-table pool (False: dense slots)
    page_size: int = 16                  # tokens per KV page
    kv_spare_pages: int | None = None    # extra pages kept as prefix cache
    prefill_chunk: int | None = None     # >0: chunk prefill across ticks
    iter_query_tokens: int = 8           # fixed iterative-query width

    def __post_init__(self):
        # the prompt budget s_max - max_new_tokens - 1 must be positive,
        # otherwise _assemble_prompt's prompt[-budget:] keeps the WHOLE
        # prompt and decode overflows the cache
        if self.s_max <= self.max_new_tokens + 1:
            raise ValueError(
                f"s_max={self.s_max} must exceed max_new_tokens + 1 = "
                f"{self.max_new_tokens + 1}: the prompt budget "
                f"(s_max - max_new_tokens - 1) would be empty and decode "
                f"would overflow the KV cache")
        if self.page_size <= 0:
            raise ValueError(f"page_size={self.page_size} must be positive")
        if self.iter_query_tokens <= 0:
            raise ValueError("iter_query_tokens must be positive")
        if self.attn_impl not in ("auto", "ref", "pallas", "splitk"):
            raise ValueError(
                f"attn_impl={self.attn_impl!r} must be one of "
                "'auto', 'ref', 'pallas', 'splitk'")
        if self.attn_num_buffers < 2:
            raise ValueError(
                f"attn_num_buffers={self.attn_num_buffers} must be >= 2 "
                "(one page in flight while computing another)")
        if not self.fused_decode:
            # the pre-fusion parity path predates paging; it decodes
            # against the dense slot pool
            self.paged = False
        if self.prefill_chunk is not None:
            if self.prefill_chunk <= 0:
                raise ValueError(
                    f"prefill_chunk={self.prefill_chunk} must be positive")
            if not self.paged:
                raise ValueError(
                    "chunked prefill requires the paged KV pool "
                    "(paged=True with fused_decode=True)")

    @classmethod
    def from_schema(cls, schema, **overrides) -> "EngineConfig":
        """Derive an EngineConfig from a RAGSchema via the stage registry.

        Every enabled StageSpec contributes its ``engine_knobs`` mapping
        (e.g. the rewriter stage sets ``rewrite_tokens`` from
        ``schema.rewriter_out_len``), so the schema is the single source
        of truth for stage enabling/sizing -- those fields are never
        hand-set alongside a schema again.  ``overrides`` are for
        deployment/resource knobs the schema does not describe
        (``decode_slots``, ``retrieval_backend``, test-scale clamps, ...)
        and win over derived values.
        """
        fields = REGISTRY.engine_config_fields(schema)
        fields.update(overrides)
        return cls(**fields)


@dataclass
class Component:
    cfg: tr.TransformerConfig
    params: dict


class RAGEngine:
    def __init__(self, generative: Component, encoder: Component,
                 corpus_tokens: np.ndarray, cfg: EngineConfig,
                 rewriter: Component | None = None,
                 reranker: Component | None = None,
                 safety: Component | None = None,
                 db_vectors: np.ndarray | None = None,
                 backend=None):
        """corpus_tokens: (n_docs, doc_len) int32 database passages.

        ``db_vectors`` / ``backend`` let a multi-engine deployment
        (``repro.serving.cluster``) share one offline corpus encode and
        one built retrieval index across engines instead of re-embedding
        / re-building per engine; they must come from an engine with the
        same encoder component and retrieval config."""
        self.gen = generative
        self.enc = encoder
        self.rewriter = rewriter
        self.reranker = reranker
        self.safety = safety
        self.cfg = cfg
        self.corpus = np.asarray(corpus_tokens)
        self.pool = (PagedKVCachePool(generative.cfg, cfg.decode_slots,
                                      cfg.s_max, page_size=cfg.page_size,
                                      spare_pages=cfg.kv_spare_pages)
                     if cfg.paged else
                     KVCachePool(generative.cfg, cfg.decode_slots, cfg.s_max))
        self.queue: list[Request] = []
        self.active: dict[int, Request] = {}     # slot -> request
        self.prefilling: dict[int, int] = {}     # slot -> prompt cursor
        self.pending_retrievals: list[Request] = []
        self.metrics = MetricsRegistry(
            {"decode_steps": 0, "idle_slot_steps": 0,
             "retrieval_batches": 0, "retrieved_queries": 0,
             "prefills": 0,
             "prefill_compiles": 0, "append_compiles": 0,
             "host_syncs": 0, "decode_host_syncs": 0,
             "cache_copy_bytes": 0, "capacity_stops": 0,
             "degraded_answers": 0, "stage_time_s": {}})
        # telemetry: no-op by default (zero-cost-when-off); a server or
        # cluster swaps in a SpanTracer via set_tracer
        self.tracer = NULL_TRACER
        self.trace_name = "engine0"          # span track id; cluster renames
        self.tick_no = 0                     # decode ticks taken
        # fault layer: health is driven by fail()/degrade() (the injector
        # or a real prober); a DEAD engine refuses work until replaced
        self.health = EngineHealth.HEALTHY
        self.fail_reason: str | None = None
        self.injector = None
        self._retrieval_degraded = False
        # resolved decode-attention implementation ("auto" picks by backend)
        self.attn_impl = cfg.attn_impl if cfg.attn_impl != "auto" else (
            "pallas" if jax.default_backend() == "tpu" else "ref")
        paged_attn, dense_attn = self._make_attn_impls()
        self._decode_jit = jax.jit(partial(tr.decode_step, cfg=self.gen.cfg,
                                           attn_impl=dense_attn))
        self._fused_decode_jit = jax.jit(
            partial(self._fused_decode, cfg=self.gen.cfg, attn=dense_attn),
            donate_argnums=(1,))
        self._paged_decode_jit = jax.jit(
            partial(self._paged_fused_decode, cfg=self.gen.cfg,
                    attn=paged_attn),
            donate_argnums=(1,))
        self._encode_jit = jax.jit(partial(tr.encode, cfg=self.enc.cfg))
        self._prefill_jit = {}                   # bucket -> jitted prefill
        self._append_jit = {}                    # bucket -> jitted extend
        # database embeddings (the paper's offline encode step)
        self.db_vectors = (np.asarray(db_vectors) if db_vectors is not None
                           else np.asarray(self._embed_batched(self.corpus)))
        primary = backend if backend is not None else make_backend(
            cfg.retrieval_backend, self.db_vectors, nprobe=cfg.nprobe,
            use_pq_kernel=cfg.use_pq_kernel)
        if cfg.retrieval_fallback and not isinstance(primary,
                                                     FallbackBackend):
            # degradation ladder: primary -> exact scan -> no-context
            # (bit-transparent while the primary keeps answering)
            chain = [primary]
            if primary.name != "exact":
                chain.append(ExactBackend(self.db_vectors))
            primary = FallbackBackend(chain)
        self.backend = primary
        # executable pipeline, derived from the stage registry
        self.executors = REGISTRY.engine_executors(self)

    # ---------------- health / fault API ------------------------------------

    @property
    def healthy(self) -> bool:
        """Alive (not DEAD).  A DRAINING engine is still alive -- it can
        finish ticking and can even be un-drained -- but it must not
        receive new work: dispatch paths check :attr:`accepting`."""
        return self.health is not EngineHealth.DEAD

    @property
    def accepting(self) -> bool:
        """Eligible for NEW dispatch (HEALTHY or DEGRADED).  DRAINING and
        DEAD engines are excluded: the live-resize contract is that a
        draining engine only sheds work, never gains it."""
        return self.health in (EngineHealth.HEALTHY, EngineHealth.DEGRADED)

    def fail(self, reason: str = "injected") -> None:
        """Declare this engine dead (crash injection or a real health
        prober).  DEAD is permanent: the cluster stops scheduling onto the
        engine and recovers its in-flight requests; any further use of the
        engine raises :class:`EngineCrash`."""
        self.health = EngineHealth.DEAD
        self.fail_reason = reason

    def degrade(self) -> None:
        """Record a survived transient fault (still serving)."""
        if self.health is EngineHealth.HEALTHY:
            self.health = EngineHealth.DEGRADED

    def drain(self) -> None:
        """Park this engine in DRAINING (live resize): it stops accepting
        new work and the cluster's health sweep migrates its in-flight
        requests via the re-prefill path.  Idempotent while already
        draining; raises on a DEAD engine (the legal-transition graph
        ``faults.LEGAL_HEALTH_TRANSITIONS`` has no DEAD -> DRAINING
        edge -- dead engines are *recovered from*, not drained)."""
        if self.health is EngineHealth.DRAINING:
            return
        if self.health is EngineHealth.DEAD:
            raise EngineCrash(
                f"cannot drain a dead engine ({self.fail_reason})")
        self.health = EngineHealth.DRAINING

    def undrain(self) -> None:
        """Abort a drain: the engine re-enters service as DEGRADED (the
        only legal DRAINING exit besides DEAD).  The cluster uses this
        instead of failing queued work when a resize races a crash and
        the draining engine is the last alive member of its group.
        No-op unless currently DRAINING."""
        if self.health is EngineHealth.DRAINING:
            self.health = EngineHealth.DEGRADED

    def check_alive(self) -> None:
        if self.health is EngineHealth.DEAD:
            raise EngineCrash(f"engine is dead ({self.fail_reason})")

    def set_injector(self, injector) -> None:
        """Thread a FaultInjector through this engine's fault points
        (currently the retrieval fallback chain)."""
        self.injector = injector
        if isinstance(self.backend, FallbackBackend):
            self.backend.injector = injector

    def note_retrieval_degraded(self, req: Request) -> None:
        """Flag ``req`` as degraded if its last retrieval was served with
        no context at all (every fallback level failed); counted once per
        request in ``metrics['degraded_answers']``."""
        if self._retrieval_degraded and not req.degraded:
            req.degraded = True
            self.metrics["degraded_answers"] += 1

    # ---------------- shared primitives -----------------------------------

    def _make_attn_impls(self):
        """Build the (paged, dense) decode-attention callables for the
        resolved ``attn_impl``.

        The callables are closed over by the jitted decode programs via
        ``functools.partial`` at construction -- jit never sees them as
        arguments, so swapping implementations costs nothing per step.
        ``(None, None)`` keeps the transformer entry points' built-in
        reference paths (gather + masked softmax), which is what every
        engine computed before this knob existed.
        """
        if self.attn_impl == "ref":
            return None, None
        if self.attn_impl == "pallas":
            from repro.kernels.decode_attention.ops import decode_attention
            from repro.kernels.paged_attention.ops import (
                paged_decode_attention)
            nb = self.cfg.attn_num_buffers

            def paged_attn(q, kp, vp, tables, cache_len):
                return paged_decode_attention(q, kp, vp, tables, cache_len,
                                              num_buffers=nb)

            return paged_attn, decode_attention
        # splitk: flash-decoding sharded over the host mesh's model axis
        # (trivially 1 shard on a single device; the point is wiring the
        # distributed path into the engine with engine-identical tokens)
        from repro.distributed.decode_attn import make_distributed_decode_attn
        from repro.kernels.paged_attention.ref import paged_gather
        from repro.launch.mesh import make_host_mesh
        dense_attn = make_distributed_decode_attn(make_host_mesh(),
                                                  self.gen.cfg.q_per_kv)

        def paged_attn(q, kp, vp, tables, cache_len):
            # split-K shards the sequence axis of a dense view, so this
            # adapter gathers it; only the "pallas" impl is gather-free
            d = q.shape[-1]
            return dense_attn(q, paged_gather(kp, tables, d),
                              paged_gather(vp, tables, d), cache_len)

        return paged_attn, dense_attn

    def has_executor(self, name: str) -> bool:
        return any(ex.name == name for ex in self.executors)

    def set_tracer(self, tracer) -> None:
        """Install a span tracer (``NULL_TRACER`` to turn tracing off)."""
        self.tracer = tracer if tracer is not None else NULL_TRACER

    @contextmanager
    def _timed(self, stage: str, req: Request | None = None, attrs=None):
        """Accumulate wall time into ``metrics['stage_time_s'][stage]``, a
        per-stage latency histogram, and (when tracing) a span.

        Attribution is wall-clock at the call site: executor stages are
        timed inclusively (their internal ``embed``/``retrieve`` primitive
        calls also count toward the primitive buckets), which is the
        breakdown the XPU-side cost-model calibration wants -- where does
        a served second actually go.  Uses ``time.monotonic`` -- the same
        clock as the request timestamps and spans, so stage time and
        request latency are directly comparable.

        With ``req`` the span is request-scoped (opened, so executors can
        :meth:`SpanTracer.annotate` payload sizes onto it mid-stage);
        without, it lands on this engine's track."""
        t0 = time.monotonic()
        tracer = self.tracer
        span = None
        if tracer.enabled and req is not None:
            span = tracer.begin(stage_kind(stage), rid=req.rid,
                                engine=self.trace_name, t=t0,
                                tick=self.tick_no,
                                attempt=req.retries + req.migrations,
                                attrs=attrs)
        try:
            yield
        finally:
            t1 = time.monotonic()
            acc = self.metrics["stage_time_s"]
            acc[stage] = acc.get(stage, 0.0) + t1 - t0
            self.metrics.observe("stage_seconds:" + stage, t1 - t0)
            if span is not None:
                tracer.end(span, t=t1)
            elif tracer.enabled:
                tracer.record(stage_kind(stage), t0, t1,
                              engine=self.trace_name, tick=self.tick_no,
                              attrs=attrs)

    def _embed_batched(self, tokens: np.ndarray, bs: int = 32) -> jnp.ndarray:
        """Encode rows in fixed-size batches through one jitted encoder.

        The final ragged chunk is padded to ``bs`` rows so every call hits
        the same compiled shape; the pad rows are sliced off afterwards
        (each row embeds independently, so padding cannot perturb the
        valid rows)."""
        tokens = np.asarray(tokens)
        outs = []
        for i in range(0, tokens.shape[0], bs):
            chunk = tokens[i:i + bs]
            valid = chunk.shape[0]
            if valid < bs:
                chunk = np.pad(chunk, ((0, bs - valid), (0, 0)))
            h = self._encode_jit(self.enc.params, jnp.asarray(chunk))
            outs.append(h[:valid])
        return jnp.concatenate(outs)

    def retrieve(self, queries: np.ndarray, k: int) -> np.ndarray:
        """queries: (B, T) -> (B, k) doc indices via the retrieval backend.

        Approximate backends may pad the id tail with -1 when the probed
        lists run out of candidates; callers must drop negative ids before
        indexing the corpus."""
        with self._timed("embed"):
            qv = self._embed_batched(queries)
        with self._timed("retrieve"):
            _, idx = self.backend.search(qv, k)
        # queries actually scanned: with bytes_per_query this turns
        # stage_time_s['retrieve'] into a measured scan bandwidth for
        # core/retrieval_model.calibrate_host (the controller's re-plan)
        self.metrics["retrieved_queries"] += len(queries)
        # did the fallback chain bottom out (no-context) on this call?
        self._retrieval_degraded = \
            getattr(self.backend, "last_level", 0) == -1
        self.metrics["host_syncs"] += 1
        return np.asarray(idx)

    # ---------------- admission / prefill ----------------------------------

    def _assemble_prompt(self, req: Request) -> np.ndarray:
        q = req.rewritten if req.rewritten is not None else req.question
        ids = req.candidate_ids if req.candidate_ids is not None \
            else np.asarray([], np.int64)
        req.retrieved_ids.append(list(map(int, ids)))
        docs = self.corpus[ids].reshape(-1)
        prompt = np.concatenate([docs, q])
        max_prompt = self.cfg.s_max - self.cfg.max_new_tokens - 1
        return prompt[-max_prompt:].astype(np.int32)

    def _prefill(self, req: Request, slot: int) -> None:
        """Collocated prefill: compute, then enter the decode loop.  A
        disaggregated cluster calls :meth:`prefill_compute` directly and
        transitions the request to ``HANDOFF`` instead."""
        self.prefill_compute(req, slot)
        req.state = State.DECODE
        req.slot = slot

    def prefill_compute(self, req: Request, slot: int) -> None:
        """Bucketed prefill: pad the prompt to the next power of two and run
        one jit-compiled full-logits forward per bucket.  Causality makes
        tail padding inert for positions < len(prompt); the first token's
        logits are read at position len(prompt)-1 and only the valid cache
        prefix is installed in the slot.  Leaves the request in ``PREFILL``
        with its first token appended; the caller decides the next state
        (``DECODE`` collocated, ``HANDOFF`` disaggregated)."""
        req.state = State.PREFILL
        prompt = req.prompt
        length = len(prompt)
        bucket = bucket_len(length)
        fn = self._prefill_jit.get(bucket)
        if fn is None:
            fn = jax.jit(partial(tr.forward, cfg=self.gen.cfg,
                                 collect_cache=True))
            self._prefill_jit[bucket] = fn
            self.metrics["prefill_compiles"] += 1
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :length] = prompt
        logits, _aux, cache = fn(self.gen.params, jnp.asarray(padded))
        # content-address full pages by prompt tokens + bucket: two prompts
        # share a page only when the prefill math for those positions was
        # the same compiled program on the same inputs (bit-identical K/V)
        self.pool.write_prefix(slot, cache, length, tokens=prompt,
                               key_salt=str(bucket).encode())
        tok = int(jnp.argmax(logits[0, length - 1,
                             :self.gen.cfg.vocab_size]))
        self.metrics["host_syncs"] += 1
        req.output.append(tok)
        req.t_first_token = time.monotonic()
        self.metrics["prefills"] += 1
        if self.tracer.enabled:
            # lands on the enclosing PREFILL span (payload attribution)
            self.tracer.annotate(req.rid, prompt_tokens=length,
                                 prefill_bucket=bucket)

    def _admit(self) -> None:
        while self.queue and self.pool.free:
            req = self.queue.pop(0)
            tracer = self.tracer
            if tracer.enabled:
                tracer.event("ADMIT", rid=req.rid, engine=self.trace_name,
                             tick=self.tick_no,
                             attempt=req.retries + req.migrations)
            for ex in self.executors:
                with self._timed(ex.name, req=req):
                    ex.run(self, req)
            req.prompt = self._assemble_prompt(req)
            slot = self.pool.alloc(req.rid)
            if self.cfg.prefill_chunk:
                # continuous batching: the slot enters PREFILL and the
                # prompt streams in chunk-by-chunk across decode ticks
                # (_prefill_tick) instead of monopolizing the engine
                req.state = State.PREFILL
                req.slot = slot
                self.prefilling[slot] = 0
                self.active[slot] = req
            else:
                with self._timed("prefill", req=req):
                    self._prefill(req, slot)
                self.active[req.slot] = req
                if tracer.enabled:
                    # decode-slot residency: open until DONE/retry closes it
                    tracer.begin("DECODE", rid=req.rid,
                                 engine=self.trace_name, tick=self.tick_no,
                                 attempt=req.retries + req.migrations,
                                 attrs={"slot": req.slot})

    def _prefill_tick(self) -> None:
        """Advance every chunk-prefilling slot by one prompt chunk.  The
        final chunk's logits (at the last valid prompt row) yield the
        request's first token, after which the slot joins the decode
        batch -- prefill work interleaves with decode ticks instead of
        running ahead of them.  Chunk-streamed pages are written
        privately (unkeyed): only the monolithic prefill content-
        addresses pages for prefix sharing."""
        if not self.prefilling:
            return
        chunk = self.cfg.prefill_chunk
        tracer = self.tracer
        with self._timed("prefill"):
            for slot, cursor in list(self.prefilling.items()):
                req = self.active[slot]
                piece = req.prompt[cursor:cursor + chunk]
                span = None
                if tracer.enabled:
                    span = tracer.begin(
                        "PREFILL_CHUNK", rid=req.rid,
                        engine=self.trace_name, tick=self.tick_no,
                        attempt=req.retries + req.migrations,
                        attrs={"tokens": len(piece), "cursor": cursor,
                               "prompt_tokens": len(req.prompt)})
                logits = self._paged_extend(slot, piece)
                cursor += len(piece)
                if cursor >= len(req.prompt):
                    del self.prefilling[slot]
                    tok = int(jnp.argmax(
                        logits[:self.gen.cfg.vocab_size]))
                    self.metrics["host_syncs"] += 1
                    req.output.append(tok)
                    req.t_first_token = time.monotonic()
                    self.metrics["prefills"] += 1
                    if span is not None:
                        tracer.end(span)
                    req.state = State.DECODE
                    if tracer.enabled:
                        tracer.begin("DECODE", rid=req.rid,
                                     engine=self.trace_name,
                                     tick=self.tick_no,
                                     attempt=req.retries + req.migrations,
                                     attrs={"slot": slot})
                else:
                    self.prefilling[slot] = cursor
                    if span is not None:
                        tracer.end(span)

    # ---------------- decode loop ------------------------------------------

    def _append_tokens(self, slot: int, tokens: np.ndarray) -> None:
        """Append retrieved content into a slot's cache (iteration prefill).

        Bucketed chunk append: the tokens are padded to the next power-of-
        two bucket and one jitted ``tr.chunk_extend`` forward writes the
        slot's cache prefix directly (cache donated, pad rows dropped), so
        an n-token append costs one dispatch instead of n decode steps."""
        t = len(tokens)
        if t == 0:
            return
        if isinstance(self.pool, PagedKVCachePool):
            self._paged_extend(slot, np.asarray(tokens, np.int32))
            return
        bucket = bucket_len(t)
        fn = self._append_jit.get(bucket)
        if fn is None:
            fn = jax.jit(partial(tr.chunk_extend, cfg=self.gen.cfg),
                         donate_argnums=(1,))
            self._append_jit[bucket] = fn
            self.metrics["append_compiles"] += 1
        padded = np.zeros(bucket, np.int32)
        padded[:t] = tokens
        self.pool.cache = fn(
            self.gen.params, self.pool.cache,
            jnp.asarray(slot, jnp.int32), jnp.asarray(padded),
            jnp.asarray(self.pool.lengths[slot], jnp.int32),
            jnp.asarray(t, jnp.int32))
        self.pool.lengths[slot] += t

    def _paged_extend(self, slot: int, tokens: np.ndarray) -> jnp.ndarray:
        """Bucketed paged chunk extend: allocate/COW the pages the write
        range touches, then one jitted ``tr.paged_chunk_extend`` per
        power-of-two bucket scatters the chunk into them.  Returns the
        last valid row's logits (device array; only chunked prefill's
        final chunk reads them -- appends leave them unfetched, costing
        no sync)."""
        t = len(tokens)
        self.pool.prepare_append(slot, t)
        bucket = bucket_len(t)
        fn = self._append_jit.get(bucket)
        if fn is None:
            fn = jax.jit(partial(tr.paged_chunk_extend, cfg=self.gen.cfg),
                         donate_argnums=(1,))
            self._append_jit[bucket] = fn
            self.metrics["append_compiles"] += 1
        padded = np.zeros(bucket, np.int32)
        padded[:t] = tokens
        self.pool.cache, logits = fn(
            self.gen.params, self.pool.cache,
            jnp.asarray(self.pool.block_row(slot)), jnp.asarray(padded),
            jnp.asarray(self.pool.lengths[slot], jnp.int32),
            jnp.asarray(t, jnp.int32))
        self.pool.lengths[slot] += t
        return logits

    def _iter_query(self, req: Request) -> np.ndarray:
        """Fixed-width iterative-retrieval query: the last
        ``iter_query_tokens`` generated tokens, falling back to the tail
        of the question, left-padded to a constant width -- mixed-source
        batches stack into one rectangular array (a ragged mix used to
        crash ``np.stack`` whenever retrieval_batch > 1 paired a
        generated-token query with a different-length question)."""
        w = self.cfg.iter_query_tokens
        src = (np.asarray(req.output[-w:], np.int32)
               if len(req.output) >= w
               else np.asarray(req.question[-w:], np.int32))
        if len(src) < w:
            src = np.pad(src, (w - len(src), 0))
        return src

    def _dispatch_iterative(self, force: bool = False) -> None:
        r = self.cfg.retrieval_batch
        while (len(self.pending_retrievals) >= r
               or (force and self.pending_retrievals)):
            batch = self.pending_retrievals[:r]
            self.pending_retrievals = self.pending_retrievals[r:]
            qs = np.stack([self._iter_query(req) for req in batch])
            ids = self.retrieve(qs, 1)
            self.metrics["retrieval_batches"] += 1
            for req in batch:
                self.note_retrieval_degraded(req)
            for req, docs in zip(batch, ids):
                if req.state is not State.WAIT_RETRIEVAL:
                    continue                    # finished (EOS) while queued
                docs = docs[docs >= 0]          # drop ANN padding ids
                # executors may screen iteratively retrieved content before
                # it reaches the cache (same events the analytical
                # decode_stall prices)
                for ex in self.executors:
                    fi = getattr(ex, "filter_iterative", None)
                    if fi is not None:
                        with self._timed(ex.name):
                            docs = fi(self, req, docs)
                req.retrieved_ids.append(list(map(int, docs)))
                req.retrievals_done += 1
                if len(docs):
                    new_ctx = self.corpus[docs[0]]
                    # reserve one cache position per remaining decode step
                    # (each step writes the previous token's K/V), so the
                    # append can never push decode writes past s_max -- the
                    # old fixed 2-token headroom let lengths overrun the
                    # cache and silently corrupt the context
                    remaining = req.max_new_tokens - len(req.output)
                    room = (self.pool.s_max
                            - int(self.pool.lengths[req.slot]) - remaining)
                    if room > 0:
                        with self._timed("append"):
                            self._append_tokens(req.slot, new_ctx[:room])
                req.state = State.DECODE

    @staticmethod
    def _fused_decode(params, cache, token_vec, positions, step_mask, *,
                      cfg, attn=None):
        """One fused decode step: forward + argmax + active-slot cache
        merge in a single XLA program.  ``step_mask`` (B,) bool selects the
        slots that actually decoded; other slots keep their old cache rows
        (the step wrote a garbage token at their current position).  The
        cache argument is donated, so the merge is an in-place update."""
        logits, new_cache = tr.decode_step(params, cache, token_vec,
                                           positions, cfg, attn_impl=attn)
        tokens = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
        mask = step_mask[None, :, None, None, None]     # (L, B, S, H, D)
        merged = jax.tree_util.tree_map(
            lambda new, old: jnp.where(mask, new, old), new_cache, cache)
        return tokens.astype(jnp.int32), merged

    @staticmethod
    def _paged_fused_decode(params, cache, token_vec, positions,
                            block_tables, step_mask, *, cfg, attn=None):
        """Fused decode against the paged pool: forward + argmax in one
        donated XLA program.  No step-mask cache merge is needed -- slots
        that are not stepping simply scatter their K/V write out of
        bounds (dropped), so the page pool is never touched for them;
        they read the same post-scatter pool bytes whichever ``attn``
        implementation runs, which is why the attention kernel needs no
        write-mask handling of its own."""
        logits, cache = tr.paged_decode_step(
            params, cache, token_vec, positions, block_tables, cfg,
            attn_impl=attn, write_mask=step_mask)
        tokens = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
        return tokens.astype(jnp.int32), cache

    def _decode_step(self) -> None:
        token_vec = np.zeros(self.pool.n_slots, np.int32)
        stepping, at_capacity = [], []
        for slot, req in self.active.items():
            if req.state is not State.DECODE:
                continue
            if self.pool.lengths[slot] >= self.pool.s_max:
                # the next step would write K/V past s_max (silently
                # dropped, corrupting the context): finish at capacity
                at_capacity.append(slot)
                continue
            token_vec[slot] = req.output[-1]
            stepping.append(slot)
        for slot in at_capacity:
            req = self.active.pop(slot)
            req.state = State.DONE
            req.t_done = time.monotonic()
            self.metrics["capacity_stops"] += 1
            self.pool.release(slot)
        self.metrics["decode_steps"] += 1
        self.metrics["idle_slot_steps"] += self.pool.n_slots - len(stepping)
        self.tick_no += 1
        if not stepping:
            return
        attrs = ({"n": len(stepping)} if self.tracer.enabled else None)
        with self._timed("decode", attrs=attrs):
            self._decode_active(token_vec, stepping)

    def _decode_active(self, token_vec, stepping) -> None:
        if isinstance(self.pool, PagedKVCachePool):
            for slot in stepping:        # allocate/COW each write target
                self.pool.prepare_append(slot, 1)
            step_mask = np.zeros(self.pool.n_slots, bool)
            step_mask[stepping] = True
            toks, self.pool.cache = self._paged_decode_jit(
                self.gen.params, self.pool.cache, jnp.asarray(token_vec),
                self.pool.positions(), jnp.asarray(self.pool.block_tables()),
                jnp.asarray(step_mask))
            new_tokens = np.asarray(toks)            # the step's one sync
        elif self.cfg.fused_decode:
            step_mask = np.zeros(self.pool.n_slots, bool)
            step_mask[stepping] = True
            toks, self.pool.cache = self._fused_decode_jit(
                self.gen.params, self.pool.cache, jnp.asarray(token_vec),
                self.pool.positions(), jnp.asarray(step_mask))
            new_tokens = np.asarray(toks)            # the step's one sync
        else:
            # pre-fusion path (kept for parity tests): host-side argmax
            # plus a full tree_map cache rebuild per step
            logits, cache = self._decode_jit(
                self.gen.params, self.pool.cache, jnp.asarray(token_vec),
                self.pool.positions())
            new_tokens = np.asarray(
                jnp.argmax(logits[:, :self.gen.cfg.vocab_size], axis=-1))
            # keep cache rows only for slots that actually decoded
            self.pool.cache = jax.tree_util.tree_map(
                lambda new, old: old.at[:, np.asarray(stepping)].set(
                    new[:, np.asarray(stepping)]),
                cache, self.pool.cache)
            self.metrics["cache_copy_bytes"] += sum(
                v.nbytes for v in self.pool.cache.values())
        self.metrics["host_syncs"] += 1
        self.metrics["decode_host_syncs"] += 1
        self.pool.advance(stepping)
        done_slots = []
        for slot in stepping:
            req = self.active[slot]
            tok = int(new_tokens[slot])
            req.output.append(tok)
            n_out = len(req.output)
            it = self.cfg.iterative_interval
            if (it and n_out % it == 0
                    and n_out < req.max_new_tokens
                    and req.state is State.DECODE):
                req.state = State.WAIT_RETRIEVAL
                self.pending_retrievals.append(req)
            if (n_out >= req.max_new_tokens
                    or (self.cfg.eos_token is not None
                        and tok == self.cfg.eos_token)):
                req.state = State.DONE
                req.t_done = time.monotonic()
                done_slots.append(slot)
        for slot in done_slots:
            self.active.pop(slot)
            self.pool.release(slot)

    # ---------------- public API ------------------------------------------

    def tick(self) -> None:
        """One continuous-batching iteration: admit newly queued requests
        into free slots, advance chunked prefills by one chunk, dispatch
        due iterative retrievals, take one decode step.  Admission and
        eviction (slot release on DONE/capacity) both happen inside every
        tick, so the decode batch re-forms continuously."""
        self.check_alive()
        self._admit()
        self._prefill_tick()
        self._dispatch_iterative(
            force=not any(r.state is State.DECODE
                          for r in self.active.values()))
        self._decode_step()

    def metrics_snapshot(self) -> dict:
        """Engine counters merged with the KV pool's page counters
        (``pages_allocated``/``pages_shared``/... for the paged pool).

        The snapshot is fully detached: every nested structure (including
        ``stage_time_s`` and the latency histograms) is a fresh copy, so
        callers can mutate it without corrupting the live registry."""
        out = self.metrics.snapshot()
        out["attn_impl"] = self.attn_impl
        out["health"] = self.health.value
        if isinstance(self.backend, FallbackBackend):
            out["retrieval_fallbacks"] = self.backend.metrics["fallbacks"]
            out["retrieval_no_context"] = self.backend.metrics["no_context"]
        out.update(dict(getattr(self.pool, "metrics", {})))
        return out

    def abort_request(self, req: Request, reason: str,
                      now: float | None = None) -> None:
        """Force ``req`` to the FAILED terminal state and release every
        resource it holds here (queue entry, decode slot, pending
        iterative retrieval, chunked-prefill cursor).  The last-resort
        path that keeps the exactly-one-terminal-state invariant when the
        serving loop gives up (step budget exhausted, engine group
        unservable)."""
        if req.done:
            return
        # identity, not ==: Request is a dataclass over numpy fields
        self.queue[:] = [r for r in self.queue if r is not req]
        self.pending_retrievals = [r for r in self.pending_retrievals
                                   if r is not req]
        for slot, r in list(self.active.items()):
            if r is req:
                self.active.pop(slot)
                self.prefilling.pop(slot, None)
                self.pool.release(slot)
        req.state = State.FAILED
        req.fail_reason = reason
        req.t_done = now if now is not None else time.monotonic()

    def serve(self, requests: list[Request],
              max_steps: int = 10000) -> list[Request]:
        """Closed-batch compatibility wrapper: submit every request at once
        to a throwaway open-loop :class:`repro.serving.server.RAGServer`
        and drain it.  Token-for-token identical to the pre-server loop
        (same admit / iterative-dispatch / decode step order); new code
        should drive a ``RAGServer`` directly."""
        from repro.serving.server import RAGServer
        server = RAGServer(self)
        for r in requests:
            server.submit_request(r)
        server.run_until_idle(max_steps=max_steps)
        return requests
