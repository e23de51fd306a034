"""IVF-PQ vector search in JAX (ScaNN/Faiss-style, paper §2).

Index: k-means coarse quantizer (IVF lists) + product-quantized residuals.
Query: (1) coarse scan -> top-nprobe lists, (2) ADC lookup-table build,
(3) PQ code scan over probed lists, (4) top-k select.

TPU-fixed-shape design: IVF lists are padded to equal length and stored as a
dense (n_lists, list_len) id table + flat code matrix, so the probe/scan path
is fully jittable with static shapes (padding entries score +inf).  The PQ
scan (step 3) is the hot loop the paper models at 18 GB/s/core on CPUs; our
Pallas kernel (repro.kernels.pq_scan) implements it TPU-natively and
``search`` can route through it.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import named
from repro.retrieval import kmeans as km


@partial(jax.tree_util.register_dataclass,
         data_fields=["centroids", "codebooks", "list_ids", "list_codes"],
         meta_fields=["n_vectors"])
@dataclass
class IVFPQIndex:
    centroids: jax.Array        # (n_lists, D)
    codebooks: jax.Array        # (S, 256, D // S)  -- residual codebooks
    list_ids: jax.Array         # (n_lists, list_len) int32, -1 = pad
    list_codes: jax.Array       # (n_lists, list_len, S) uint8
    n_vectors: int

    @property
    def n_lists(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_subq(self) -> int:
        return self.codebooks.shape[0]


def build_index(key: jax.Array, vectors: jax.Array, n_lists: int,
                n_subq: int = 8, kmeans_iters: int = 20) -> IVFPQIndex:
    """Train coarse quantizer + PQ on residuals; pack padded IVF lists."""
    n, d = vectors.shape
    k1, k2 = jax.random.split(key)
    centroids, assign = km.kmeans(k1, vectors, n_lists, iters=kmeans_iters)
    residuals = vectors - centroids[assign]
    codebooks = km.train_pq_codebooks(k2, residuals, n_subq)
    codes = km.pq_encode(residuals, codebooks)

    assign_np = np.asarray(assign)
    codes_np = np.asarray(codes)
    counts = np.bincount(assign_np, minlength=n_lists)
    list_len = int(counts.max())
    # pad list length to a lane-friendly multiple
    list_len = max(8, -(-list_len // 8) * 8)
    ids = np.full((n_lists, list_len), -1, np.int32)
    packed = np.zeros((n_lists, list_len, codes_np.shape[1]), np.uint8)
    fill = np.zeros(n_lists, np.int64)
    for i, a in enumerate(assign_np):
        ids[a, fill[a]] = i
        packed[a, fill[a]] = codes_np[i]
        fill[a] += 1
    return IVFPQIndex(centroids=centroids, codebooks=jnp.asarray(codebooks),
                      list_ids=jnp.asarray(ids),
                      list_codes=jnp.asarray(packed), n_vectors=n)


def adc_tables(index: IVFPQIndex, queries: jax.Array,
               probe_centroids: jax.Array) -> jax.Array:
    """Asymmetric-distance lookup tables per (query, probed list).

    queries: (Q, D); probe_centroids: (Q, P, D).
    Returns (Q, P, S, 256) partial squared-L2 tables for the residuals.
    """
    q_res = queries[:, None, :] - probe_centroids          # (Q, P, D)
    s, n_codes, dsub = index.codebooks.shape
    qr = q_res.reshape(q_res.shape[0], q_res.shape[1], s, dsub)
    # ||r - c||^2 per sub-quantizer code
    diff = qr[:, :, :, None, :] - index.codebooks[None, None]   # (Q,P,S,256,dsub)
    return jnp.sum(diff * diff, axis=-1)


def pq_scan_ref(tables: jax.Array, codes: jax.Array) -> jax.Array:
    """Pure-jnp ADC scan.  tables: (..., S, 256); codes: (..., N, S).

    Returns (..., N) distances: sum_s tables[s, codes[n, s]].
    """
    s = tables.shape[-2]
    gathered = jnp.take_along_axis(
        tables[..., None, :, :],                          # (..., 1, S, 256)
        codes[..., :, :, None].astype(jnp.int32),         # (..., N, S, 1)
        axis=-1)[..., 0]                                  # (..., N, S)
    return gathered.sum(axis=-1)


def _search(index: IVFPQIndex, queries: jax.Array, nprobe: int = 8,
            k: int = 10, use_kernel: bool = False):
    """Returns (distances (Q, k), ids (Q, k)).  Fully static shapes."""
    # 1) coarse scan
    with jax.named_scope("coarse"):
        c2 = jnp.sum(index.centroids ** 2, axis=-1)
        coarse = c2[None] - 2.0 * queries @ index.centroids.T  # (Q, L)
        _, probe = jax.lax.top_k(-coarse, nprobe)              # (Q, P)
        probe_centroids = jnp.take(index.centroids, probe, axis=0)

    with jax.named_scope("adc"):
        # 2) ADC tables
        tables = adc_tables(index, queries, probe_centroids)   # (Q,P,S,256)
        # 3) PQ scan over probed lists
        codes = jnp.take(index.list_codes, probe, axis=0)      # (Q,P,LL,S)
        ids = jnp.take(index.list_ids, probe, axis=0)          # (Q,P,LL)
        if use_kernel:
            from repro.kernels.pq_scan.ops import pq_scan
            q, p, ll, s = codes.shape
            dists = pq_scan(tables.reshape(q * p, s, 256),
                            codes.reshape(q * p, ll, s)).reshape(q, p, ll)
        else:
            dists = pq_scan_ref(tables, codes)                 # (Q,P,LL)
        dists = jnp.where(ids >= 0, dists, jnp.inf)

    # 4) top-k across all probed lists
    with jax.named_scope("topk"):
        qn = queries.shape[0]
        flat_d = dists.reshape(qn, -1)
        flat_i = ids.reshape(qn, -1)
        neg, pos = jax.lax.top_k(-flat_d, k)
        return -neg, jnp.take_along_axis(flat_i, pos, axis=1)


search = jax.jit(named("rago_search", _search),
                 static_argnames=("nprobe", "k", "use_kernel"))


def overlap_recall(approx_ids, exact_ids) -> float:
    """Fraction of the exact ids the approximate search recovered.

    Row-wise set overlap over (Q, k) id arrays (or equal-length id lists);
    negative ids in the approximate results -- IVF list padding -- never
    count as hits.
    """
    a = np.asarray(approx_ids)
    e = np.asarray(exact_ids)
    hits = sum(len({int(i) for i in ar if i >= 0} & {int(i) for i in er})
               for ar, er in zip(a, e))
    return hits / e.size


def recall_at_k(index: IVFPQIndex, vectors: jax.Array, queries: jax.Array,
                k: int = 10, nprobe: int = 8) -> float:
    """Recall@k against exact L2 ground truth."""
    from repro.retrieval.exact import knn
    _, approx = search(index, queries, nprobe=nprobe, k=k)
    _, exact_ids = knn(queries, vectors, k=k)
    return overlap_recall(approx, exact_ids)
