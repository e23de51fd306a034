"""Ahead-of-time compiles for one described TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would
refuse (tile-misaligned kernel slices, VMEM overruns, programs that do not
fit the device).  Interpret mode catches none of that.  These tests compile
the serving main path at granite-3-2b's full widths: the paged-decode
kernel (also at chatglm3-6b's head geometry), the IVF-PQ scan at the smoke
run's shape, and the bucketed prefill, paged fused decode step and
chunk-extend programs with bf16 parameters;
and the decode step at the benchmark cells' shapes (granite-3-2b and
chatglm3-6b), which must update the donated page pool in place and hand
the kernel the whole pool, never a per-layer slice of it.

The topology is described inside a module-scoped fixture (never at import:
only one process at a time may load the TPU library), and the persistent
compilation cache is off around the compiles -- an entry written for a
described chip cannot be read back without one.
"""

import math
import os
import re
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import chatglm3_6b, granite_3_2b
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.pq_scan.pq_scan import pq_scan_pallas
from repro.models import transformer as tr
from repro.serving.engine import EngineConfig, RAGEngine, bucket_len

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
try:
    from chip_smoke import Sizes
finally:
    sys.path.pop(0)

HBM_BYTES = 16 * 2**30                 # one TPU v5e chip
GRANITE = granite_3_2b.CONFIG
CHATGLM = chatglm3_6b.CONFIG
# the chip smoke run's serving shapes
SIZES = Sizes()
SLOTS, S_MAX = SIZES.decode_slots, SIZES.s_max
PAGE, SPARE = EngineConfig().page_size, SIZES.kv_spare_pages
# the largest prompt the engine admits, and one retrieved passage per
# iterative append
PREFILL_BUCKET = bucket_len(S_MAX - SIZES.max_new_tokens)
EXTEND_BUCKET = bucket_len(SIZES.doc_len)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < HBM_BYTES, total
    return total


def _pallas_attn(q, kp, vp, tables, cache_len):
    # the engine's "pallas" impl with the kernel compiled, not interpreted
    return paged_decode_attention(q, kp, vp, tables, cache_len,
                                  interpret=False)


@pytest.mark.parametrize("h_kv,q_per_kv,d,slots,m", [
    (GRANITE.n_kv_heads, GRANITE.q_per_kv, GRANITE.d_head, SLOTS,
     S_MAX // PAGE),
    (GRANITE.n_kv_heads, GRANITE.q_per_kv, 128, SLOTS, S_MAX // PAGE),
    # chatglm3-6b's heads (256-wide page rows) at its benchmark cell's 32
    # slots and 48-page tables
    (CHATGLM.n_kv_heads, CHATGLM.q_per_kv, CHATGLM.d_head, 32, 48),
], ids=["granite", "d128", "chatglm3"])
def test_paged_decode_kernel_compiles(one_chip, h_kv, q_per_kv, d, slots, m):
    n_pages = slots * m + SPARE
    f = jax.jit(partial(paged_decode_attention, interpret=False))
    compiled = f.lower(
        *_spec((jax.ShapeDtypeStruct((slots, 1, h_kv * q_per_kv, d),
                                     jnp.bfloat16),
                jax.ShapeDtypeStruct((n_pages, PAGE, h_kv * d),
                                     jnp.bfloat16),
                jax.ShapeDtypeStruct((n_pages, PAGE, h_kv * d),
                                     jnp.bfloat16),
                jax.ShapeDtypeStruct((slots, m), jnp.int32),
                jax.ShapeDtypeStruct((slots,), jnp.int32)), one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("b,n", [
    (SIZES.n_baseline * EngineConfig().nprobe, 128), (8, 1024)],
    ids=["smoke", "blocked"])
def test_pq_scan_compiles(one_chip, b, n):
    """(queries*nprobe, list_len, S): the smoke run's check, 8 queries
    probing 8 lists each of its IVF index (128-long lists as built on the
    chip, one block per list), and one query over lists long enough to
    split into 512-wide blocks."""
    s = 8
    bn = n if n <= 512 else 512
    f = jax.jit(partial(pq_scan_pallas, block_n=bn, interpret=False))
    compiled = f.lower(*_spec(
        (jax.ShapeDtypeStruct((b, s, 256), jnp.float32),
         jax.ShapeDtypeStruct((b, n, s), jnp.int32)), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.fixture(scope="module")
def granite_params(one_chip):
    return _spec(tr.abstract_params(GRANITE, jnp.bfloat16), one_chip)


def _paged_pool(sharding, slots=SLOTS, s_max=S_MAX, cfg=GRANITE):
    n_pages = slots * (s_max // PAGE) + SPARE
    shape = (cfg.n_layers, n_pages, PAGE, cfg.n_kv_heads * cfg.d_head)
    return {k: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
            for k in ("k", "v")}


def test_granite_prefill_compiles(one_chip, granite_params):
    f = jax.jit(partial(tr.forward, cfg=GRANITE, collect_cache=True))
    tokens = jax.ShapeDtypeStruct((1, PREFILL_BUCKET), jnp.int32,
                                  sharding=one_chip)
    _fits(f.lower(granite_params, tokens).compile())


def _compile_paged_decode(one_chip, params, pool, slots, s_max,
                          cfg=GRANITE):
    """The engine's donated fused decode step with the Pallas kernel."""
    f = jax.jit(partial(RAGEngine._paged_fused_decode, cfg=cfg,
                        attn=_pallas_attn), donate_argnums=(1,))
    vec = _spec(jax.ShapeDtypeStruct((slots,), jnp.int32), one_chip)
    return f.lower(
        params, pool, vec, vec,
        _spec(jax.ShapeDtypeStruct((slots, s_max // PAGE), jnp.int32),
              one_chip),
        _spec(jax.ShapeDtypeStruct((slots,), jnp.bool_), one_chip)).compile()


def test_granite_paged_decode_step_compiles(one_chip, granite_params):
    compiled = _compile_paged_decode(one_chip, granite_params,
                                     _paged_pool(one_chip), SLOTS, S_MAX)
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def _serving_extras(cfg) -> int:
    """Bytes the benchmark's deployment keeps on the chip beside the
    generator and its pool: the ENCODER_120M-shaped question encoder (bf16,
    the generator's vocabulary, no head) and one chip's IVF-PQ shard
    (2048 lists of 2048 vectors, 96 one-byte codes each, int32 ids,
    float32 centroids and codebooks of 768 dims)."""
    enc = tr.TransformerConfig(
        name="encoder", n_layers=12, d_model=768, n_heads=12, n_kv_heads=12,
        d_head=64, d_ff=3072, vocab_size=cfg.vocab_size, causal=False)
    enc_params = tr.abstract_params(enc, jnp.bfloat16)
    enc_params.pop("head")
    n_vectors, n_lists, dim, n_subq = 2**22, 2048, 768, 96
    index = n_vectors * (n_subq + 4) + 4 * (n_lists * dim + 256 * dim)
    return _nbytes(enc_params) + index


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


CELLS = pytest.mark.parametrize("cfg", [GRANITE, CHATGLM],
                                ids=["granite-3-2b", "chatglm3-6b"])


def _cell_decode(one_chip, cfg):
    """The decode step compiled at a ``<model>.rag-decode`` benchmark
    cell's shapes (32 slots, s_max 768, 16 spare pages: 1552 pages):
    ``(compiled, pool)``."""
    slots, s_max = 32, 768
    params = _spec(tr.abstract_params(cfg, jnp.bfloat16), one_chip)
    pool = _paged_pool(one_chip, slots, s_max, cfg)
    return _compile_paged_decode(one_chip, params, pool, slots, s_max,
                                 cfg), pool


@CELLS
def test_paged_decode_step_updates_pool_in_place(one_chip, cfg):
    """At the benchmark cells' shapes the donated pool is written in
    place: the step needs less scratch memory than one layer's K and V
    pages, and neither copies nor dynamic-update-slices a whole pool (a
    pool passed through the layer scan as xs/ys costs a second pool, its
    write-back and a copy of each).  With the weights, the pool, the
    encoder and the index resident, the step fits one chip."""
    compiled, pool = _cell_decode(one_chip, cfg)
    shape = pool["k"].shape
    layer_bytes = 2 * math.prod(shape[1:]) * pool["k"].dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes
    whole_pool = re.compile(
        r"= bf16\[%s\]\{[^}]*\} (copy|dynamic-update-slice)\("
        % ",".join(map(str, shape)))
    writes = [ln for ln in compiled.as_text().splitlines()
              if whole_pool.search(ln)]
    assert not writes, writes
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes
             + _serving_extras(cfg))
    assert total < HBM_BYTES, (total, HBM_BYTES)


@CELLS
def test_paged_decode_step_kernel_reads_whole_pool(one_chip, cfg):
    """At the benchmark cells' shapes the paged kernel reads its pages
    straight from the carried pool: its K and V operands are the whole
    pool viewed as (L*P, page, row), and no op in the step yields one
    layer's (P, page, row) pages -- a per-layer slice, which XLA lowers
    to a read of that layer's whole K and V pool into VMEM."""
    compiled, pool = _cell_decode(one_chip, cfg)
    n_layers, n_pages, page, row = pool["k"].shape
    text = compiled.as_text()
    layer = re.compile(r"= \w+\[%d,%d,%d\]\{" % (n_pages, page, row))
    sliced = [ln for ln in text.splitlines() if layer.search(ln)]
    assert not sliced, sliced
    flat = "bf16[%d,%d,%d]" % (n_layers * n_pages, page, row)
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls
    for ln in calls:
        operands = ln.split("operand_layout_constraints={", 1)[1]
        assert operands.count(flat) == 2, (flat, ln[:600])


def test_granite_paged_chunk_extend_compiles(one_chip, granite_params):
    f = jax.jit(partial(tr.paged_chunk_extend, cfg=GRANITE),
                donate_argnums=(1,))
    scalar = _spec(jax.ShapeDtypeStruct((), jnp.int32), one_chip)
    compiled = f.lower(
        granite_params, _paged_pool(one_chip),
        _spec(jax.ShapeDtypeStruct((S_MAX // PAGE,), jnp.int32), one_chip),
        _spec(jax.ShapeDtypeStruct((EXTEND_BUCKET,), jnp.int32), one_chip),
        scalar, scalar).compile()
    _fits(compiled)
