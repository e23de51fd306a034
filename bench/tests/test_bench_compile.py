"""Ahead-of-time compiles, for one described TPU v5e chip, of the programs
every benchmark cell drives: its prefill bucket, its paged fused decode
step (with the Pallas paged-attention kernel) and its IVF-PQ search (on
the scan the cell names), at the cell's own sizes.  Each must compile, and with
everything else the cell keeps on the chip (weights, encoder, index, KV
pool) fit one chip's memory.

The topology is described inside a module-scoped fixture, never at
import (only one process at a time may load the TPU library), and the
persistent compilation cache is off around the compiles: an entry written
for a described chip cannot be read back without one.
"""

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness
from bench.traffic import max_output, prompt_budget
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.models import transformer as tr
from repro.retrieval import ivf_pq
from repro.serving.engine import EngineConfig, RAGEngine, bucket_len

HBM_BYTES = 16 * 2**30                 # one TPU v5e chip
SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]


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


class Cell:
    """Shapes of one cell, as ``bench/harness.build_server`` deploys it."""

    def __init__(self, name, sharding):
        spec = harness.load_spec(name)
        model, mix, ix = spec.model, spec.mix, spec.model["index"]
        self.sharding = sharding
        self.gen = harness._transformer_config(tr, model, model["name"])
        self.enc = harness._transformer_config(
            tr, harness.encoder_model(model), "encoder", causal=False)
        self.slots = spec.cell["decode_slots"]
        page = EngineConfig().page_size
        self.pages_per_slot = -(-mix["s_max"] // page)
        n_pages = self.slots * self.pages_per_slot + mix["kv_spare_pages"]
        row = self.gen.n_kv_heads * self.gen.d_head
        pool = jax.ShapeDtypeStruct(
            (self.gen.n_layers, n_pages, page, row), jnp.bfloat16)
        self.pool = {"k": pool, "v": pool}
        self.params = tr.abstract_params(self.gen, jnp.bfloat16)
        enc = tr.abstract_params(self.enc, jnp.bfloat16)
        enc.pop("head")               # the encoder never reads a head
        self.index = ivf_pq.IVFPQIndex(
            centroids=jax.ShapeDtypeStruct((ix["n_lists"], ix["dim"]),
                                           jnp.float32),
            codebooks=jax.ShapeDtypeStruct(
                (ix["n_subq"], 256, ix["dim"] // ix["n_subq"]), jnp.float32),
            list_ids=jax.ShapeDtypeStruct((ix["n_lists"], ix["list_len"]),
                                          jnp.int32),
            list_codes=jax.ShapeDtypeStruct(
                (ix["n_lists"], ix["list_len"], ix["n_subq"]), jnp.uint8),
            n_vectors=ix["n_lists"] * ix["list_len"])
        self.ix = ix
        self.k = mix["passages_per_request"]
        # unset: the program's choice on a TPU, the Pallas scan
        self.pq_kernel = spec.cell.get("pq_scan_kernel") is not False
        self.bucket = bucket_len(min(
            self.k * model["passage_tokens"] + max(mix["question_tokens"]),
            prompt_budget(mix)))
        self.max_new = max_output(mix)
        self.resident = sum(
            _nbytes(t) for t in (self.params, enc, self.pool, self.index))

    def spec(self, tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=self.sharding), tree)


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


def _fits(cell, compiled, args_resident: bool) -> int:
    """Resident state plus what the program adds: its temporaries, its
    outputs that do not alias a donated input, and its arguments when
    they are not part of the resident state."""
    mem = compiled.memory_analysis()
    total = (cell.resident + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    if not args_resident:
        total += mem.argument_size_in_bytes
    assert total < HBM_BYTES, (total, HBM_BYTES)
    return total


@pytest.fixture(scope="module", params=CELLS)
def cell(request, one_chip):
    return Cell(request.param, one_chip)


def test_prefill_compiles(cell):
    f = jax.jit(partial(tr.forward, cfg=cell.gen, collect_cache=True))
    tokens = jax.ShapeDtypeStruct((1, cell.bucket), jnp.int32,
                                  sharding=cell.sharding)
    compiled = f.lower(cell.spec(cell.params), tokens).compile()
    # the logits and the bucket's K/V come on top of what stays resident
    _fits(cell, compiled, args_resident=True)


def _pallas_attn(q, kp, vp, tables, cache_len):
    # the engine's "pallas" impl with the kernel compiled, not interpreted
    return paged_decode_attention(q, kp, vp, tables, cache_len,
                                  interpret=False)


def test_paged_decode_step_compiles(cell):
    f = jax.jit(partial(RAGEngine._paged_fused_decode, cfg=cell.gen,
                        attn=_pallas_attn), donate_argnums=(1,))
    vec = jax.ShapeDtypeStruct((cell.slots,), jnp.int32)
    compiled = f.lower(
        cell.spec(cell.params), cell.spec(cell.pool), cell.spec(vec),
        cell.spec(vec),
        cell.spec(jax.ShapeDtypeStruct((cell.slots, cell.pages_per_slot),
                                       jnp.int32)),
        cell.spec(jax.ShapeDtypeStruct((cell.slots,), jnp.bool_))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(cell, compiled, args_resident=True)


def test_ivfpq_search_compiles(cell, monkeypatch):
    """The engine's search at batch 1, on the cell's scan; a Pallas scan
    compiled, not interpreted (the wrapper picks interpret mode off a TPU
    backend)."""
    from repro.kernels.pq_scan import ops
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    q = jax.ShapeDtypeStruct((1, cell.ix["dim"]), jnp.float32,
                             sharding=cell.sharding)
    compiled = ivf_pq.search.lower(
        cell.spec(cell.index), q, nprobe=cell.ix["nprobe"], k=cell.k,
        use_kernel=cell.pq_kernel).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == cell.pq_kernel
    _fits(cell, compiled, args_resident=True)
