"""Compile the serve path for a described TPU v5e, at lira-ann widths.

Nothing runs: each test lowers and compiles with Mosaic/XLA for a chip that
is described, not attached, which is where the TPU's compiler refuses what
the Pallas interpreter accepts (in-kernel ``top_k``, blocks that break the
(8, 128) tiling, more VMEM or HBM than the chip has). Shapes follow
``configs/lira_ann.py``: d=128, 1024 partitions, k=100, residual PQ with
m=16, ks=256 and a rerank depth of 4·k.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.lira_ann import CONFIG_QUANTIZED
from repro.kernels import dedup_topk as dd
from repro.kernels import l2_topk, ops, pq_adc
from repro.serving import tiers
from repro.serving.engine import make_serve_step, probing_param_specs_cache, store_specs

B, D, K, M, KS, RERANK = 1024, 128, 100, 16, 256, 4
CAP = 4096          # ~4× the mean partition of a 1M-vector store at B=1024
N_QUERIES = 128     # the largest batch bucket served on one chip
Q_CAP = max(8, int(N_QUERIES * CONFIG_QUANTIZED.nprobe_max / B * 2.0))
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the TPU library raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return topo.devices[0]


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _streams_live_blocks(text: str, kernel: str, b_loc: int) -> bool:
    """Whether the compiled ``kernel`` takes its per-bucket block counts:
    ``[b_loc]`` int32 beside the ``[b_loc, q_cap]`` dispatch buffer, its
    two scalar-prefetch operands."""
    return re.search(rf"%{kernel}(\.\d+)? = .*operand_layout_constraints="
                     rf"\{{s32\[{b_loc},\d+\]\{{1,0\}}, s32\[{b_loc}\]\{{0\}}",
                     text) is not None


def test_l2_topk_qbuf_compiles(one_chip):
    on = SingleDeviceSharding(one_chip)
    compiled = _compile(
        lambda q, qb, c, i: l2_topk.l2_topk_qbuf(q, qb, c, i, K),
        _sds((N_QUERIES + 1, D), jnp.float32, on),
        _sds((B, Q_CAP), jnp.int32, on),
        _sds((B, CAP, D), jnp.float32, on),
        _sds((B, CAP), jnp.int32, on))
    assert _streams_live_blocks(compiled.as_text(), "l2_topk_qbuf", B)


def test_pq_adc_topk_qbuf_compiles_with_residual_offsets(one_chip):
    on = SingleDeviceSharding(one_chip)
    compiled = _compile(
        lambda lut, qb, c, i, co, qo: pq_adc.pq_adc_topk_qbuf(
            lut, qb, c, i, RERANK * K, cand_off=co, q_off=qo),
        _sds((N_QUERIES + 1, M, KS), jnp.float32, on),
        _sds((B, Q_CAP), jnp.int32, on),
        _sds((B, CAP, M), jnp.int32, on),
        _sds((B, CAP), jnp.int32, on),
        _sds((B, CAP), jnp.float32, on),
        _sds((B, Q_CAP), jnp.float32, on))
    assert _streams_live_blocks(compiled.as_text(), "pq_adc_topk_qbuf", B)


def test_dedup_topk_compiles_at_serve_pool_width(one_chip):
    """The serve step's local merge pool: every local partition's k."""
    on = SingleDeviceSharding(one_chip)
    compiled = _compile(
        lambda d, i: dd.dedup_topk(d, i, K),
        _sds((N_QUERIES, B * K), jnp.float32, on),
        _sds((N_QUERIES, B * K), jnp.int32, on))
    assert "tpu_custom_call" in compiled.as_text()


def _compile_serve_step(devices, model: int, *, tier: str = "residual_pq", cap: int = CAP,
                        n_queries: int = N_QUERIES):
    """The whole jitted serve step, Mosaic kernels and all, at the largest
    bucket the smoke serves (or ``n_queries``), on a (data=1, model) mesh."""
    import dataclasses

    cfg = dataclasses.replace(CONFIG_QUANTIZED, capacity=cap, tier=tier)
    mesh = Mesh(np.array(devices[:model]).reshape(1, model), ("data", "model"))
    step = make_serve_step(cfg, mesh, n_queries, tier=tier, impl="pallas",
                           count_dedup=True)
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, rep),
                          probing_param_specs_cache(cfg))
    pspecs = tiers.resolve(tier).store_pspecs(cfg)
    store = {n: _sds(s.shape, s.dtype, NamedSharding(mesh, pspecs[n]))
             for n, s in store_specs(cfg).items()}
    return _compile(step, params, store,
                    _sds((n_queries, D), jnp.float32, rep),
                    _sds((n_queries,), jnp.bool_, rep))


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def test_residual_pq_serve_step_compiles_and_fits_hbm(one_chip):
    compiled = _compile_serve_step([one_chip], 1)
    assert compiled.as_text().count("tpu_custom_call") >= 2  # scan + merge kernels
    assert _streams_live_blocks(compiled.as_text(), "pq_adc_topk_qbuf", B)
    assert _device_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()


def test_f32_serve_step_at_the_batch_bucket_fits_hbm(one_chip):
    """The f32 step at a 1,024-query bucket over a SIFT1M-sized store
    (12,800 slots per partition): the scan takes its block counts and the
    step's temporaries stay what the full-capacity scan needed (1.59 GB)."""
    compiled = _compile_serve_step([one_chip], 1, tier="f32", cap=12800, n_queries=1024)
    assert _streams_live_blocks(compiled.as_text(), "l2_topk_qbuf", B)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.7e9, mem
    assert _device_bytes(compiled) < HBM_BYTES, mem


def test_f32_serve_step_scans_a_lane_aligned_store_in_place(one_chip):
    """The build keeps capacity whole 128-lane tiles, which the default
    256-slot scan tile need not divide: the step must still stream such a
    store where it lies, not pad a copy of it (6 GB at SIFT1M scale)."""
    cap = CAP + ops.SLOT_ALIGN      # an odd number of lane tiles
    mem = _compile_serve_step([one_chip], 1, tier="f32", cap=cap).memory_analysis()
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes // 8, mem


def test_model_sharded_serve_step_compiles_on_four_chips(topo):
    """The model=4 path: partitions split over four chips, then the
    cross-shard all-gather and a second dedup merge."""
    compiled = _compile_serve_step(topo.devices, 4)
    text = compiled.as_text()
    assert "all-gather" in text
    # each chip counts blocks over its own quarter of the partitions
    assert _streams_live_blocks(text, "pq_adc_topk_qbuf", B // 4)
    assert text.count("tpu_custom_call") >= 3  # scan + local merge + cross-shard merge
    # the dedup counter, local and cross-shard, sits beside the merge scope
    tele = re.findall(r'op_name="([^"]*lira\.telemetry[^"]*)"', text)
    assert tele and not [n for n in tele if "lira.merge" in n]
    # per-device bytes: each chip holds a quarter of the partition planes
    assert _device_bytes(compiled) < _device_bytes(_compile_serve_step(topo.devices, 1))
