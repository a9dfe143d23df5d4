"""Fused K-Means assignment kernel: distance + running argmin over centroid blocks.

assign[n] = argmin_b ||x_n − c_b||², min_d2[n] = the minimum. The full [N, B]
distance matrix is never materialized in HBM: each grid step computes a
[TN, TB] tile on the MXU and folds it into running (min, argmin) VMEM scratch.

Used by index construction (repro.core.kmeans with use_kernel=True) — at 50M+
points the assignment pass dominates K-Means cost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30


def _assign_kernel(x_ref, c_ref, oa_ref, od_ref, run_d, run_i, *, tb: int, n_bblocks: int):
    bb = pl.program_id(1)

    @pl.when(bb == 0)
    def _init():
        run_d[...] = jnp.full_like(run_d, BIG)
        run_i[...] = jnp.zeros_like(run_i)

    x = x_ref[...].astype(jnp.float32)   # [TN, d]
    c = c_ref[...].astype(jnp.float32)   # [TB, d]
    d2 = (
        jnp.sum(x * x, axis=-1, keepdims=True)
        - 2.0 * jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        + jnp.sum(c * c, axis=-1)[None, :]
    )  # [TN, TB]
    # argmin as min + first matching lane (no argmin lowering in-kernel)
    blk_min = jnp.min(d2, axis=1, keepdims=True)                       # [TN, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    blk_arg = jnp.min(jnp.where(d2 == blk_min, lane, tb), axis=1, keepdims=True) + bb * tb
    better = blk_min < run_d[...]
    run_d[...] = jnp.where(better, blk_min, run_d[...])
    run_i[...] = jnp.where(better, blk_arg, run_i[...])

    @pl.when(bb == n_bblocks - 1)
    def _flush():
        oa_ref[...] = run_i[...]
        od_ref[...] = run_d[...]


@functools.partial(jax.jit, static_argnames=("tn", "tb", "interpret"))
def kmeans_assign(
    x: jax.Array,          # [N, d] — N multiple of tn
    centroids: jax.Array,  # [B, d] — B multiple of tb
    *,
    tn: int = 512,
    tb: int = 128,
    interpret: bool = False,
):
    n, d = x.shape
    b = centroids.shape[0]
    assert n % tn == 0 and b % tb == 0, (n, tn, b, tb)
    n_bblocks = b // tb
    kernel = functools.partial(_assign_kernel, tb=tb, n_bblocks=n_bblocks)
    # per-point outputs are [N, 1] columns: 1-D blocks do not match the
    # TPU's vector layout
    assign, mind = pl.pallas_call(
        kernel,
        grid=(n // tn, n_bblocks),
        in_specs=[
            pl.BlockSpec((tn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tb, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tn, 1), jnp.float32),
            pltpu.VMEM((tn, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x, centroids)
    return assign[:, 0], mind[:, 0]
