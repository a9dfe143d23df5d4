"""K-Means partition initialization (paper §3.1 step 1).

Pure-JAX Lloyd iterations, written so the same code runs:
  * single-device for tests/benches (CPU),
  * sharded over a mesh via jit + sharding constraints (data axis shards points).

Distances use the ||x||² - 2x·c + ||c||² expansion so the inner loop is a GEMM
(the MXU-friendly formulation; the assignment hot path also exists as a fused
Pallas kernel in repro.kernels.kmeans_assign).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

# f32 distances at full precision: a default f32 dot on the TPU rounds its
# operands to bf16, which moves assignments and the probing model's inputs
_EXACT = jax.lax.Precision.HIGHEST


class KMeansState(NamedTuple):
    centroids: jax.Array  # [B, d] f32
    assign: jax.Array     # [N] i32
    inertia: jax.Array    # [] f32  (sum of squared distances to assigned centroid)


def plus_plus_init(rng: jax.Array, x: jax.Array, n_clusters: int) -> jax.Array:
    """k-means++ style seeding (D² sampling), O(B·N·d)."""
    n = x.shape[0]
    k0 = jax.random.randint(rng, (), 0, n)
    first = x[k0]

    def body(carry, rng_i):
        cents, d2 = carry  # cents: [B, d] (rows >= i are garbage), d2: [N]
        i, rng_i = rng_i
        probs = d2 / jnp.maximum(d2.sum(), 1e-12)
        idx = jax.random.choice(rng_i, n, p=probs)
        new_c = x[idx]
        cents = cents.at[i].set(new_c)
        nd2 = jnp.sum((x - new_c) ** 2, axis=-1)
        return (cents, jnp.minimum(d2, nd2)), None

    cents = jnp.zeros((n_clusters, x.shape[1]), x.dtype).at[0].set(first)
    d2 = jnp.sum((x - first) ** 2, axis=-1)
    rngs = jax.random.split(rng, n_clusters - 1)
    (cents, _), _ = jax.lax.scan(body, (cents, d2), (jnp.arange(1, n_clusters), rngs))
    return cents


def assign_points(x: jax.Array, centroids: jax.Array, *, use_kernel: bool = False):
    """Return (assignment [N] i32, sq-distance-to-assigned [N] f32)."""
    if use_kernel:
        return kops.kmeans_assign(x, centroids)
    d2 = (
        jnp.sum(x * x, axis=-1, keepdims=True)
        - 2.0 * jnp.dot(x, centroids.T, precision=_EXACT)
        + jnp.sum(centroids * centroids, axis=-1)[None, :]
    )
    assign = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    return assign, jnp.take_along_axis(d2, assign[:, None], axis=-1)[:, 0]


@functools.partial(jax.jit, static_argnames=("n_clusters", "n_iters", "use_kernel"))
def kmeans_fit(
    rng: jax.Array,
    x: jax.Array,
    n_clusters: int,
    n_iters: int = 25,
    use_kernel: bool = False,
) -> KMeansState:
    """Lloyd's algorithm. x: [N, d] f32. Deterministic given rng."""
    x = x.astype(jnp.float32)
    cents = plus_plus_init(rng, x, n_clusters)

    def step(cents, _):
        assign, d2 = assign_points(x, cents, use_kernel=use_kernel)
        # segment mean; empty clusters keep their old centroid
        sums = jax.ops.segment_sum(x, assign, num_segments=n_clusters)
        counts = jax.ops.segment_sum(jnp.ones_like(assign, jnp.float32), assign, num_segments=n_clusters)
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], cents)
        return new, d2.sum()

    cents, inertias = jax.lax.scan(step, cents, None, length=n_iters)
    assign, d2 = assign_points(x, cents, use_kernel=use_kernel)
    return KMeansState(centroids=cents, assign=assign, inertia=d2.sum())


def centroid_distances(q: jax.Array, centroids: jax.Array) -> jax.Array:
    """Query→centroid squared L2 distances `I` (probing-model input). [Q, B]."""
    return (
        jnp.sum(q * q, axis=-1, keepdims=True)
        - 2.0 * jnp.dot(q, centroids.T, precision=_EXACT)
        + jnp.sum(centroids * centroids, axis=-1)[None, :]
    )
