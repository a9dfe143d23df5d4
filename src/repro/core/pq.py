"""Product quantization (IVFPQ baseline; Jégou et al. TPAMI'11).

ADC fact used by the evaluation engine: with orthogonal subspace decomposition,
ADC distance == exact L2 between the query and the RECONSTRUCTED point
(centroid + decoded residual for IVFPQ). So recall-accurate IVFPQ evaluation =
partition_topk over reconstructions (GEMM-bound, fast on CPU), while the
kernel-accurate LUT path lives in repro.kernels.pq_adc for TPU.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kmeans import kmeans_fit


class PQCodebook(NamedTuple):
    codebooks: jax.Array  # [m, ks, d_sub] f32
    m: int
    ks: int


def code_dtype(ks: int) -> np.dtype:
    """Narrowest integer dtype that can hold a code in [0, ks)."""
    if ks <= 256:
        return np.dtype(np.uint8)
    if ks <= 65536:
        return np.dtype(np.uint16)
    return np.dtype(np.int32)


def train_pq(rng: jax.Array, x: np.ndarray, m: int = 16, ks: int = 256, n_iters: int = 15) -> PQCodebook:
    n, d = x.shape
    assert d % m == 0, f"dim {d} not divisible by m={m}"
    d_sub = d // m
    xs = jnp.asarray(x, jnp.float32).reshape(n, m, d_sub)
    rngs = jax.random.split(rng, m)
    cbs = []
    for j in range(m):  # python loop: m small, keeps peak memory low
        st = kmeans_fit(rngs[j], xs[:, j], n_clusters=ks, n_iters=n_iters)
        cbs.append(st.centroids)
    return PQCodebook(codebooks=jnp.stack(cbs), m=m, ks=ks)


def encode(pq: PQCodebook, x: np.ndarray, *, batch: int = 8192) -> np.ndarray:
    """x -> codes [N, m]; uint8 when ks ≤ 256, uint16 when ks ≤ 65536."""
    n, d = x.shape
    d_sub = d // pq.m
    out = np.empty((n, pq.m), code_dtype(pq.ks))

    @jax.jit
    def enc(xb):
        xb = xb.reshape(xb.shape[0], pq.m, d_sub)
        d2 = (
            jnp.sum(xb * xb, -1)[..., None]
            - 2.0 * jnp.einsum("nmd,mkd->nmk", xb, pq.codebooks,
                               precision=jax.lax.Precision.HIGHEST)
            + jnp.sum(pq.codebooks * pq.codebooks, -1)[None]
        )
        return jnp.argmin(d2, -1).astype(jnp.int32)

    for s in range(0, n, batch):
        out[s : s + batch] = np.asarray(enc(jnp.asarray(x[s : s + batch], jnp.float32))).astype(out.dtype)
    return out


def decode(pq: PQCodebook, codes: np.ndarray, *, batch: int = 65536) -> np.ndarray:
    """codes -> reconstructed vectors [N, d]."""
    n = codes.shape[0]
    d_sub = pq.codebooks.shape[-1]
    out = np.empty((n, pq.m * d_sub), np.float32)

    @jax.jit
    def dec(cb):
        cb = cb.astype(jnp.int32)  # accept uint8/uint16 code stores
        recon = jnp.take_along_axis(pq.codebooks[None], cb[:, :, None, None], axis=2)
        return recon[:, :, 0, :].reshape(cb.shape[0], -1)

    for s in range(0, n, batch):
        out[s : s + batch] = np.asarray(dec(jnp.asarray(codes[s : s + batch])))
    return out


def adc_lut_raw(codebooks: jax.Array, q: jax.Array) -> jax.Array:
    """Per-query LUT of subspace distances from a raw [m, ks, d_sub] codebook
    array: [Q, m, ks]. The serve step holds codebooks as a plain array, so
    this is the shared implementation behind both call styles."""
    qs = q.reshape(q.shape[0], codebooks.shape[0], -1)
    return (
        jnp.sum(qs * qs, -1)[..., None]
        - 2.0 * jnp.einsum("qmd,mkd->qmk", qs, codebooks,
                           precision=jax.lax.Precision.HIGHEST)
        + jnp.sum(codebooks * codebooks, -1)[None]
    )


def adc_lut(pq: PQCodebook, q: jax.Array) -> jax.Array:
    """Per-query LUT of subspace distances: [Q, m, ks]."""
    return adc_lut_raw(pq.codebooks, q)


def adc_distances(pq: PQCodebook, q: jax.Array, codes: jax.Array) -> jax.Array:
    """Exact ADC: dist[q, n] = sum_m LUT[q, m, codes[n, m]] -> [Q, N].
    This is the jnp oracle for the Pallas pq_adc kernel."""
    lut = adc_lut(pq, q)  # [Q, m, ks]
    codes_t = codes.astype(jnp.int32).T  # [m, N]

    def per_query(lq):  # lq: [m, ks]
        return jnp.sum(jnp.take_along_axis(lq, codes_t, axis=1), axis=0)  # [N]

    return jax.vmap(per_query)(lut)


# --------------------------------------------------------------- residual PQ
#
# IVFPQ residual encoding (codes over x − centroid[assign(x)]) normally breaks
# the one-LUT-per-query property: the LUT of q − c_b depends on the partition.
# The exact distance to the reconstruction c_b + r̂ decomposes instead as
#
#   ‖q − (c_b + r̂)‖² =   Σ_m lut[q, m, code_m]     (shared across partitions)
#                       + ‖c_b‖² − 2⟨q, c_b⟩        (per-(query, partition))
#                       + 2⟨c_b, r̂⟩                 (per-slot, query-free)
#
# where lut is the ordinary ``adc_lut`` of the RESIDUAL codebooks evaluated at
# the raw query q. The serving tier precomputes the third term at build time
# (``residual_cross_terms``, stored next to the codes); for the second it
# reuses the probing centroid-distance matrix already in the serve step
# (off = cd − ‖q‖², the same quantity ``residual_query_offsets`` computes
# standalone — the differential tests pin the two forms together). So a
# residual stage-1 scan stays a single LUT gather plus two offset adds.
# tests/test_residual_pq.py asserts this identity against exact L2 in fp32.


def residual_query_offsets(centroids: jax.Array, q: jax.Array) -> jax.Array:
    """off[q, b] = ‖c_b‖² − 2⟨q, c_b⟩ — the per-(query, partition) scalar of
    the residual ADC identity above. Equals ‖q − c_b‖² − ‖q‖²."""
    return (jnp.sum(centroids * centroids, -1)[None, :]
            - 2.0 * jnp.dot(q, centroids.T, precision=jax.lax.Precision.HIGHEST))


def residual_cross_terms(pq: PQCodebook, centroids_per_row: np.ndarray,
                         codes: np.ndarray, *, batch: int = 65536) -> np.ndarray:
    """cterm[n] = 2⟨c_n, decode(codes_n)⟩ — the per-slot, query-free term of
    the residual ADC identity; ``centroids_per_row`` is each row's assigned
    partition centroid [N, d]. Precomputed once at store-build time."""
    n = codes.shape[0]
    out = np.empty((n,), np.float32)
    for s in range(0, n, batch):
        recon = decode(pq, codes[s : s + batch])
        out[s : s + batch] = 2.0 * np.einsum(
            "nd,nd->n", np.asarray(centroids_per_row[s : s + batch], np.float32), recon)
    return out
