"""Quantized two-stage serving tier: PQ/ADC shortlist + exact f32 rerank.

The serve step's in-partition scan is memory-bandwidth bound: the f32 path
reads ``capacity · d · 4`` bytes per probed partition. This tier shrinks the
scan store 8–32× by scanning uint8 PQ codes instead (the HARMONY / LANNS
compressed-scan-then-rerank split):

  stage 0 (per query, once):  ADC LUT  [m, ks] subspace distance table;
  stage 1 (per probed partition): LUT scan over the partition's codes →
          shortlist of ``r·k`` candidate slots. The scan is backend-dispatched
          through ``serving/scan.py``: ``kernels.pq_adc_topk_qbuf`` fuses
          it over every dispatch bucket in one launch (Mosaic on TPU,
          interpretable anywhere); the jnp gather path is the portable
          reference and parity oracle;
  stage 2: exact f32 distances on the shortlist only → top-k, then the usual
          replica-aware ``dedup_topk`` local + cross-shard merges.

Two PQ modes share this pipeline:

  * non-residual (default): codebooks trained on raw vectors, so one LUT per
    query is valid across every partition — the shared-LUT fast case with no
    extra per-slot state;
  * residual (``residual=True``): codebooks trained on x − centroid[assign],
    which spends the whole code budget on the within-partition residual —
    the win on clustered data where centroids carry most of the norm. The
    cross terms that a per-partition LUT would normally absorb fold into a
    per-slot scalar plane ``cterm[b, n] = 2⟨c_b, decode(codes[b, n])⟩``
    (precomputed here at build time) plus a per-(query, partition) scalar
    added inside the serve step's scan — see the residual ADC identity in
    ``core/pq.py``. Stage 1 stays a single shared-LUT gather + offset adds.

The full-precision store stays resident as the rerank operand and as the
exact fallback/oracle path in both modes.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pq as pqmod


class QuantizedStore(NamedTuple):
    """PQ codes per partition slot + the shared codebooks.

    ``codes`` rows beyond a partition's fill are real encodings of the padding
    sentinel vectors; they are masked at scan time by ``ids < 0`` exactly like
    the f32 path, so no separate validity plane is needed.

    ``residual=True`` means codes encode x − centroid[assign] and ``cterm``
    holds the per-slot cross-term plane of the residual ADC identity
    (core/pq.py); non-residual stores leave ``cterm`` as None.
    """

    codes: jax.Array      # [B, capacity, m] uint8 (ks ≤ 256) / uint16
    codebooks: jax.Array  # [m, ks, d_sub] f32
    cterm: jax.Array | None = None  # [B, capacity] f32, residual mode only
    residual: bool = False

    @property
    def ks(self) -> int:
        return self.codebooks.shape[1]


# per-query subspace distance tables [Q, m, ks] from raw codebook arrays (the
# serve step holds codebooks as a plain array, not a PQCodebook)
adc_lut = pqmod.adc_lut_raw


def build_quantized_store(
    rng: jax.Array,
    vectors,              # [B, capacity, d] np/jax — the padded partition store
    ids,                  # [B, capacity] int32, -1 = padding
    *,
    m: int = 16,
    ks: int = 256,
    train_n: int = 32768,
    n_iters: int = 12,
    residual: bool = False,
    centroids=None,       # [B, d] — required when residual=True
) -> QuantizedStore:
    """Train PQ on a sample of the valid slots, encode every slot.

    ``ks`` is clamped to the number of valid training rows so tiny stores
    (tests, smoke configs) build without under-determined codebooks.

    With ``residual=True`` the codebooks are trained on (and codes encode)
    x − centroid[partition], and the per-slot cross-term plane ``cterm`` is
    precomputed so serve-time scans keep one shared LUT per query.
    """
    vec = np.asarray(vectors, np.float32)
    idv = np.asarray(ids)
    b, cap, d = vec.shape
    assert d % m == 0, f"dim {d} not divisible by pq_m={m}"
    flat = vec.reshape(-1, d)
    cents_rep = None
    if residual:
        assert centroids is not None, "residual PQ needs the partition centroids"
        cents_rep = np.repeat(np.asarray(centroids, np.float32), cap, axis=0)  # [B·cap, d]
        flat = flat - cents_rep
    rows = np.flatnonzero(idv.reshape(-1) >= 0)
    ks = int(min(ks, max(2, len(rows) // 2)))
    rng_sample, rng_train = jax.random.split(rng)
    if len(rows) > train_n:
        host = np.random.default_rng(int(jax.random.randint(rng_sample, (), 0, 2**31 - 1)))
        rows = host.choice(rows, train_n, replace=False)
    pq = pqmod.train_pq(rng_train, flat[rows], m=m, ks=ks, n_iters=n_iters)
    codes = pqmod.encode(pq, flat)  # [B·cap, m] narrow integer dtype
    cterm = None
    if residual:
        cterm = jnp.asarray(
            pqmod.residual_cross_terms(pq, cents_rep, codes).reshape(b, cap))
    return QuantizedStore(codes=jnp.asarray(codes.reshape(b, cap, m)),
                          codebooks=pq.codebooks, cterm=cterm, residual=residual)


def scan_store_bytes(store: dict) -> dict:
    """Bytes each scan path reads per full pass over the store (the quantized
    tier's raison d'être: this ratio is the bandwidth win)."""
    vec = store["vectors"]
    f32_bytes = vec.size * vec.dtype.itemsize
    out = {"f32": int(f32_bytes)}
    if "codes" in store:
        codes = store["codes"]
        q_bytes = codes.size * codes.dtype.itemsize
        if "cterm" in store:  # residual mode reads the offset plane too
            q_bytes += store["cterm"].size * store["cterm"].dtype.itemsize
        out["quantized"] = int(q_bytes)
        out["ratio"] = f32_bytes / max(1, q_bytes)
    return out
