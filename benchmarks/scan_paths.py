"""Partition-scan backend comparison + parity gate (ISSUE 4).

Serves one η>0 LIRA store through the distributed engine with the two CPU-
runnable scan backends of serving/scan.py — ``ref`` (portable jnp) and
``interpret`` (the grid-batched Pallas kernels through the interpreter) — on
all three tiers (f32, quantized, residual), reporting latency per path and
ASSERTING parity: bit-identical distances, set-identical ids, identical
nprobe/overflow counters.

This is the CI tripwire for kernel/oracle drift in the scan layer, exactly
like the PR 3 coverage floor: run.py turns any raise into a bench-smoke
failure. Latency note: on CPU the interpreter is expected to lose to the jnp
path — the row exists to track the gap, not to win it; on TPU ``pallas``
compiles natively and the kernels are the fast path.

ISSUE 8: the kernel path now consumes the compact ``q_pad``/``lut_pad``
planes directly (scalar-prefetched qbuf gather, no per-slot host expansion);
the payload records the staged-operand accounting per tier and the stream-
tile autotune sweeps, and CI's perf ratchet compares the persisted
``ceiling_fracs`` against the committed snapshot.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from benchmarks import _harness as H
from repro.data import make_vector_dataset
from repro.launch.mesh import make_test_mesh
from repro.serving.engine import LiraEngine
from repro.serving.quantized import build_quantized_store

N, NQ, DIM, B, K = 10_000, 128, 64, 16, 10
ETA, SIGMA, SEED = 0.03, 0.3, 6
PQ_M, PQ_KS, RERANK = 8, 64, 8
NPROBE, TRAIN_FRAC, EPOCHS = 8, 0.3, 4
# cached artifacts bake in the full cfg/params/store, so the key must cover
# every build parameter — a constant edit must miss the stale pickle (same
# convention as quantized_scan's cache keys)
_DS_KEY = (f"scanpaths_n{N}_d{DIM}_B{B}_s{SEED}_eta{ETA}_m{PQ_M}_ks{PQ_KS}"
           f"_k{K}_r{RERANK}_np{NPROBE}_tf{TRAIN_FRAC}_e{EPOCHS}")


def _engines():
    ds = H._cached(
        f"ds_{_DS_KEY}",
        lambda: make_vector_dataset("sift-like", n=N, n_queries=NQ, dim=DIM,
                                    n_modes=B * 2, seed=SEED))

    def build():
        from repro.serving import BuildConfig

        eng = LiraEngine.build(
            make_test_mesh(), ds.base, BuildConfig(
                n_partitions=B, k=K, eta=ETA, train_frac=TRAIN_FRAC,
                epochs=EPOCHS, nprobe_max=NPROBE, tier="pq", pq_m=PQ_M,
                pq_ks=PQ_KS, rerank=RERANK))
        qs = build_quantized_store(
            jax.random.PRNGKey(1), eng.store["vectors"], eng.store["ids"],
            m=PQ_M, ks=eng.cfg.pq_ks, residual=True,
            centroids=eng.store["centroids"])
        return eng.cfg, eng.params, eng.store, qs

    cfg, params, store, qs = H._cached(f"eng_{_DS_KEY}", build)
    eng = LiraEngine(cfg=cfg, params=params, store=store, mesh=make_test_mesh())
    store_r = {**store, "codes": qs.codes, "codebooks": qs.codebooks,
               "cterm": qs.cterm}
    eng_r = LiraEngine(cfg=dataclasses.replace(cfg, tier="residual_pq"),
                       params=params, store=store_r, mesh=eng.mesh)
    return eng, eng_r, ds


def _scan_cost(cfg, tier_name: str, n_probes: float, nq: int):
    """Analytic (flops, bytes) for one serve call's scan stage — the work the
    measured wall time is divided into for roofline-relative rates. Per
    dispatched probe the scan touches one partition of ``capacity`` slots:

      f32:        2·cap·d flops (squared-L2 MACs), cap·d·dtype + cap·4 bytes
      pq:         2·cap·m ADC lookup-adds over uint8 codes, then an exact
                  rerank of rk = min(cap, rerank·k) shortlist rows; plus a
                  per-query LUT build of 2·m·ks·d flops / m·ks·4 bytes
      residual:   pq + the cterm plane (cap·4 bytes, cap adds)

    This is a lower-bound work model (top-k and scatter excluded), so the
    roofline fractions it yields are conservative."""
    cap, d, m, ks = cfg.capacity, cfg.dim, cfg.pq_m, cfg.pq_ks
    if tier_name == "f32":
        dtype_bytes = 2 if cfg.store_dtype == "bfloat16" else 4
        return (2.0 * cap * d * n_probes,
                (cap * d * dtype_bytes + cap * 4) * n_probes)
    rk = min(cap, cfg.rerank * cfg.k)
    flops = (2.0 * cap * m + 2.0 * rk * d) * n_probes + 2.0 * m * ks * d * nq
    bytes_ = (cap * m + rk * d * 4 + cap * 4) * n_probes + m * ks * 4 * nq
    if tier_name == "residual_pq":
        flops += cap * n_probes
        bytes_ += cap * 4 * n_probes
    return flops, bytes_


def run(emit):
    from benchmarks import roofline
    from repro.kernels import autotune
    from repro.serving import scan as serving_scan

    eng, eng_r, ds = _engines()
    q = ds.queries[:NQ]
    # tune the stream tiles for this store shape before jit warm-up so the
    # interpret path below bakes the winners in; sweeps land in the payload
    cap = int(eng.cfg.capacity)
    rk = min(cap, RERANK * K)
    autotune.autotune_l2_qbuf(cap, DIM, K, candidates=(128, 256), impl="interpret")
    autotune.autotune_pq_adc_qbuf(cap, PQ_M, PQ_KS, rk, candidates=(64, 128),
                                  impl="interpret")
    # stage-1 staged-operand accounting per tier: the compact plane + qbuf
    # indices the scalar-prefetch kernels stage vs the retired per-slot
    # host expansion (NQ=128 is already a pow2 jit bucket → q_row = NQ)
    q_cap = max(8, int(NQ * NPROBE / B * eng.cfg.q_cap_factor))
    qbuf_sds = jax.ShapeDtypeStruct((B, q_cap), "int32")
    staged_by_tier = {
        "f32": serving_scan.staged_operand_bytes(
            qbuf_sds, jax.ShapeDtypeStruct((NQ + 1, DIM), "float32")),
        "quantized": serving_scan.staged_operand_bytes(
            qbuf_sds, jax.ShapeDtypeStruct((NQ + 1, PQ_M, PQ_KS), "float32")),
    }
    staged_by_tier["residual"] = staged_by_tier["quantized"]
    mismatches = []
    payload_tiers = {}
    for tier, engine, tier_name in (("f32", eng, "f32"),
                                    ("quantized", eng, "pq"),
                                    ("residual", eng_r, "residual_pq")):
        results = {}
        rows = {}
        for impl in ("ref", "interpret"):
            engine.search(q, sigma=SIGMA, tier=tier_name, impl=impl)  # warm jit
            t0 = time.perf_counter()
            res = engine.search(q, sigma=SIGMA, tier=tier_name, impl=impl)
            dt = time.perf_counter() - t0
            d, ids, npb, ovf = (res.dists, res.ids, res.nprobe_eff,
                                res.overflow)
            results[impl] = (dt, d, ids, npb, ovf)
            # dispatched probes = σ-selected minus q_cap-dropped
            flops, bytes_ = _scan_cost(engine.cfg, tier_name,
                                       float(npb.sum()) - ovf, NQ)
            rows[impl] = {
                "seconds": dt, "qps": NQ / dt,
                "nprobe_mean": float(npb.mean()), "overflow": int(ovf),
                "dedup_hits": int(res.stats.dedup_hits),
                **roofline.ceiling_fracs(flops / dt, bytes_ / dt),
            }
            emit(f"scan_paths/{tier}_{impl}", dt * 1e6,
                 f"qps={NQ/dt:.0f};nprobe={npb.mean():.2f};overflow={ovf}")
        (t_r, d_r, i_r, np_r, o_r), (t_k, d_k, i_k, np_k, o_k) = \
            results["ref"], results["interpret"]
        bit_d = np.array_equal(d_r, d_k)
        same_i = all(
            set(i_r[r][np.isfinite(d_r[r])].tolist())
            == set(i_k[r][np.isfinite(d_k[r])].tolist())
            for r in range(NQ))
        same_ct = np.array_equal(np_r, np_k) and o_r == o_k
        emit(f"scan_paths/{tier}_parity", 0.0,
             f"dists_bit_identical={bit_d};ids_set_identical={same_i};"
             f"counters_identical={same_ct};kernel_over_ref=x{t_k/t_r:.2f}")
        if not (bit_d and same_i and same_ct):
            mismatches.append(tier)
        staged = staged_by_tier[tier]
        payload_tiers[tier] = {
            **rows, "parity": {"dists_bit_identical": bit_d,
                               "ids_set_identical": same_i,
                               "counters_identical": same_ct},
            "kernel_over_ref": t_k / t_r,
            "staged_operand_bytes": {
                **staged,
                "amplification_removed":
                    staged["expanded_bytes"] / staged["compact_bytes"]},
        }
    if mismatches:
        raise AssertionError(
            f"scan kernel/oracle drift on tier(s) {','.join(mismatches)}: "
            "serving/scan.py impls disagree — see scan_paths/*_parity rows")
    return {
        "suite": "scan_paths",
        "config": {"n": N, "n_queries": NQ, "dim": DIM, "partitions": B,
                   "k": K, "sigma": SIGMA, "eta": ETA, "pq_m": PQ_M,
                   "pq_ks": PQ_KS, "rerank": RERANK, "nprobe_max": NPROBE},
        "roofline_ceilings": {"peak_flops": roofline.PEAK,
                              "hbm_bytes_per_s": roofline.HBM},
        "tiers": payload_tiers,
        "autotune": autotune.records(),
    }


if __name__ == "__main__":
    run(lambda *a: print(*a))
