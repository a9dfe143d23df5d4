#!/usr/bin/env python3
"""Chip smoke test: the LIRA serve path end to end on a TPU, at SIFT1M scale.

One process drives the system through the entry points a user calls:
``LiraEngine.build`` → ``LiraEngine.search`` → ``search_one`` through an
attached ``ServingFrontend`` on the real clock.

* Data: the shape of ann-benchmarks ``sift-128-euclidean`` (the paper's
  headline dataset): 1,000,000 base vectors and 1,000 queries, 128-d f32,
  L2, generated from ``--seed`` by ``make_vector_dataset``.
* Index: lira-ann / lira-ann-q widths (``configs/lira_ann.py``): 1024
  partitions, k=100, nprobe_max=64, η=0.03, residual PQ with m=16, ks=256 and
  a 4·k rerank. The f32 plane stays resident, so the same engine serves both
  tiers. One cut: ``capacity`` comes from the build (lira-ann's 65,536 slots
  per partition are 34 GB of f32, past the chip's 16 GB).
* Traffic: batches of 64 and 128 queries in both tiers, through
  ``impl="pallas"`` (the Mosaic kernels) and then ``impl="ref"`` (jnp/XLA),
  then single queries through the front-end.

Checks (any failure exits nonzero and prints no result line): pallas agrees
with ref, recall@100 against a brute-force f32 reference at HIGHEST precision
meets RECALL_FLOOR, the residual_pq tier is within TIER_GAP of the f32 tier,
and every returned id lies in the corpus.

    python chip_smoke.py              # one chip, everything above
    python chip_smoke.py --chips 4    # only the model-sharded serve step on a
                                      # (data=1, model=4) mesh vs model=1

The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_BASE, N_QUERIES, DIM, K = 1_000_000, 1_000, 128, 100
BATCHES = (64, 128)   # the rerank gathers [b_loc, q_cap, 4k, d] f32: 3.4 GB at 128
TIERS = ("f32", "residual_pq")

# recall@100 of the f32 tier over all 1,000 queries, against exact kNN. CPU
# runs of the same build (ref scan, same generator) measured 0.968 at 200k
# vectors / 256 partitions and 0.978 at 500k / 512; this script measured
# 0.978 on a TPU v5e at 1M / 1024.
RECALL_FLOOR = 0.90
# How much recall the residual_pq tier may lose to its ADC shortlist of
# rerank·k = 400 slots per probed partition. Partitions are skewed (the
# largest holds ~12× the mean), so in the partitions where neighbours
# crowd 400 slots are a few percent of the partition: the same CPU runs
# measured a gap of 0.023 at 200k and 0.050 at 500k, and this script 0.065
# on a TPU v5e at 1M. 0.02 holds only on small stores.
TIER_GAP = 0.10
# pallas vs ref. The repo's CPU contract holds the two bit-identical; on the
# TPU the Mosaic MXU dot and XLA's dot (both at HIGHEST precision) may round
# the last bits differently. So distances agree within DIST_RTOL·(‖q‖² + max‖c‖²) — a few
# f32 ulps of the terms the expansion ‖q‖² − 2q·c + ‖c‖² cancels — and ids
# agree as sets except where the two distance lists tie within that tolerance
# at the k-th place. The ADC shortlist can also flip a candidate at its r·k
# boundary on a last-bit difference; MAX_SHORTLIST_FLIPS bounds the share of
# rows where the residual_pq sets differ for that reason.
DIST_RTOL = 1e-5
MAX_SHORTLIST_FLIPS = 0.01


def log(msg: str) -> None:
    print(msg, flush=True)


FAILED: list[str] = []   # every phase runs; any failed check fails the run


def check(ok: bool, what: str) -> None:
    log(f"check {'ok  ' if ok else 'FAIL'} {what}")
    if not ok:
        FAILED.append(what)


def compile_counter():
    """Counts XLA backend compiles and their seconds from JAX's own event."""
    import jax

    stats = {"n": 0, "s": 0.0}

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["n"] += 1
            stats["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return stats


def make_data(seed: int):
    from repro.data import make_vector_dataset

    t = time.perf_counter()
    ds = make_vector_dataset("sift-128-euclidean", n=N_BASE, n_queries=N_QUERIES,
                             dim=DIM, seed=seed)
    log(f"phase data: {N_BASE}x{DIM} base + {N_QUERIES} queries f32 "
        f"in {time.perf_counter() - t:.3f}s")
    return ds


def build(mesh, base, seed: int):
    from repro.configs.lira_ann import CONFIG_QUANTIZED
    from repro.serving import BuildConfig, LiraEngine

    t = time.perf_counter()
    engine = LiraEngine.build(mesh, base, BuildConfig(
        n_partitions=CONFIG_QUANTIZED.n_partitions, k=K, eta=0.03,
        nprobe_max=CONFIG_QUANTIZED.nprobe_max, tier="residual_pq",
        pq_m=CONFIG_QUANTIZED.pq_m, pq_ks=CONFIG_QUANTIZED.pq_ks,
        rerank=CONFIG_QUANTIZED.rerank, impl="pallas", seed=seed))
    cfg = engine.cfg
    store_bytes = sum(a.size * a.dtype.itemsize for a in engine.store.values())
    log(f"phase build: {time.perf_counter() - t:.3f}s; partitions={cfg.n_partitions} "
        f"k={cfg.k} nprobe_max={cfg.nprobe_max} eta={cfg.eta} tier={cfg.tier} "
        f"pq_m={cfg.pq_m} pq_ks={cfg.pq_ks} rerank={cfg.rerank}")
    log(f"cut: capacity {CONFIG_QUANTIZED.capacity} (lira-ann) -> {cfg.capacity} "
        f"(largest partition of this build, rounded up to whole scan tiles); "
        f"store {store_bytes} bytes on device")
    return engine


def ground_truth(queries, base):
    from repro.core import ground_truth as gt

    t = time.perf_counter()
    _, ids = gt.exact_knn(queries, base, K, batch=250)
    log(f"phase ground truth: exact kNN@{K} of {len(queries)} queries over "
        f"{len(base)} vectors (f32, HIGHEST) in {time.perf_counter() - t:.3f}s")
    return ids


def recall(ids, gt_ids) -> float:
    hits = sum(len(set(r[r >= 0].tolist()) & set(g.tolist())) for r, g in zip(ids, gt_ids))
    return hits / gt_ids.size


def ids_in_corpus(res) -> bool:
    import numpy as np

    finite = np.isfinite(res.dists)
    return bool(((res.ids >= 0) & (res.ids < N_BASE))[finite].all()
                and (res.ids[~finite] == -1).all())


def agreement(a, b, q, c_sq_max):
    """(rows with bit-identical dists, rows whose id sets differ beyond a
    k-th-place tie, max |Δdist| / tolerance) between two results."""
    import numpy as np

    tol = DIST_RTOL * ((q * q).sum(1) + c_sq_max)                      # [nq]
    da, db = np.sort(a.dists, 1), np.sort(b.dists, 1)
    both = np.isfinite(da) & np.isfinite(db)
    diff = np.zeros_like(da)
    diff[both] = np.abs(da[both] - db[both])
    rel = diff.max(1) / tol
    bitwise = int((a.dists == b.dists).all(1).sum())
    differ = 0
    for r in range(len(q)):
        sa, sb = set(a.ids[r].tolist()), set(b.ids[r].tolist())
        if sa == sb:
            continue
        kth = max(da[r, -1], db[r, -1])
        extra = [(a.ids[r] == i).argmax() for i in sa - sb]
        near = all(a.dists[r, j] >= kth - tol[r] for j in extra)
        differ += int(not near)
    return bitwise, differ, float(rel.max())


def serve_memory(engine, nq: int, tier: str, impl: str) -> str:
    import jax.numpy as jnp
    import numpy as np

    fn, _, _ = engine.serve_fn(engine._batch_bucket(nq), engine.sigma, tier, impl, K)
    bucket = engine._batch_bucket(nq)
    with engine.mesh:
        mem = fn.lower(engine.params, engine.store,
                       jnp.zeros((bucket, DIM), jnp.float32),
                       jnp.asarray(np.ones(bucket, bool))).compile().memory_analysis()
    return (f"arguments={mem.argument_size_in_bytes} outputs={mem.output_size_in_bytes} "
            f"temp={mem.temp_size_in_bytes} aliased={mem.alias_size_in_bytes} "
            f"code={mem.generated_code_size_in_bytes}")


def one_chip(args, jax, device, compiles) -> None:
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import FrontendConfig
    from repro.serving import SearchRequest

    ds = make_data(args.seed)
    engine = build(Mesh(np.array([device]).reshape(1, 1), ("data", "model")),
                   ds.base, args.seed)
    gt_ids = ground_truth(ds.queries, ds.base)
    c_sq_max = float((ds.base.astype(np.float64) ** 2).sum(1).max())
    log(f"compiles so far: {compiles['n']} ({compiles['s']:.3f}s)")

    batches = {64: slice(0, 64), 128: slice(64, 192)}
    served = {}
    for impl in ("pallas", "ref"):
        for tier in TIERS:
            for nq, rows in batches.items():
                req = SearchRequest(queries=ds.queries[rows], tier=tier, impl=impl, k=K)
                n0, t = compiles["n"], time.perf_counter()
                res = engine.search(req)
                first = time.perf_counter() - t
                t = time.perf_counter()
                warm = engine.search(req)
                warm_s = time.perf_counter() - t
                served[impl, tier, nq] = res
                log(f"serve {impl:6s} {tier:11s} batch={nq:3d} bucket={res.stats.bucket} "
                    f"first={first:.3f}s (compiles {compiles['n'] - n0}) warm={warm_s:.4f}s "
                    f"overflow={res.overflow} nprobe_eff={float(res.nprobe_eff.mean()):.2f} "
                    f"dedup_hits={res.stats.dedup_hits}")
                check(np.array_equal(res.ids, warm.ids) and np.array_equal(res.dists, warm.dists),
                      f"{impl}/{tier}/{nq}: a repeated batch returns the same answer")
                check(ids_in_corpus(res), f"{impl}/{tier}/{nq}: every id lies in the corpus")
                if impl == "pallas":
                    log(f"memory {tier} bucket={res.stats.bucket}: "
                        f"{serve_memory(engine, nq, tier, impl)}")

    for tier in TIERS:
        for nq, rows in batches.items():
            bitwise, differ, rel = agreement(served["pallas", tier, nq], served["ref", tier, nq],
                                             ds.queries[rows], c_sq_max)
            log(f"pallas vs ref {tier} batch={nq}: {bitwise}/{nq} rows bit-identical, "
                f"{differ} rows with id sets differing beyond a k-th-place tie, "
                f"max |d_pallas - d_ref| = {rel:.3f} x tolerance")
            check(rel <= 1.0, f"{tier}/{nq}: pallas distances within tolerance of ref")
            allowed = 0 if tier == "f32" else math.ceil(MAX_SHORTLIST_FLIPS * nq)
            check(differ <= allowed, f"{tier}/{nq}: pallas ids match ref ({differ} <= {allowed})")

    rec = {}
    for tier in TIERS:
        t = time.perf_counter()
        ids = np.concatenate([
            engine.search(SearchRequest(queries=ds.queries[s:s + 128], tier=tier,
                                        impl="pallas", k=K)).ids
            for s in range(0, N_QUERIES, 128)])
        rec[tier] = recall(ids, gt_ids)
        log(f"recall@{K} {tier} (pallas, {N_QUERIES} queries in batches of 128): "
            f"{rec[tier]:.4f} in {time.perf_counter() - t:.3f}s")
    check(rec["f32"] >= RECALL_FLOOR, f"f32 recall@{K} {rec['f32']:.4f} >= {RECALL_FLOOR}")
    check(rec["residual_pq"] >= rec["f32"] - TIER_GAP,
          f"residual_pq recall within {TIER_GAP} of f32")

    engine.attach_frontend(FrontendConfig(max_batch=8, max_wait_ms=2.0, max_queue=64))
    try:
        batch = served["pallas", "residual_pq", 64]
        for i in range(4):   # each costs a full residual_pq scan (~16 s on a v5e)
            t = time.perf_counter()
            one = engine.search_one(SearchRequest(queries=ds.queries[i], k=K))
            log(f"search_one #{i} via front-end: {time.perf_counter() - t:.4f}s "
                f"tier={one.stats.tier} impl={one.stats.impl} bucket={one.stats.bucket} "
                f"overflow={one.overflow}")
            check(ids_in_corpus(one), f"search_one #{i}: every id lies in the corpus")
            if one.overflow == 0 and batch.overflow == 0:
                check(set(one.ids[0].tolist()) == set(batch.ids[i].tolist()),
                      f"search_one #{i} matches the same query served in the 64-batch")
    finally:
        engine.frontend = None

    stats = device.memory_stats() or {}
    log(f"overflow rate: {engine.overflow_rate():.6f}")
    log(f"compiles: {compiles['n']} ({compiles['s']:.3f}s)")
    log(f"device memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")


def four_chips(args, jax, devices, compiles) -> None:
    """Only the model-sharded serve step and what it is compared with: the
    same index served on a (data=1, model=4) mesh and on the first chip."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.serving import LiraEngine, SearchRequest

    ds = make_data(args.seed)
    engine1 = build(Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model")),
                    ds.base, args.seed)
    t = time.perf_counter()
    engine4 = LiraEngine(cfg=engine1.cfg, params=engine1.params, store=engine1.store,
                         mesh=Mesh(np.array(devices).reshape(1, 4), ("data", "model")),
                         sigma=engine1.sigma).place()
    jax.block_until_ready(engine4.store)
    log(f"phase place: store sharded over model=4 in {time.perf_counter() - t:.3f}s")
    total = sum(a.size * a.dtype.itemsize for a in engine4.store.values())
    for d in devices:
        held = sum(s.data.size * s.data.dtype.itemsize for a in engine4.store.values()
                   for s in a.addressable_shards if s.device == d)
        log(f"store bytes on {d}: {held} of {total} ({held / total:.3f}); "
            f"bytes_in_use={(d.memory_stats() or {}).get('bytes_in_use')}")
        check(0.2 <= held / total <= 0.3, f"{d} holds about a quarter of the store")

    batches = {64: slice(0, 64), 128: slice(64, 192)}
    for tier in TIERS:
        for nq, rows in batches.items():
            req = SearchRequest(queries=ds.queries[rows], tier=tier, impl="pallas", k=K)
            r1 = engine1.search(req)
            t = time.perf_counter()
            r4 = engine4.search(req)
            first = time.perf_counter() - t
            t = time.perf_counter()
            engine4.search(req)
            log(f"serve model=4 {tier:11s} batch={nq:3d} first={first:.3f}s "
                f"warm={time.perf_counter() - t:.4f}s "
                f"overflow={r4.overflow} (model=1: {r1.overflow}) "
                f"dedup_hits={r4.stats.dedup_hits} (model=1: {r1.stats.dedup_hits})")
            check(np.array_equal(r1.dists, r4.dists),
                  f"{tier}/{nq}: model=4 dists bit-identical to model=1")
            check(all(set(a.tolist()) == set(b.tolist()) for a, b in zip(r1.ids, r4.ids)),
                  f"{tier}/{nq}: model=4 ids set-identical to model=1")
    log(f"compiles: {compiles['n']} ({compiles['s']:.3f}s)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the model-sharded serve path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); nothing to run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    from repro.launch import compile_cache

    log(f"device: {devices[0].device_kind} x{len(devices)}; jax {jax.__version__}; "
        f"compile cache {compile_cache.enable(ROOT)}")
    compiles = compile_counter()
    t = time.perf_counter()
    if args.chips == 4:
        four_chips(args, jax, devices, compiles)
    else:
        one_chip(args, jax, devices[0], compiles)
    log(f"total: {time.perf_counter() - t:.3f}s")
    if FAILED:
        log(f"{len(FAILED)} check(s) failed: {'; '.join(FAILED)}")
        return 1
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
