"""Run one cell once: set-up, the measured window, the reference, the metrics.

Everything a cell is made of is found by name: its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<mix>.json``), the limits of the numbers that decide
``correct`` (``bench/limits/<cell>.json``: what a sound run reads depends on
both the index and the traffic) and each metric it reports
(``bench/metrics/<metric>.py``, a ``read(run)`` that returns a number, or
None where it finds nothing to read). Adding a configuration, a mix, a cell
or a metric is adding files and entries in ``BENCHMARK.json``.

A configuration runs under what it declares and nothing else: its
``dataset.metric`` (``l2`` or ``ip``) decides the reference's distances and
the probing copy the work is counted with (``lirabench/probing/<metric>.py``),
its ``dataset.queries`` the query distribution (``corpus.py``). A metric,
dtype or query distribution the benchmark does not implement is refused
before set-up (``check_declared``). A cell's ``chips`` sets the mesh, (1,
chips) over ("data", "model").

Order of a run:

1. set-up (``setup_s``): the corpus on the device, the index loaded, every
   serve step the traffic can use compiled, and one step executed at the
   largest batch bucket. On a checkout's first run the index is built and
   kept first; that build is logged on its own line and left out of
   ``setup_s``;
2. the window, with a fresh metrics registry on the engine; with
   ``--trace 1`` also the program's span tracer and the JAX profiler;
3. the peak of device memory is read and the program's state is freed;
4. the reference: exact distances of every answer the window returned,
   exact k-NN for recall and for the share of true neighbours missed (kept
   per corpus once computed), and the work each step had to do, from the
   benchmark's own probing;
5. the metrics, then the numbers compared with their limits.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from lirabench import corpus, index_cache, reference, trace_reduce, traffic, work


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class Step:
    """One engine.search call in the window."""
    t0: float
    t1: float
    queries: np.ndarray
    bucket: int
    overflow: int
    nprobe: np.ndarray


@dataclasses.dataclass
class RunContext:
    """What a metric reader may read."""
    cell: str
    config: dict
    mix: dict
    seconds: float
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    latencies_ms: Optional[np.ndarray] = None
    recall: float = 0.0
    steps: list = dataclasses.field(default_factory=list)
    registry: object = None
    tracer: object = None
    trace: Optional[dict] = None
    work: list = dataclasses.field(default_factory=list)
    peaks: Optional[dict] = None


class CompileCounter:
    """XLA backend compiles and their seconds, from JAX's own events."""

    def __init__(self):
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += duration


def enable_compile_cache(root: pathlib.Path) -> str:
    path = str(root / "bench" / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def find(entries: list, name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"{name!r} is not in BENCHMARK.json")


def reader(root: pathlib.Path, name: str) -> Callable:
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}

    def applies(m):
        return cell in m["workloads"] if "workloads" in m else m["moves"] in names

    return [m for m in bench["per_layer"] if applies(m)]


def buckets_for(engine, mix: dict) -> list:
    if mix["loop"] == "closed":
        return [engine._batch_bucket(int(mix["batch"]))]
    top = int(mix["frontend"]["max_batch"])
    return sorted({engine._batch_bucket(n) for n in range(1, top + 1)})


def warm_up(engine, buckets: list, k: int, pool_np: np.ndarray) -> tuple:
    """Compile every serve step the window can use (the engine's own jitted
    steps, so its calls find them) and execute the largest once. Returns
    (seconds per phase, {bucket: HLO instruction → op_name})."""
    from repro.serving import SearchRequest

    split, hlo = {}, {}
    dim = engine.cfg.dim
    for b in buckets:
        t = time.perf_counter()
        fn, _, _ = engine.serve_fn(b, engine.sigma, engine.cfg.tier, None, k)
        with engine.mesh:
            compiled = fn.lower(engine.params, engine.store, jnp.zeros((b, dim), jnp.float32),
                                jnp.asarray(np.ones((b,), bool))).compile()
        hlo[b] = trace_reduce.hlo_op_names(compiled.as_text())
        split[f"compile bucket={b}"] = time.perf_counter() - t
    t = time.perf_counter()
    engine.search(SearchRequest(queries=pool_np[:buckets[-1]], k=k))
    split[f"warm bucket={buckets[-1]}"] = time.perf_counter() - t
    return split, hlo


def instrument(engine, steps: list, answers_hook=None) -> None:
    """Record each engine.search call of the window as a Step, with a
    profiler annotation around it. ``answers_hook(request, result)``, for the
    controls (``faults.py``), alters what the engine returned before anyone
    sees it."""
    orig = engine.search

    def search(req, *args, **kwargs):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.search"):
            res = orig(req, *args, **kwargs)
        if answers_hook is not None:
            res = answers_hook(req, res)
        steps.append(Step(t0, time.monotonic(), np.asarray(req.queries), res.stats.bucket,
                          int(res.overflow), np.asarray(res.nprobe_eff)))
        return res

    engine.search = search


MAX_BATCHES = 4096


def closed_loop(engine, mix: dict, pool_np: np.ndarray, seed: int, seconds: float) -> dict:
    """One caller, batches of ``batch`` queries, until the first batch
    boundary at or after ``seconds``."""
    from repro.serving import SearchRequest

    batch, k = int(mix["batch"]), int(mix["k"])
    plan = traffic.query_rows(mix, len(pool_np), batch * MAX_BATCHES, seed)
    rows_all, ids, dists = [], [], []
    b = 0
    t0 = time.monotonic()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        while True:
            if b == MAX_BATCHES:
                raise RuntimeError(f"more than {MAX_BATCHES} batches in the window")
            rows = plan[b * batch:(b + 1) * batch]
            res = engine.search(SearchRequest(queries=pool_np[rows], k=k))
            rows_all.append(rows)
            ids.append(res.ids)
            dists.append(res.dists)
            b += 1
            t1 = time.monotonic()
            if t1 - t0 >= seconds:
                break
    rows = np.concatenate(rows_all)
    return {"rows": rows, "ids": np.concatenate(ids), "dists": np.concatenate(dists),
            "t0": t0, "window_s": t1 - t0, "attempted": len(rows), "failed": 0, "missing": 0,
            "latencies_ms": None, "lateness_s": None}


def open_loop(engine, mix: dict, pool_np: np.ndarray, seed: int, seconds: float) -> dict:
    """Single-query requests through the serving front-end at due times
    fixed in advance. Each request is submitted as soon as the loop sees it
    due, stamped with its due time, so its latency runs from when it was due;
    the run waits up to ``drain_s`` past the window for answers due in it."""
    from repro.configs.base import FrontendConfig
    from repro.serving import SearchRequest

    k = int(mix["k"])
    due = traffic.due_times(mix, seconds, seed)
    rows = traffic.query_rows(mix, len(pool_np), len(due), seed)
    fe = engine.attach_frontend(FrontendConfig(**mix["frontend"]))
    deadline = mix.get("deadline_ms")
    pend: list = []
    late = np.zeros(len(due))
    give_up = seconds + float(mix["drain_s"])
    i = 0
    t0 = time.monotonic()
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            while True:
                now = time.monotonic() - t0
                while i < len(due) and due[i] <= now:
                    late[i] = now - due[i]
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        pend.append(fe.submit(SearchRequest(queries=pool_np[rows[i]], k=k,
                                                            deadline_ms=deadline),
                                              t_arrival=t0 + due[i]))
                    i += 1
                    now = time.monotonic() - t0
                with jax.profiler.TraceAnnotation("bench.poll"):
                    fe.poll()
                now = time.monotonic() - t0
                if (i == len(due) and fe.depth() == 0) or now > give_up:
                    break
                wake = [due[i]] if i < len(due) else []
                nd = fe.next_deadline()
                if nd is not None:
                    wake.append(nd - t0)
                pause = min(wake) - now if wake else 0.001
                if pause > 0:
                    with jax.profiler.TraceAnnotation("bench.idle"):
                        time.sleep(min(pause, 0.05))
        t1 = time.monotonic()
    finally:
        engine.frontend = None
    n = len(due)
    ids = np.full((n, k), -1, np.int32)
    dists = np.full((n, k), np.inf, np.float32)
    lat = np.full(n, (t1 - t0) * 1e3)       # never answered: as late as the run
    answered = np.zeros(n, bool)
    failed = missing = n - len(pend)
    for j, p in enumerate(pend):
        if not p.done():
            missing += 1
            failed += 1
            continue
        r = p.result()
        if r.stats.shed:
            failed += 1
            lat[j] = (t1 - t0 - due[j]) * 1e3
            continue
        answered[j] = True
        ids[j], dists[j] = r.ids[0], r.dists[0]
        lat[j] = r.stats.latency_ms
    return {"rows": rows[answered], "ids": ids[answered], "dists": dists[answered],
            "t0": t0, "window_s": t1 - t0, "attempted": n, "failed": failed, "missing": missing,
            "latencies_ms": lat, "lateness_s": late[:i], "due": due}


def ground_truth(root: pathlib.Path, cfg: dict, fp: str, pool_np, base, k: int) -> np.ndarray:
    """Exact k-NN ids of the whole query pool under the configuration's
    metric, kept per corpus (and per metric and pool, where the pool is not
    the tail of the corpus's own draw or the metric is not squared L2)."""
    ds = cfg["dataset"]
    stem = f"{fp}_k{k}"
    if ds["metric"] != "l2" or "queries" in ds:
        stem = f"{fp}_{ds['metric']}_{corpus.fingerprint(pool_np)}_k{k}"
    path = root / "bench" / ".cache" / "gt" / cfg["name"] / f"{stem}.npy"
    if path.exists():
        return np.load(path)
    _, ids = reference.knn(pool_np, base, k, metric=ds["metric"])
    path.parent.mkdir(parents=True, exist_ok=True)
    np.save(path, ids)
    return ids


def capture_trace(root: pathlib.Path, cell: str) -> pathlib.Path:
    d = root / "bench" / ".cache" / "trace" / cell
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    return d


def read_trace(d: pathlib.Path, step_scopes: list) -> dict:
    import gzip

    path = sorted(d.rglob("*.xplane.pb"))[-1]
    ex = trace_reduce.extract(str(path), step_scopes)
    with gzip.open(d / "extracted.json.gz", "wt") as fh:
        json.dump(ex, fh)
    return trace_reduce.reduce(ex)


def check_declared(cfg: dict) -> None:
    """Refuse a configuration that declares what the benchmark does not
    implement, naming the key and the value: a metric other than ``l2`` or
    ``ip``, one with no ``probing/<metric>.py``, a dtype other than float32,
    an unknown query distribution, an ``index.metric`` other than the
    dataset's (the metric is declared once, under ``dataset``)."""
    ds = cfg["dataset"]
    reference.check_metric(ds.get("metric"))
    if cfg["index"].get("metric", ds["metric"]) != ds["metric"]:
        raise ValueError(f"index.metric {cfg['index']['metric']!r} differs from "
                         f"dataset.metric {ds['metric']!r}")
    corpus.check(ds)
    work.probing(ds["metric"])


@dataclasses.dataclass
class Prepared:
    """A cell after set-up: its files, the corpus and the warm engine."""
    cfg: dict
    mix: dict
    devices: list
    engine: object
    base: object
    base_np: np.ndarray
    pool_np: np.ndarray
    fp: str
    split: dict
    hlo: dict
    setup_s: float
    compiles: CompileCounter


def prepare(root: pathlib.Path, bench: dict, cell_name: str, devices: list) -> Prepared:
    """Set-up on the cell's first ``chips`` of ``devices``: the corpus on the
    device, the index loaded or built, every serve step the mix can use
    compiled, one executed. What the configuration declares is checked
    first."""
    from jax.sharding import Mesh

    cell = find(bench["workloads"], cell_name)
    cfg = load_json(root / find(bench["configs"], cell["config"])["file"])
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    check_declared(cfg)
    chips = int(cell.get("chips", 1))
    if len(devices) < chips:
        raise RuntimeError(f"{cell_name} needs {chips} chips, {len(devices)} given")
    devices = list(devices[:chips])
    compiles = CompileCounter()
    split: dict = {}
    t_setup = time.perf_counter()
    t = time.perf_counter()
    base, pool, learn = corpus.make_corpus(cfg["dataset"])
    jax.block_until_ready(base)
    split["data generate"] = time.perf_counter() - t
    t = time.perf_counter()
    base_np, pool_np = np.asarray(base), np.asarray(pool)
    fp = corpus.fingerprint(base_np)
    split["data to host"] = time.perf_counter() - t
    mesh = Mesh(np.array(devices).reshape(1, len(devices)), ("data", "model"))
    engine, info = index_cache.load_or_build(root, cfg, base, base_np, learn, mesh, fp,
                                             devices[0].device_kind, log)
    split["load read"], split["load place"] = info["read_s"], info["place_s"]
    warm, hlo = warm_up(engine, buckets_for(engine, mix), int(mix["k"]), pool_np)
    split.update(warm)
    # a checkout's one index build is not set-up the runs repeat
    setup_s = time.perf_counter() - t_setup - info.get("build_s", 0.0)
    return Prepared(cfg, mix, devices, engine, base, base_np, pool_np, fp, split, hlo, setup_s,
                    compiles)


def run_cell(root: pathlib.Path, bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, devices: list, *, fault=None) -> dict:
    """One run of one cell on the first ``chips`` of ``devices``; returns the
    result object (the last line the benchmark prints). ``fault``
    (``faults.Fault``), for the controls only, breaks the timed path
    underneath."""
    with fault.patch() if fault is not None else contextlib.nullcontext():
        return _run_cell(root, bench, cell_name, seed, seconds, trace, devices, fault)


def _run_cell(root, bench, cell_name, seed, seconds, trace, devices, fault) -> dict:
    p = prepare(root, bench, cell_name, devices)
    if fault is not None:
        fault.engine(p.engine, p.base_np)
    cfg, mix, devices, engine, base, base_np, pool_np, fp = (
        p.cfg, p.mix, p.devices, p.engine, p.base, p.base_np, p.pool_np, p.fp)
    metric = cfg["dataset"]["metric"]
    split, hlo, setup_s, compiles = p.split, p.hlo, p.setup_s, p.compiles
    k = int(mix["k"])
    del p

    from repro.obs.metrics import MetricsRegistry
    from repro.obs.profiling import profile_capture
    from repro.obs.trace import Tracer

    ctx = RunContext(cell=cell_name, config=cfg, mix=mix, seconds=seconds, setup_s=setup_s)
    ctx.registry = engine.metrics = MetricsRegistry()
    if trace:
        ctx.tracer = engine.tracer = Tracer()
    instrument(engine, ctx.steps, fault.answers if fault is not None else None)
    n0, s0 = compiles.n, compiles.s
    tdir = capture_trace(root, cell_name) if trace else None
    with profile_capture(str(tdir) if trace else None):
        loop = closed_loop if mix["loop"] == "closed" else open_loop
        out = loop(engine, mix, pool_np, seed, seconds)
    window_compiles = (compiles.n - n0, compiles.s - s0)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(st.get("peak_bytes_in_use", 0)) for st in stats)

    # the program's state goes before the reference runs
    params = jax.tree.map(np.asarray, engine.params)
    cents = np.asarray(engine.store["centroids"])
    live = np.asarray(engine.store["occupancy"]).sum(1)
    ecfg = engine.cfg
    sigma = float(engine.sigma)
    del engine
    gc.collect()

    for name, s in split.items():
        log(f"setup {name}: {s:.3f} s")
    log(f"setup total: {setup_s:.3f} s ({compiles.n - window_compiles[0]} compiles "
        f"before the window)")
    log(f"window: {out['window_s']:.3f} s, {len(ctx.steps)} steps, compiles in the window: "
        f"{window_compiles[0]} ({window_compiles[1]:.3f} s)")
    if out["lateness_s"] is not None and len(out["lateness_s"]):
        lt = out["lateness_s"]
        log(f"generator lateness: mean {lt.mean() * 1e3:.3f} ms, p95 "
            f"{np.percentile(lt, 95) * 1e3:.3f} ms, max {lt.max() * 1e3:.3f} ms "
            f"over {len(lt)} submits")
    log(f"device memory: peak_bytes_in_use={peak} (fullest of {len(devices)}) "
        f"bytes_limit={stats[0].get('bytes_limit')}")

    scan = {"dim": ecfg.dim}
    if ecfg.tier in ("pq", "residual_pq"):
        scan.update(pq_m=ecfg.pq_m, pq_ks=ecfg.pq_ks, residual=ecfg.tier == "residual_pq")
    for j, st in enumerate(ctx.steps):
        mask = work.probe_mask(params, cents, st.queries, sigma, ecfg.nprobe_max, metric)
        ctx.work.append(work.step_work(mask, live, scan))
        log(f"step {j}: rows={len(st.queries)} bucket={st.bucket} "
            f"ms={(st.t1 - st.t0) * 1e3:.3f} overflow={st.overflow} "
            f"occupied_partitions={int(mask.any(0).sum())} "
            f"nprobe_eff={float(st.nprobe.mean()):.3f}")

    gt = ground_truth(root, cfg, fp, pool_np, base, k)
    answered = len(out["rows"]) > 0
    ctx.recall = reference.recall(out["ids"], gt[out["rows"]]) if answered else 0.0
    gap = reference.dist_gap(pool_np[out["rows"]], out["ids"], out["dists"], base_np,
                             metric=metric)
    bad = reference.bad_answers(out["ids"], out["dists"], len(base_np)) + out["missing"]
    limits = load_json(root / "bench" / "limits" / f"{cell_name}.json")
    checks = {"dist_gap": {"value": gap, "limit": limits["dist_gap"]},
              "bad_answers": {"value": bad, "limit": limits["bad_answers"]},
              "recall_miss": {"value": 1.0 - ctx.recall, "limit": limits["recall_miss"]}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    ctx.window_s = out["window_s"]
    ctx.attempted, ctx.failed = out["attempted"], out["failed"]
    ctx.latencies_ms = out["latencies_ms"]
    result_device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                     "count": len(jax.devices()),
                     "memory_peak_bytes": peak}
    if trace:
        ctx.trace = read_trace(tdir, [hlo[st.bucket] for st in ctx.steps])
        ctx.peaks = work.peaks(root / "bench", devices[0].device_kind)
        result_device.update(busy_s=ctx.trace["busy_s"], window_s=ctx.trace["window_s"])
        log(f"trace: window {ctx.trace['window_s']:.6f} s, busy {ctx.trace['busy_s']:.6f} s, "
            f"by scope {json.dumps(ctx.trace['by_scope'])}, by kernel "
            f"{json.dumps(ctx.trace['by_kernel'])}")
    metrics = {}
    for m in metrics_for(bench, cell_name, trace):
        v = reader(root, m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(ctx.attempted),
              "failed": int(ctx.failed), "metrics": metrics, "device": result_device}
    if trace:
        result["breakdown"] = {"device_ops": ctx.trace["top_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["checks"] = checks
    return result


def report(result: dict) -> None:
    """Each number compared beside its limit, last on standard error, then
    the result line, last on standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
