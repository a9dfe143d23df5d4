"""Benchmark driver: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows (and tees them to results/bench.csv).
Suites whose ``run`` returns a dict produce a per-PR perf snapshot:
``--json-out DIR`` writes each as ``DIR/BENCH_<suite>.json``, stamped with
``schema_version`` so downstream trajectory tooling can detect payload shape
changes (serving, scan_paths and quantized_scan all snapshot; the kernel
suites carry roofline-relative ops/s + bytes/s). ``--metrics-out FILE``
additionally dumps the process metrics registry (repro.obs) as a text
exposition, and ``--profile-dir DIR`` wraps the whole run in a jax.profiler
capture for TensorBoard (README "Observability").

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig7] [--json-out .]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

# bump when the shape of any BENCH_*.json payload changes incompatibly
SCHEMA_VERSION = 1

SUITES = [
    ("eval_merge", "benchmarks.eval_merge"),
    ("quantized_scan", "benchmarks.quantized_scan"),
    ("scan_paths", "benchmarks.scan_paths"),
    ("serving", "benchmarks.serving_frontend"),
    ("churn", "benchmarks.churn"),
    ("cluster", "benchmarks.cluster"),
    ("fig2", "benchmarks.fig2_motivation"),
    ("fig11", "benchmarks.fig11_convergence"),
    ("table1", "benchmarks.table1_vary_k"),
    ("fig7", "benchmarks.fig7_8_tradeoff"),
    ("fig13", "benchmarks.fig13_eta"),
    ("fig14", "benchmarks.fig14_B"),
    ("table2", "benchmarks.table2_large_scale"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated suite names")
    ap.add_argument("--json-out", default="",
                    help="directory to write BENCH_<suite>.json perf "
                         "snapshots for suites that produce one")
    ap.add_argument("--metrics-out", default="",
                    help="file to write the metrics-registry exposition "
                         "(repro.obs) accumulated across the run")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of the whole run into "
                         "this directory (TensorBoard profile plugin)")
    args = ap.parse_args()
    from repro.launch import compile_cache

    compile_cache.enable(pathlib.Path(__file__).resolve().parents[1])
    only = {s for s in args.only.split(",") if s}
    unknown = only - {tag for tag, _ in SUITES}
    if unknown:  # a typo'd --only must not pass vacuously in CI
        print(f"unknown suite(s): {','.join(sorted(unknown))}", file=sys.stderr)
        sys.exit(2)

    out_path = pathlib.Path(__file__).resolve().parent / "results" / "bench.csv"
    out_path.parent.mkdir(parents=True, exist_ok=True)
    rows = ["name,us_per_call,derived"]
    print(rows[0])

    def emit(name: str, us: float, derived: str):
        line = f"{name},{us:.1f},{derived}"
        rows.append(line)
        print(line, flush=True)

    import importlib

    from repro.obs import profile_capture

    failed: list[str] = []
    payloads: dict[str, dict] = {}
    t_all = time.time()
    with profile_capture(args.profile_dir):
        for tag, mod_name in SUITES:
            if only and tag not in only:
                continue
            t0 = time.time()
            try:
                mod = importlib.import_module(mod_name)
                payload = mod.run(emit)
                if isinstance(payload, dict):
                    payload.setdefault("schema_version", SCHEMA_VERSION)
                    payloads[tag] = payload
                emit(f"{tag}/_suite_seconds", (time.time() - t0) * 1e6, "ok")
            except Exception as e:  # keep the harness going; record the failure
                failed.append(tag)
                emit(f"{tag}/_suite_seconds", (time.time() - t0) * 1e6, f"FAIL:{type(e).__name__}:{e}")
                import traceback

                traceback.print_exc()
    emit("_total_seconds", (time.time() - t_all) * 1e6, "")
    out_path.write_text("\n".join(rows) + "\n")
    if args.json_out:
        outdir = pathlib.Path(args.json_out)
        outdir.mkdir(parents=True, exist_ok=True)
        for tag, payload in payloads.items():
            f = outdir / f"BENCH_{tag}.json"
            f.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
            print(f"wrote {f}", file=sys.stderr)
    if args.metrics_out:
        from repro.obs import default_registry, parse_exposition

        text = default_registry().render()
        parse_exposition(text)  # malformed exposition must fail the run
        mp = pathlib.Path(args.metrics_out)
        mp.parent.mkdir(parents=True, exist_ok=True)
        mp.write_text(text)
        print(f"wrote {mp}", file=sys.stderr)
    if failed:  # a half-run must not look green (CI smoke relies on this)
        print(f"FAILED suites: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
