#!/usr/bin/env python3
"""LIRA chip benchmark: run one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on, on as many of its TPUs as the cell's
``chips`` says. The last line of
standard output is the result (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared beside its limit); the lines before it
give the set-up split, compiles, generator lateness, peak device memory and
each step. With no TPU, fewer chips than the cell asks for, or a device kind
missing from ``bench/peaks.json``, it exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))
# the TPU library would log under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell's name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="traffic seed")
    ap.add_argument("--seconds", type=float, required=True, help="length of the window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: report the per-layer metrics from a profiled run")
    args = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = next(w["chips"] for w in bench["workloads"] if w["name"] == args.workload)

    import jax

    from lirabench import harness, work

    harness.enable_compile_cache(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX found {devices[0].platform}); nothing to run",
              file=sys.stderr)
        return 1
    if len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    work.peaks(ROOT / "bench", devices[0].device_kind)     # an unknown kind is an error
    result = harness.run_cell(ROOT, bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), devices)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
