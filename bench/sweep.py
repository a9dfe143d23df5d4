#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate at which
nothing is shed and the backlog does not grow.

    python3 bench/sweep.py --workload sift1m-rpq.online --seed 1 --seconds 50 \\
        --rates 5.5 6.5 7 7.5 8 8.5

One process, one set-up, then the cell's mix at each rate in turn. For each
step of the window it counts the backlog it left behind: requests due before
the step started that neither it nor an earlier step took. Below the
knee every step takes everything waiting and the backlog stays at zero; above
it the backlog grows from step to step. It prints one JSON line per rate.
The cell's mix file keeps 0.8 of the knee as its fixed rate.
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())

    import jax
    import numpy as np

    from lirabench import harness

    harness.enable_compile_cache(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep.py: no TPU", file=sys.stderr)
        return 1
    p = harness.prepare(ROOT, bench, args.workload, devices)
    harness.log(f"setup: {p.setup_s:.3f} s")
    steps: list = []
    harness.instrument(p.engine, steps)
    for rate in args.rates:
        mix = dict(p.mix, rate_qps=rate, drain_s=0.0)
        steps.clear()
        out = harness.open_loop(p.engine, mix, p.pool_np, args.seed, args.seconds)
        due = out["due"]
        taken = np.cumsum([len(s.queries) for s in steps])
        waiting = np.array([int((due < s.t0 - out["t0"]).sum()) for s in steps])
        print(json.dumps({"rate_qps": rate, "requests": len(due),
                          "shed": out["failed"] - out["missing"],
                          "steps": len(steps), "rows": [len(s.queries) for s in steps],
                          "backlog_left": (waiting - taken).tolist(),
                          "step_s": [s.t1 - s.t0 for s in steps],
                          "p95_ms": float(np.percentile(out["latencies_ms"], 95))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
