#!/usr/bin/env python3
"""The controls of a cell's ``correct``: the reference put in the program's
place one precision lower, and the program with a fault planted under its
timed path. The comparison has to reject both.

    python3 bench/control.py --workload sift1m-f32.batch --seeds 1 2 3 --queries 13000
    python3 bench/control.py --workload sift1m-f32.batch --seeds 1 2 3 \
        --fault bf16_select --seconds 50

For each seed it takes the queries that seed's traffic sends first (as many
as a run answers), answers them by brute force over the whole corpus with
the query-vector dot in three bfloat16 passes (``reference.knn(passes=3)``,
``Precision.HIGH`` spelled out), and puts those answers through the same
comparison a run's answers go through. It prints one JSON line per seed with
the numbers compared, their limits, and the same readings for brute force at
HIGHEST precision (``passes=6``), the precision the configuration states.
No index is built; the corpus is made as a run makes it.

With ``--fault`` (a name in ``lirabench/faults.py``) it runs the whole cell
once per seed, at its own size and for ``--seconds``, with that fault
planted, and prints each run's result line with the seed and the fault.
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


def readings(mix: dict, seed: int, n_queries: int, base, base_np, pool_np,
             metric: str) -> dict:
    """The numbers a run compares, for brute-force answers under ``metric``
    in three passes (``control``) and at HIGHEST (``highest``, which is also
    the ground truth of ``recall_miss``)."""
    from lirabench import reference, traffic

    rows = traffic.query_rows(mix, len(pool_np), n_queries, seed)
    q = pool_np[rows]
    out, gt = {}, None
    for name, passes in (("highest", 6), ("control", 3)):
        dists, ids = reference.knn(q, base, int(mix["k"]), metric=metric, passes=passes)
        gt = ids if gt is None else gt
        out[name] = {"dist_gap": reference.dist_gap(q, ids, dists, base_np, metric=metric),
                     "bad_answers": reference.bad_answers(ids, dists, len(base_np)),
                     "recall_miss": 1.0 - reference.recall(ids, gt)}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int,
                    help="queries per seed: as many as one run of the cell answers")
    ap.add_argument("--fault", help="run the cell with this fault planted")
    ap.add_argument("--seconds", type=float, help="window of a run with --fault")
    args = ap.parse_args()
    if (args.seconds if args.fault else args.queries) is None:
        ap.error("--fault needs --seconds; without --fault, --queries is needed")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())

    import jax
    import numpy as np

    from lirabench import corpus, faults, harness

    harness.enable_compile_cache(ROOT)
    if args.fault:
        for seed in args.seeds:
            res = harness.run_cell(ROOT, bench, args.workload, seed, args.seconds, False,
                                   jax.devices(), fault=faults.FAULTS[args.fault]())
            print(json.dumps({"seed": seed, "fault": args.fault, **res}), flush=True)
        return 0
    cell = harness.find(bench["workloads"], args.workload)
    cfg = harness.load_json(ROOT / harness.find(bench["configs"], cell["config"])["file"])
    mix = harness.load_json(ROOT / "bench" / "traffic" / f"{cell['traffic']}.json")
    limits = harness.load_json(ROOT / "bench" / "limits" / f"{args.workload}.json")
    harness.check_declared(cfg)
    base, pool, _ = corpus.make_corpus(cfg["dataset"])
    base_np, pool_np = np.asarray(base), np.asarray(pool)
    for seed in args.seeds:
        out = {"seed": seed, "queries": args.queries, "limits": limits}
        out.update(readings(mix, seed, args.queries, base, base_np, pool_np,
                            cfg["dataset"]["metric"]))
        out["control_rejected"] = any(out["control"][k] > v for k, v in limits.items())
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
