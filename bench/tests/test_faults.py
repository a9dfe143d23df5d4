"""A run with the timed path broken underneath comes out not correct.

Each case drives a whole run of a tiny cell on the CPU (``harness.run_cell``
with the chip check skipped: corpus, index build and cache, warm-up, the
window through ``LiraEngine.search`` or the serving front-end, the
reference), with a fault from ``lirabench/faults.py`` planted under the
timed path. The faults a search cell can have: an answer altered where it
is produced, half of a batch left out, a merge over half of its candidate
pool, and a top-k chosen from bfloat16 distances with exact distances put
back afterwards. The training faults (state returned unchanged) and the
exchange between chips do not exist in a one-chip search cell.

The tiny index probes all 16 partitions (sigma below every probability), so
a sound run misses no true neighbour and ``recall_miss`` reads 0; its limit
here is one missed neighbour in a thousand. The cells' own limits are in
``bench/limits/<cell>.json``, set from chip readings (PERF.md)."""
import json
import pathlib

import jax
import pytest

from lirabench import faults, harness

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _tiny_root(tmp: pathlib.Path) -> dict:
    (tmp / "bench/configs").mkdir(parents=True)
    (tmp / "bench/traffic").mkdir(parents=True)
    (tmp / "bench/limits").mkdir(parents=True)
    (tmp / "bench/metrics").symlink_to(ROOT / "bench/metrics")
    (tmp / "src").symlink_to(ROOT / "src")
    ds = {"name": "tiny", "n_base": 4000, "n_queries": 300, "dim": 32, "metric": "l2",
          "dtype": "float32", "data_seed": 7, "n_modes": 20, "boundary_frac": 0.4,
          "noise_frac": 0.02, "center_scale": 1.5, "spread": 2.0}
    limits = json.loads((ROOT / "bench/limits/sift1m-f32.batch.json").read_text())
    limits["recall_miss"] = 1e-3
    for cell in ("tiny.batch", "tiny.online"):
        (tmp / f"bench/limits/{cell}.json").write_text(json.dumps(limits))
    for tier in ("f32", "residual_pq"):
        ix = {"n_partitions": 16, "k": 10, "nprobe_max": 16, "eta": 0.03, "sigma": -1.0,
              "train_frac": 0.5, "epochs": 2, "tier": tier, "impl": "auto", "seed": 0}
        if tier != "f32":
            ix.update(pq_m=8, pq_ks=16, rerank=40)
        (tmp / f"bench/configs/tiny-{tier}.json").write_text(json.dumps(
            {"name": f"tiny-{tier}", "dataset": ds, "index": ix}))
    (tmp / "bench/traffic/tiny-batch.json").write_text(json.dumps(
        {"loop": "closed", "batch": 50, "k": 10}))
    (tmp / "bench/traffic/tiny-online.json").write_text(json.dumps(
        {"loop": "open", "rate_qps": 40.0, "arrival_seed": 3, "k": 10,
         "query_seed": 4, "drain_s": 30,
         "frontend": {"max_batch": 16, "max_wait_ms": 2.0, "max_queue": 256},
         "deadline_ms": None}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": f"tiny-{t}", "file": f"bench/configs/tiny-{t}.json"}
                        for t in ("f32", "residual_pq")]
    bench["workloads"] = [{"name": "tiny.batch", "config": "tiny-f32", "traffic": "tiny-batch"},
                          {"name": "tiny.online", "config": "tiny-residual_pq",
                           "traffic": "tiny-online"}]
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.batch" if w.endswith(".batch") else "tiny.online"
                              for w in m["workloads"]]
    return bench


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    bench = _tiny_root(root)
    harness.enable_compile_cache(root)
    return root, bench


@pytest.mark.parametrize("cell", ["tiny.batch", "tiny.online"])
@pytest.mark.parametrize("fault", [None, *faults.FAULTS],
                         ids=["sound", *faults.FAULTS])
def test_broken_timed_path_is_not_correct(tiny, cell, fault):
    root, bench = tiny
    res = harness.run_cell(root, bench, cell, 2**31 + 12345, 1.0, False, jax.devices(),
                           fault=faults.FAULTS[fault]() if fault else None)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"
