"""The control has to come out as not correct: brute-force answers with the
query-vector dot in three bfloat16 passes (``Precision.HIGH``) fail the
distance comparison at the committed limits, and the same answers at HIGHEST
precision pass it. The widths are the cells' (d=128, k=100) over a corpus
of 20,000 vectors from the cells' generator; the chip readings at full size
are in PERF.md."""
import json
import pathlib

import numpy as np
import pytest

import control
from lirabench import corpus

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def data():
    cfg = json.loads((ROOT / "bench/configs/sift1m-f32.json").read_text())
    ds = dict(cfg["dataset"], n_base=20_000, n_queries=400)
    base, pool, _ = corpus.make_corpus(ds)
    return base, np.asarray(base), np.asarray(pool)


@pytest.mark.parametrize("cell,traffic", [("sift1m-f32.batch", "batch1000"),
                                          ("sift1m-rpq.online", "online-poisson")])
def test_control_fails_and_highest_passes(data, cell, traffic):
    base, base_np, pool_np = data
    mix = json.loads((ROOT / f"bench/traffic/{traffic}.json").read_text())
    limits = json.loads((ROOT / f"bench/limits/{cell}.json").read_text())
    for seed in (1, 2, 3):
        r = control.readings(mix, seed, 200, base, base_np, pool_np, "l2")
        low, high = r["control"], r["highest"]
        assert any(low[k] > v for k, v in limits.items()), (seed, low, limits)
        assert all(high[k] <= v for k, v in limits.items()), (seed, high, limits)
