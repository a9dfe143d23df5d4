"""The corpus and query pools a configuration declares.

Without ``dataset.queries`` the base and the pool are the ones every cell
has been measured on, bit for bit: the fingerprints below are those the
generator gave before the section existed, for the tiny dataset of
``test_faults.py`` and for the SIFT1M section at 20,000 base vectors. With
``{"dist": "shifted"}`` the queries lie away from the base, as text queries
do from image embeddings."""
import json
import pathlib

import numpy as np
import pytest

from lirabench import corpus

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = {"name": "tiny", "n_base": 4000, "n_queries": 300, "dim": 32, "metric": "l2",
        "dtype": "float32", "data_seed": 7, "n_modes": 20, "boundary_frac": 0.4,
        "noise_frac": 0.02, "center_scale": 1.5, "spread": 2.0}
SHIFTED = {"dist": "shifted", "offset": 12.0, "n_learn": 200}


def _sift(n_base):
    cfg = json.loads((ROOT / "bench/configs/sift1m-f32.json").read_text())
    return dict(cfg["dataset"], n_base=n_base)


@pytest.mark.parametrize("ds,base_fp,pool_fp", [
    (TINY, "a70a617d8bf68964", "a11fc5e975ef13e5"),
    (_sift(20_000), "7bcf87bd28041705", "4059e396f876eaf2"),
], ids=["tiny", "sift1m-20000"])
def test_without_queries_the_corpus_is_unchanged(ds, base_fp, pool_fp):
    base, pool, learn = corpus.make_corpus(ds)
    assert corpus.fingerprint(np.asarray(base)) == base_fp
    assert corpus.fingerprint(np.asarray(pool)) == pool_fp
    assert learn is None


def _nn(a: np.ndarray, b: np.ndarray, skip_self: bool = False) -> np.ndarray:
    d = ((a[:, None, :] - b[None]) ** 2).sum(-1)
    if skip_self:
        d[np.arange(len(a)), np.arange(len(a))] = np.inf
    return np.sqrt(d.min(1))


def test_shifted_queries_are_deterministic_and_disjoint():
    ds = dict(TINY, queries=SHIFTED)
    base, pool, learn = map(np.asarray, corpus.make_corpus(ds))
    base2, pool2, learn2 = map(np.asarray, corpus.make_corpus(ds))
    np.testing.assert_array_equal(pool, pool2)
    np.testing.assert_array_equal(learn, learn2)
    assert pool.shape == (300, 32) and learn.shape == (200, 32)
    assert pool.dtype == learn.dtype == np.float32
    # the base is the one the configuration has without the section
    np.testing.assert_array_equal(base, np.asarray(corpus.make_corpus(TINY)[0]))
    assert _nn(pool.astype(np.float64), learn.astype(np.float64)).min() > 0
    other = np.asarray(corpus.make_corpus(dict(ds, data_seed=8))[1])
    assert not np.array_equal(pool, other)
    assert corpus.make_corpus(dict(ds, queries={"dist": "shifted", "offset": 12.0}))[2] is None


def test_shifted_queries_lie_away_from_the_base():
    """The shift is there and the unshifted pool is not moved. The 1.5 is no
    published figure: it only tells a moved cloud from an unmoved one at
    this offset."""
    base, pool, _ = map(np.asarray, corpus.make_corpus(TINY))
    _, shifted, _ = map(np.asarray, corpus.make_corpus(dict(TINY, queries=SHIFTED)))
    base = base.astype(np.float64)
    base_nn = np.median(_nn(base[:500], base, skip_self=True))
    same = np.median(_nn(pool.astype(np.float64), base)) / base_nn
    away = np.median(_nn(shifted.astype(np.float64), base)) / base_nn
    assert 0.9 <= same <= 1.2, same
    assert away >= 1.5, away
