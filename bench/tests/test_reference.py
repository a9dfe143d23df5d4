"""The reference under each metric a configuration may declare.

Under ``"ip"`` a program returns ``-<q, x>``, ascending; the reference's
k-NN must be the exact one and ``dist_gap`` must separate exact answers
from wrong distances and from the three-pass control. The corpus is the
tiny one of ``test_faults.py``'s cells, from the cells' generator."""
import numpy as np
import pytest

from lirabench import corpus, reference

TINY = {"name": "tiny", "n_base": 4000, "n_queries": 300, "dim": 32, "metric": "ip",
        "dtype": "float32", "data_seed": 7, "n_modes": 20, "boundary_frac": 0.4,
        "noise_frac": 0.02, "center_scale": 1.5, "spread": 2.0}
K = 10
LIMIT = 1e-6          # what an exact answer reads under, with room


@pytest.fixture(scope="module")
def data():
    base, pool, _ = corpus.make_corpus(TINY)
    return base, np.asarray(base), np.asarray(pool)[:120]


def _brute_ip(q: np.ndarray, x: np.ndarray, k: int):
    ip = q.astype(np.float64) @ x.astype(np.float64).T
    ids = np.argsort(-ip, axis=1, kind="stable")[:, :k]
    return -np.take_along_axis(ip, ids, 1), ids


def test_ip_knn_is_exact(data):
    base, base_np, q = data
    dists, ids = reference.knn(q, base, K, metric="ip")
    want_d, want_i = _brute_ip(q, base_np, K)
    np.testing.assert_array_equal(ids, want_i)
    np.testing.assert_allclose(dists, want_d, rtol=1e-5)
    assert (np.diff(dists, axis=1) >= 0).all()
    assert reference.bad_answers(ids, dists, len(base_np)) == 0


def test_ip_dist_gap_separates_exact_wrong_and_control(data):
    base, base_np, q = data
    dists, ids = reference.knn(q, base, K, metric="ip")
    exact = reference.dist_gap(q, ids, dists, base_np, metric="ip")
    assert exact <= LIMIT, exact
    # the same ids with their squared-L2 distances: another metric's answer
    x = base_np[ids].astype(np.float64)
    l2 = ((q.astype(np.float64)[:, None, :] - x) ** 2).sum(-1).astype(np.float32)
    assert reference.dist_gap(q, ids, l2, base_np, metric="ip") > LIMIT
    c_d, c_i = reference.knn(q, base, K, metric="ip", passes=3)
    control = reference.dist_gap(q, c_i, c_d, base_np, metric="ip")
    assert control > exact and control > LIMIT, (control, exact)


@pytest.mark.parametrize("metric", ["cosine", None, "L2"])
def test_unknown_metric_is_refused(data, metric):
    base, base_np, q = data
    with pytest.raises(ValueError, match="dataset.metric"):
        reference.knn(q, base, K, metric=metric)
    with pytest.raises(ValueError, match="dataset.metric"):
        reference.dist_gap(q, np.zeros((len(q), K), np.int32), np.zeros((len(q), K)),
                           base_np, metric=metric)
