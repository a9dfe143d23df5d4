"""A configuration runs under what it declares, or not at all.

What a configuration may declare and the harness honours: ``dataset.metric``
(the reference's distances, the probing copy the work is counted with),
``dataset.queries`` (the query and learn pools, the learn pool handed to the
build), and a cell's ``chips`` (the mesh). Anything else is refused before
set-up, naming the key and the value. The cells are the tiny ones of
``test_faults.py``."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lirabench import corpus, harness, index_cache, reference, work
from test_faults import _tiny_root

ROOT = pathlib.Path(__file__).resolve().parents[2]
SHIFTED = {"dist": "shifted", "offset": 12.0, "n_learn": 200}


def _params(rng, d, b, h):
    def layers(sizes):
        return [{"w": rng.standard_normal((a, c)).astype(np.float32) / np.sqrt(a),
                 "b": rng.standard_normal(c).astype(np.float32) * 0.1}
                for a, c in zip(sizes, sizes[1:])]

    return {"phi_q": layers([d, h]), "phi_i": layers([b, h]), "phi_p": layers([2 * h, h, b])}


def test_l2_probing_copy_gives_the_masks_it_gave_before():
    """The digest is of the mask ``work.probe_mask`` gave while the copy
    still lived in ``work.py``, on these hand-made parameters."""
    import hashlib

    rng = np.random.default_rng(5)
    params = _params(rng, 32, 16, 24)
    cents = rng.standard_normal((16, 32)).astype(np.float32) * 3
    q = rng.standard_normal((37, 32)).astype(np.float32) * 3
    mask = work.probe_mask(params, cents, q, 0.52, 10, "l2")
    assert int(mask.sum()) == 159
    assert hashlib.sha256(np.packbits(mask).tobytes()).hexdigest()[:16] == "f7156dbd16ef6ed7"


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """A tiny root whose f32 cell has been set up once: its index built and
    kept, then loaded."""
    root = tmp_path_factory.mktemp("declared_root")
    bench = _tiny_root(root)
    harness.enable_compile_cache(root)
    p = harness.prepare(root, bench, "tiny.batch", jax.devices())
    return root, bench, p


def test_l2_probing_copy_matches_the_program_on_a_built_index(built):
    from repro.core import probing

    _, _, p = built
    cents = np.asarray(p.engine.store["centroids"])
    q = p.pool_np[:64]
    sigma, nmax = float(p.engine.sigma), p.engine.cfg.nprobe_max
    got = work.probe_mask(p.engine.params, cents, q, sigma, nmax, "l2")
    qj = jnp.asarray(q)
    cd = (jnp.sum(qj * qj, -1, keepdims=True)
          - 2.0 * jnp.dot(qj, cents.T, precision=jax.lax.Precision.HIGHEST)
          + jnp.sum(cents * cents, -1)[None, :])
    prob = np.asarray(probing.probs(p.engine.params, qj, cd))
    top = np.argsort(-prob, axis=1, kind="stable")[:, :nmax]
    keep = np.take_along_axis(prob, top, 1) > sigma
    keep[:, 0] = True
    want = np.zeros(prob.shape, bool)
    np.put_along_axis(want, top, keep, 1)
    np.testing.assert_array_equal(got, want)


def test_pad_row_is_kept_and_an_old_index_loads_with_the_default(built, tmp_path):
    root, _, p = built
    (directory,) = [d for d in (root / "bench/.cache/index/tiny-f32").iterdir()
                    if (d / "meta.json").exists()]
    meta = json.loads((directory / "meta.json").read_text())
    ids = np.asarray(p.engine.store["ids"])
    assert (ids < 0).any()
    assert meta["pad"] == [1e6] * p.engine.cfg.dim
    np.testing.assert_array_equal(np.asarray(p.engine.store["vectors"])[ids < 0], 1e6)
    old = tmp_path / "old"
    shutil.copytree(directory, old)
    del meta["pad"]
    (old / "meta.json").write_text(json.dumps(meta))
    engine, _ = index_cache.load(old, p.base, p.engine.mesh)
    np.testing.assert_array_equal(np.asarray(engine.store["vectors"]),
                                  np.asarray(p.engine.store["vectors"]))


def _refused_root(tmp: pathlib.Path, cfg_edit) -> dict:
    bench = _tiny_root(tmp)
    path = tmp / "bench/configs/tiny-f32.json"
    cfg = json.loads(path.read_text())
    cfg_edit(cfg)
    path.write_text(json.dumps(cfg))
    return bench


def _set(key, value):
    def edit(cfg):
        ds = cfg["dataset"]
        if value is None:
            ds.pop(key)
        else:
            ds[key] = value
    return edit


@pytest.mark.parametrize("edit,match", [
    (_set("metric", "cosine"), "dataset.metric 'cosine'"),
    (_set("metric", None), "dataset.metric None"),
    (_set("dtype", "uint8"), "dataset.dtype 'uint8'"),
    (_set("dtype", "bfloat16"), "dataset.dtype 'bfloat16'"),
    (_set("queries", {"dist": "uniform", "offset": 1.0}), "dataset.queries.dist 'uniform'"),
    (_set("queries", {"dist": "shifted", "offset": 1.0, "skew": 2}), "dataset.queries.skew"),
    (_set("queries", {"dist": "shifted"}), "dataset.queries.offset"),
    (_set("queries", {"dist": "shifted", "offset": 1.0, "n_learn": -5}),
     "dataset.queries.n_learn -5"),
    (lambda cfg: cfg["index"].update(metric="ip"),
     "index.metric 'ip' differs from dataset.metric 'l2'"),
], ids=["metric", "no_metric", "uint8", "bf16", "dist", "queries_key", "no_offset",
        "n_learn", "index_metric"])
def test_undeclared_semantics_are_refused_before_setup(tmp_path, monkeypatch, edit, match):
    bench = _refused_root(tmp_path, edit)

    def setup_started(*a, **k):
        raise AssertionError("set-up started")

    monkeypatch.setattr(corpus, "make_corpus", setup_started)
    with pytest.raises(ValueError, match=match):
        harness.run_cell(tmp_path, bench, "tiny.batch", 1, 1.0, False, jax.devices())


def test_metric_without_probing_copy_is_refused_before_setup(tmp_path, monkeypatch):
    bench = _tiny_root(tmp_path)
    empty = tmp_path / "probing"
    empty.mkdir()
    monkeypatch.setattr(work, "PROBING_DIR", empty)
    monkeypatch.setattr(corpus, "make_corpus", lambda *a, **k: pytest.fail("set-up started"))
    work.probing.cache_clear()
    try:
        with pytest.raises(FileNotFoundError, match="l2.py"):
            harness.run_cell(tmp_path, bench, "tiny.batch", 1, 1.0, False, jax.devices())
    finally:
        work.probing.cache_clear()


def test_ip_configuration_is_judged_under_inner_product(tmp_path, monkeypatch):
    """A configuration that declares ``ip``, shifted queries with a learn
    pool, and brings ``probing/ip.py`` needs no other edit: it passes the
    check, its ground truth and ``dist_gap`` are those of ``-<q, x>``, and
    the build is handed the learn pool. A stand-in takes ``ip.py``'s place."""
    probing_dir = tmp_path / "probing"
    probing_dir.mkdir()
    shutil.copy(work.PROBING_DIR / "l2.py", probing_dir / "ip.py")
    monkeypatch.setattr(work, "PROBING_DIR", probing_dir)
    work.probing.cache_clear()
    _tiny_root(tmp_path)
    cfg = json.loads((tmp_path / "bench/configs/tiny-f32.json").read_text())
    cfg["dataset"].update(metric="ip", queries=SHIFTED)
    try:
        harness.check_declared(cfg)
    finally:
        work.probing.cache_clear()
    base, pool, learn = corpus.make_corpus(cfg["dataset"])
    base_np, pool_np = np.asarray(base), np.asarray(pool)
    assert learn.shape == (200, 32)
    fp = corpus.fingerprint(base_np)
    gt = harness.ground_truth(tmp_path, cfg, fp, pool_np, base, 10)
    ip = pool_np.astype(np.float64) @ base_np.astype(np.float64).T
    np.testing.assert_array_equal(gt, np.argsort(-ip, axis=1, kind="stable")[:, :10])
    dists = -np.take_along_axis(ip, gt, 1).astype(np.float32)
    assert reference.dist_gap(pool_np, gt, dists, base_np, metric="ip") <= 1e-6
    assert reference.dist_gap(pool_np, gt, dists, base_np, metric="l2") > 1e-3

    # the program as it stands takes neither keyword: the call fails, naming it
    from repro.serving import LiraEngine

    with pytest.raises(TypeError, match="metric"):
        index_cache._build(cfg, base_np, learn, None)
    l2_learn = dict(cfg, dataset=dict(cfg["dataset"], metric="l2"))
    with pytest.raises(TypeError, match="train_queries"):
        index_cache._build(l2_learn, base_np, learn, None)

    seen = {}

    def build(mesh, x, config, **kwargs):
        seen.update(kwargs)
        return "engine"

    monkeypatch.setattr(LiraEngine, "build", build)
    assert index_cache._build(cfg, base_np, learn, None) == "engine"
    assert seen.pop("metric") == "ip"
    np.testing.assert_array_equal(seen.pop("train_queries"), np.asarray(learn))
    assert not seen
    no_learn = dict(cfg, dataset=dict(cfg["dataset"], queries={"dist": "shifted", "offset": 1.0}))
    index_cache._build(no_learn, base_np, None, None)
    assert seen == {"metric": "ip"}
    seen.clear()
    index_cache._build(dict(no_learn, dataset=dict(no_learn["dataset"], metric="l2")),
                       base_np, None, None)
    assert seen == {}


_TWO_CHIPS = """
import json, pathlib, sys
import jax
from lirabench import harness
from test_faults import _tiny_root

root = pathlib.Path(sys.argv[1])
bench = _tiny_root(root)
for w in bench["workloads"]:
    w["chips"] = 2
harness.enable_compile_cache(root)
meshes = []
prepare = harness.prepare


def recording(*a, **k):
    p = prepare(*a, **k)
    meshes.append(dict(p.engine.mesh.shape))
    return p


harness.prepare = recording
res = harness.run_cell(root, bench, "tiny.batch", 2**31 + 777, 1.0, False, jax.devices())
print(json.dumps({"mesh": meshes, "devices": len(jax.devices()), "correct": res["correct"],
                  "checks": res["checks"]}))
"""


def test_two_chip_cell_runs_on_a_two_device_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(str(ROOT / p) for p in ("src", "bench",
                                                                  "bench/tests")))
    out = subprocess.run([sys.executable, "-c", _TWO_CHIPS, str(tmp_path)], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["mesh"] == [{"data": 1, "model": 2}] and res["devices"] == 4, res
    assert res["correct"] is True, res["checks"]
