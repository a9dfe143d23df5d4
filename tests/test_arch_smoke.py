"""Per-architecture smoke tests (deliverable f): REDUCED same-family configs,
one forward/train step on CPU, asserting output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh

from repro.configs import ARCH_IDS, get_smoke
from repro.data.smoke import make_smoke_inputs
from repro.models import build_bundle
from repro.train import optimizer as opt


@pytest.fixture(scope="module")
def mesh():
    # single CPU device, both mesh axes size 1 — same code path as the pod
    return make_mesh((1, 1), ("data", "model"))


def _finite(tree):
    return all(bool(jnp.isfinite(jnp.asarray(x, jnp.float32)).all())
               for x in jax.tree.leaves(tree) if hasattr(x, "dtype") and jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke(arch, mesh):
    smoke, shapes = get_smoke(arch)
    assert shapes, f"no smoke shapes for {arch}"
    bundle = build_bundle(smoke, mesh)
    for shape in shapes:
        sd = bundle.step(shape)
        params = bundle.init(jax.random.PRNGKey(0), shape)
        inputs = make_smoke_inputs(smoke, shape, mesh, seed=1)
        with mesh:
            if shape.kind in ("train", "graph_train", "rec_train", "lira_train"):
                tx = opt.adamw(1e-3)
                state = (params, tx.init(params))
                # bundle steps embed their own tx; just run the step fn
                new_state, metrics = jax.jit(sd.fn)(state, inputs["batch"])
                loss = float(metrics["loss"])
                assert np.isfinite(loss), f"{arch}/{shape.name} loss={loss}"
                # params actually changed
                changed = jax.tree.map(lambda a, b: bool(jnp.any(a != b)), params, new_state[0])
                assert any(jax.tree.leaves(changed)), f"{arch}/{shape.name}: no param updated"
            elif shape.kind == "decode":
                out = jax.jit(sd.fn)(params, inputs["cache"], inputs["tokens"], inputs["pos"])
                nt, cache = out
                assert nt.shape == (shape["global_batch"],)
                assert _finite(cache), f"{arch}/{shape.name} cache NaN"
            elif shape.kind == "prefill":
                logits, cache = jax.jit(sd.fn)(params, inputs["tokens"])
                assert logits.shape[0] == shape["global_batch"]
                assert _finite(logits)
            elif shape.kind == "rec_serve":
                score = jax.jit(sd.fn)(params, inputs["batch"])
                assert score.shape == (shape["batch"],)
                assert _finite(score)
            elif shape.kind == "lira_serve":
                d, i, npb, ovf = jax.jit(sd.fn)(params, inputs["store"], inputs["queries"])
                assert d.shape == (shape["n_queries"], smoke.k)
                assert i.shape == (shape["n_queries"], smoke.k)
                assert float(npb.mean()) >= 1.0
                # overflow is a per-batch-shard int32 count (bprod=1 here)
                ovf = jnp.asarray(ovf)
                assert ovf.shape == (1,) and ovf.dtype == jnp.int32
                assert int(ovf.sum()) >= 0
            else:
                raise AssertionError(shape.kind)


def test_lira_serve_matches_bruteforce(mesh):
    """The distributed serve_step must agree with brute force when every
    partition is probed (σ=0 ⇒ nprobe_max partitions probed)."""
    from repro.configs.base import LiraSystemConfig, ShapeSpec
    from repro.serving.engine import make_serve_step
    from repro.core import probing

    cfg = LiraSystemConfig(arch="t", dim=8, n_partitions=4, capacity=32, k=5, nprobe_max=4)
    host = np.random.default_rng(0)
    vecs = host.normal(0, 1, (4, 32, 8)).astype(np.float32)
    ids = np.arange(128, dtype=np.int32).reshape(4, 32)
    store = {"centroids": jnp.asarray(vecs.mean(1)), "vectors": jnp.asarray(vecs),
             "ids": jnp.asarray(ids)}
    pc = probing.ProbingConfig(dim=8, n_partitions=4)
    params = probing.init(jax.random.PRNGKey(1), pc)
    q = host.normal(0, 1, (16, 8)).astype(np.float32)
    fn = make_serve_step(cfg, mesh, 16, sigma=-1.0, q_cap_factor=8.0)  # probe all
    with mesh:
        d, i, npb, _ = jax.jit(fn)(params, store, jnp.asarray(q))
    flat = vecs.reshape(-1, 8)
    exact = ((q[:, None] - flat[None]) ** 2).sum(-1)
    gt_ids = np.argsort(exact, 1)[:, :5]
    for r in range(16):
        assert set(np.asarray(i)[r].tolist()) == set(gt_ids[r].tolist()), r
    assert float(np.asarray(npb).mean()) == 4.0
