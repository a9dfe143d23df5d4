"""Substrate tests: optimizer, checkpointing, pipeline determinism, fault
tolerance (crash/restart), replica failover, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh

from repro.ckpt import CheckpointManager
from repro.data.pipeline import PipelineSpec, TokenPipeline
from repro.distributed.fault import ReplicaRouter, StragglerMitigator
from repro.train import optimizer as opt
from repro.train.trainer import Trainer


def _quadratic_problem():
    """min ||w - target||² — closed-form checkable."""
    target = jnp.asarray(np.random.default_rng(0).normal(size=(8, 4)).astype(np.float32))

    def step_fn(state, batch):
        params, opt_state = state

        def loss(p):
            return jnp.sum((p["w"] - target) ** 2)

        l, g = jax.value_and_grad(loss)(params)
        tx = opt.adamw(1e-1)
        updates, opt_state = tx.update(g, opt_state, params)
        params = opt.apply_updates(params, updates)
        return (params, opt_state), {"loss": l}

    params = {"w": jnp.zeros((8, 4))}
    tx = opt.adamw(1e-1)
    return step_fn, (params, tx.init(params)), target


class _ConstPipeline:
    def batch_at(self, step):
        return {"x": np.zeros(1, np.float32)}


def test_adamw_converges():
    step_fn, state, target = _quadratic_problem()
    jstep = jax.jit(step_fn)
    for _ in range(300):
        state, m = jstep(state, None)
    np.testing.assert_allclose(np.asarray(state[0]["w"]), np.asarray(target), atol=1e-2)


def test_adamw_weight_decay_mask():
    tx = opt.adamw(1e-2, weight_decay=0.1)
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    state = tx.init(params)
    zero_g = jax.tree.map(jnp.zeros_like, params)
    updates, _ = tx.update(zero_g, state, params)
    assert float(jnp.abs(updates["w"]).sum()) > 0    # 2-D decayed
    assert float(jnp.abs(updates["b"]).sum()) == 0   # 1-D not decayed


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 100.0)}
    clipped, norm = opt.clip_by_global_norm(g, 1.0)
    from repro.utils.tree import global_norm

    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    assert float(norm) == pytest.approx(100.0 * np.sqrt(10), rel=1e-5)


def test_checkpoint_roundtrip(tmp_path):
    cm = CheckpointManager(tmp_path, keep=2)
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32), "b": {"c": jnp.ones(4, jnp.int32)}}
    cm.save(10, tree, extra={"note": "x"})
    cm.save(20, tree)
    cm.save(30, tree)
    assert cm.all_steps() == [20, 30]  # keep=2 GC'd step 10
    restored, step, extra = cm.restore(tree)
    assert step == 30
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(tree["a"]))


def test_checkpoint_ignores_partial_writes(tmp_path):
    cm = CheckpointManager(tmp_path)
    tree = {"a": jnp.ones(3)}
    cm.save(1, tree)
    # simulate crash mid-save: orphan tmp dir + step dir without manifest
    (tmp_path / "step_0000000002.tmp").mkdir()
    (tmp_path / "step_0000000003").mkdir()
    assert cm.latest_step() == 1


def test_pipeline_deterministic_resume():
    spec = PipelineSpec(global_batch=8, seed=42)
    p1 = TokenPipeline(spec, seq_len=16, vocab=100)
    p2 = TokenPipeline(spec, seq_len=16, vocab=100)
    for step in (0, 5, 17):
        np.testing.assert_array_equal(p1.batch_at(step)["tokens"], p2.batch_at(step)["tokens"])
    assert not np.array_equal(p1.batch_at(0)["tokens"], p1.batch_at(1)["tokens"])


def test_trainer_crash_restart_is_exact(tmp_path):
    """Gold-standard fault-tolerance test: a run that crashes at step 7 and
    restarts must end bit-identical to an uninterrupted run."""
    step_fn, state0, _ = _quadratic_problem()

    t_gold = Trainer(step_fn, state0, _ConstPipeline(), ckpt_manager=None)
    gold_state, _ = t_gold.run(12)

    cm = CheckpointManager(tmp_path / "ck", keep=3)
    t1 = Trainer(step_fn, state0, _ConstPipeline(), ckpt_manager=cm, ckpt_every=5)
    with pytest.raises(RuntimeError, match="simulated failure"):
        t1.run(12, fail_at=7)
    # restart: auto-resumes from step 5 checkpoint, replays 6..12
    t2 = Trainer(step_fn, state0, _ConstPipeline(), ckpt_manager=cm, ckpt_every=5)
    assert t2.start_step == 5
    state2, _ = t2.run(12)
    np.testing.assert_array_equal(np.asarray(gold_state[0]["w"]), np.asarray(state2[0]["w"]))


def test_replica_failover_serves_everything():
    r = ReplicaRouter(4, seed=1)
    served = r.dispatch(100, fail_at=(30, 2))
    assert sum(served.values()) == 100
    assert served[2] < 100 and not r.replicas[2].healthy
    assert r.requeued >= 1


def test_straggler_hedging_cuts_tail():
    rng = np.random.default_rng(0)
    r = ReplicaRouter(4, seed=0)
    r.replicas[3].latency_scale = 20.0  # one bad node
    mit = StragglerMitigator(r, hedge_factor=3.0)
    lats = [mit.serve(float(rng.lognormal(0, 0.2))) for _ in range(400)]
    p99 = np.quantile(lats, 0.99)
    assert mit.hedges > 0
    assert p99 < 20.0  # un-hedged p99 would be ≈ 20× base latency


def test_grad_compression_error_feedback():
    """Compressed psum over pod axis: single-step is lossy, but error feedback
    makes the RUNNING SUM converge to the true gradient sum."""
    from repro.train.grad_compress import compressed_psum_pod, init_error_buffers

    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64,)).astype(np.float32))}
    err = init_error_buffers(g)
    total = jnp.zeros((64,))
    with mesh:
        for step in range(20):
            out, err = compressed_psum_pod(g, err, mesh)
            total = total + out["w"]
    # after N steps the accumulated compressed sum ≈ N * g (error feedback)
    np.testing.assert_allclose(np.asarray(total) / 20, np.asarray(g["w"]), atol=0.02)


def test_neighbor_sampler_fanout():
    from repro.data.graph import NeighborSampler
    from repro.data.synthetic import make_geometric_graph

    rng = np.random.default_rng(0)
    pos, feat, ei = make_geometric_graph(rng, 200, 8, 4)
    s = NeighborSampler(200, ei, fanout=(5, 3), seed=0)
    nodes, edges = s.sample(step=0, batch_nodes=16)
    assert len(nodes) <= 16 * (1 + 5 + 15) and len(nodes) > 16
    assert edges.shape[0] == 2
    # determinism
    nodes2, edges2 = s.sample(step=0, batch_nodes=16)
    np.testing.assert_array_equal(nodes, nodes2)
