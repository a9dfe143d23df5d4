"""Faults planted under a cell's timed path, for the controls of ``correct``.

``bench/control.py --fault <name>`` runs a cell with one of them on the chip
at the cell's own size; ``bench/tests/test_faults.py`` runs each through a
whole tiny run on the CPU. The benchmark's own runs never plant one.

A fault may patch the program while the run is set up and measured
(``patch``), change the warm engine before the window (``engine``), and
alter what each ``LiraEngine.search`` returns before the harness or the
front-end sees it (``answers``).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import numpy as np


class Fault:
    name = "none"

    def patch(self):
        return contextlib.nullcontext()

    def engine(self, engine, base_np: np.ndarray) -> None:
        pass

    def answers(self, req, res):
        return res


class AlterOne(Fault):
    """One answer of each call points at the next vector, distance kept."""
    name = "answer_altered"

    def engine(self, engine, base_np):
        self.n_base = len(base_np)

    def answers(self, req, res):
        res.ids = res.ids.copy()
        res.ids[0, 0] = (res.ids[0, 0] + 1) % self.n_base
        return res


class DropHalf(Fault):
    """The second half of each call's batch gets no answer."""
    name = "half_left_out"

    def answers(self, req, res):
        res.ids, res.dists = res.ids.copy(), res.dists.copy()
        half = (len(res.ids) + 1) // 2
        res.ids[half:], res.dists[half:] = -1, np.inf
        return res


class HalfMerge(Fault):
    """The serve step's merge sees the first half of its candidate pool:
    the candidates of the first half of the partitions, 25 of the 50
    2,048-wide chunks at SIFT1M scale."""
    name = "half_merge"

    def patch(self):
        from repro.kernels import ops

        orig = ops.dedup_topk

        @functools.wraps(orig)
        def half(dists, ids, k, **kw):
            w = max(k, dists.shape[1] // 2)
            return orig(dists[:, :w], ids[:, :w], k, **kw)

        @contextlib.contextmanager
        def cm():
            ops.dedup_topk = half
            try:
                yield
            finally:
                ops.dedup_topk = orig

        return cm()


@functools.partial(jax.jit, donate_argnums=0)
def _round_bf16(v):
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


class Bf16Select(Fault):
    """Top-k chosen from distances to the stored vectors rounded to
    bfloat16 (the f32 scan, or residual PQ's rerank), then exact float32
    distances recomputed for the chosen ids and each row sorted again: the
    distances are right, only the choice is not."""
    name = "bf16_select"

    def engine(self, engine, base_np):
        self.base_np = base_np
        engine.store["vectors"] = _round_bf16(engine.store["vectors"])

    def answers(self, req, res):
        q = np.asarray(req.queries, np.float32)
        ok = res.ids >= 0
        x = self.base_np[np.where(ok, res.ids, 0)]
        d = np.where(ok, np.square(q[:, None, :] - x).sum(-1, dtype=np.float32), np.inf)
        order = np.argsort(d, axis=1, kind="stable")
        res.ids = np.take_along_axis(res.ids, order, 1)
        res.dists = np.take_along_axis(d, order, 1).astype(np.float32)
        return res


FAULTS = {f.name: f for f in (AlterOne, DropHalf, HalfMerge, Bf16Select)}
