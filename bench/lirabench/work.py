"""The work a serve step must do, counted from the benchmark's own probing.

A kernel's roofline share compares its time in the trace with the least time
the chip needs for the work any implementation of that step must do. That
work is counted here, never from ``capacity`` or ``q_cap``, so a kernel that
skips empty buckets or padding does the same work in less time and cannot
pass 100%:

* which partitions each query probes: the probing model (copied from the
  paper's equations, ``core/probing.py``, into ``probing/<metric>.py`` for
  the configuration's ``dataset.metric``) over the built index's parameters
  and centroids, top ``nprobe_max`` by probability, those above ``sigma``,
  the best always;
* bytes: the live slots of every partition at least one query of the step
  probes, once each, plus the query rows (f32 scan) or the queries' ADC
  tables (PQ scan);
* operations: for each (query, probed partition) pair, each live slot once:
  ``2 d`` multiply-adds for an exact distance, ``m`` table additions for an
  ADC distance.

The least time is the larger of bytes over the HBM bandwidth and operations
over the chip's fastest arithmetic (bf16 on the MXU), from ``peaks.json``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib

import jax.numpy as jnp
import numpy as np


PROBING_DIR = pathlib.Path(__file__).parent / "probing"


def peaks(bench_dir: pathlib.Path, device_kind: str) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


@functools.cache
def probing(metric: str):
    """The benchmark's copy of the probing model under ``metric``:
    ``probing/<metric>.py``, whose ``probs(params, cents, q)`` gives [nq, B]
    probabilities. Loaded once per process, as a metric reader is found."""
    path = PROBING_DIR / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"dataset.metric {metric!r} has no probing model: "
                                f"{path} is missing")
    spec = importlib.util.spec_from_file_location(f"lirabench_probing_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def probe_mask(params, cents, q: np.ndarray, sigma: float, nprobe_max: int,
               metric: str) -> np.ndarray:
    """[nq, B] bool: the partitions each query probes."""
    n = len(q)
    pad = np.zeros((max(8, 1 << (n - 1).bit_length()), q.shape[1]), np.float32)
    pad[:n] = q          # a few padded shapes, not one program per batch size
    p = np.asarray(probing(metric).probs(params, cents, jnp.asarray(pad)))[:n]
    top = np.argsort(-p, axis=1, kind="stable")[:, :nprobe_max]
    keep = np.take_along_axis(p, top, 1) > sigma
    keep[:, 0] = True
    mask = np.zeros(p.shape, bool)
    np.put_along_axis(mask, top, keep, 1)
    return mask


def step_work(mask: np.ndarray, live: np.ndarray, scan: dict) -> dict:
    """{kernel: (bytes, ops)} for one step's probe mask [nq, B] and live
    slots per partition [B]. ``scan`` describes the configuration's scan:
    ``dim``, and for PQ ``pq_m``/``pq_ks``."""
    nq = mask.shape[0]
    touched = mask.any(0)
    slots = float(live[touched].sum())
    pair_slots = float((mask.astype(np.float64) @ live.astype(np.float64)).sum())
    d = int(scan["dim"])
    if scan.get("pq_m"):
        m, ks = int(scan["pq_m"]), int(scan["pq_ks"])
        # uint8 codes and the id of each live slot, with residual PQ its f32
        # cross term; each query's f32 ADC table
        per_slot = m + 4 + (4 if scan.get("residual") else 0)
        return {"pq_adc_topk_qbuf": (slots * per_slot + nq * m * ks * 4, pair_slots * m)}
    return {"l2_topk_qbuf": (slots * (d * 4 + 4) + nq * d * 4, pair_slots * 2 * d)}


def least_time(work: tuple, pk: dict) -> float:
    nbytes, ops = work
    return max(nbytes / pk["hbm_bytes_per_s"], ops / pk["bf16_flops_per_s"])
