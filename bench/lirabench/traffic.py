"""One general generator for every traffic mix: a mix is a data file of
parameters, read here.

Keys of a mix (``bench/traffic/<mix>.json``):

* ``loop``: ``"closed"`` (one caller sends a batch of ``batch`` queries and
  waits for its answer before the next; the window ends at the first batch
  boundary at or after ``--seconds``) or ``"open"`` (single-query requests
  through the serving front-end at due times fixed in advance, whatever the
  system does; the window holds every request due before ``--seconds``).
* ``k``: neighbours per query.
* ``query_seed`` (optional): draw the set of queries from it, not from the
  run's seed (see below).
* open loop only: ``rate_qps``; ``arrival_seed`` (optional): draw the due
  times from it; ``frontend`` (``FrontendConfig`` fields); ``deadline_ms``
  (null: none); ``drain_s``, how long past the window the run waits for
  answers due in it.

Queries walk a permutation of the configuration's pool drawn from the seed.
Every seed gets the same amount of work: an open loop of ``s`` seconds at
rate ``r`` holds exactly ``round(r * s)`` requests, placed uniformly at
random in the window (a Poisson process given its count), and a closed loop
sends equal batches. A mix may fix its arrivals (``arrival_seed``) and its
set of queries (``query_seed``), so that every seed sends the same requests
at the same times in another order: a tail over a few hundred requests then
reads the system, not the draw.
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def query_rows(mix: dict, pool: int, n: int, seed: int) -> np.ndarray:
    """Pool rows of the first ``n`` queries the mix sends. With
    ``query_seed`` in the mix, every run sends the same queries and its own
    seed only shuffles them."""
    if "query_seed" in mix:
        rows = query_rows({k: v for k, v in mix.items() if k != "query_seed"}, pool, n,
                          mix["query_seed"])
        return rows[_rng(seed, 3).permutation(n)]
    perm = _rng(seed, 1).permutation(pool)
    return perm[np.arange(n) % pool]


def due_times(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Sorted due times (seconds from the window's start) of an open loop,
    drawn from the mix's ``arrival_seed`` where it has one."""
    rng = _rng(mix.get("arrival_seed", seed), 2)
    n = int(round(float(mix["rate_qps"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))
