"""Host-side mutation planning for the epoch-versioned mutable store.

The serving store is a static-shape [B, capacity] slot grid (per-slot planes
declared by the tier registry, serving/tiers.py) — mutations therefore reduce
to SLOT bookkeeping, planned here on the host in numpy and applied by
``LiraEngine.insert/delete/compact/maybe_repartition`` (serving/engine.py):

  * ``plan_insert``    — greedy nearest-partition-with-free-slot placement of
    appended rows; reports which rows landed off their argmin partition (the
    staleness signal IRLI-style re-partitioning consumes) and which found no
    slot at all (the grow signal);
  * ``grow_store``     — widen every per-slot plane to a new capacity, padding
    with the same sentinels ``core.partitions.build_store`` uses;
  * ``compact_store``  — repack live slots to the front of each partition and
    shrink capacity to the max live count, erasing tombstones;
  * ``layout_rows``    — a full (partition → slots) layout for re-partition
    rebuilds: stable within-partition ordering, contiguous slots.

Everything here is pure host math over occupancy/id planes — no jit, no mesh.
The invariant the engine maintains on top: a slot is LIVE iff occupancy is
True; a tombstone is occupancy=False with a non-negative id left behind (the
id plane is only healed when the slot is reused or compacted away); the serve
step masks ``ids`` with occupancy before the scan, so holes reuse the scan
layer's universal ``id < 0`` invalid sentinel.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

# how many nearest partitions an inserted row may spill into before the
# engine grows the store instead (spilling further than this would plant
# rows so far off their argmin partition that probing rarely finds them)
PLACE_WINDOW = 4

# pad sentinels per slot plane — mirrors core.partitions.build_store (vector
# sentinel 1e6 keeps padding out of any top-k; PAD_ID=-1 is the scan layer's
# invalid marker). Planes not named here (codes, cterm, ...) zero-fill: their
# slots are unreachable once ids/occupancy mark them dead.
_FILL = {"vectors": 1e6, "ids": -1, "occupancy": False}


def fill_value(name: str):
    return _FILL.get(name, 0)


class InsertPlan(NamedTuple):
    parts: np.ndarray        # [n] destination partition (-1 = no slot found)
    slots: np.ndarray        # [n] destination slot within the partition
    misassigned: np.ndarray  # [n] bool: placed, but not in argmin partition
    ok: np.ndarray           # [n] bool: a slot was found within the window


def plan_insert(occ: np.ndarray, dist: np.ndarray, *,
                window: int = PLACE_WINDOW) -> InsertPlan:
    """Place ``n`` new rows into free slots: each row tries its ``window``
    nearest partitions in order and takes the lowest free slot of the first
    one with room. ``occ`` is the [B, capacity] occupancy plane (not
    modified); ``dist`` the [n, B] row→centroid squared distances. Rows are
    placed in input order — earlier rows claim contested slots first."""
    n, nb = dist.shape
    order = np.argsort(dist, axis=1, kind="stable")[:, :max(1, window)]
    parts = np.full(n, -1, np.int64)
    slots = np.full(n, -1, np.int64)
    # per-partition free-slot stacks, lowest slot on top
    free = [list(np.flatnonzero(~occ[b])[::-1]) for b in range(nb)]
    for i in range(n):
        for b in order[i]:
            if free[b]:
                parts[i], slots[i] = b, free[b].pop()
                break
    ok = parts >= 0
    return InsertPlan(parts=parts, slots=slots,
                      misassigned=ok & (parts != order[:, 0]), ok=ok)


def grow_store(planes: dict, new_cap: int) -> dict:
    """Widen every per-slot plane (leading dims [B, cap, ...]) to
    ``new_cap`` slots, sentinel-padded. Host numpy in, host numpy out."""
    out = {}
    for name, arr in planes.items():
        arr = np.asarray(arr)
        if new_cap < arr.shape[1]:
            raise ValueError(f"grow_store cannot shrink {name}: "
                             f"{arr.shape[1]} -> {new_cap} (use compact_store)")
        pad = np.full((arr.shape[0], new_cap - arr.shape[1], *arr.shape[2:]),
                      fill_value(name), arr.dtype)
        out[name] = np.concatenate([arr, pad], axis=1)
    return out


def pack_order(occ: np.ndarray):
    """Per-partition permutation that moves live slots to the front (stable:
    live slots keep their relative order). Returns (perm [B, cap], live [B])."""
    perm = np.argsort(~occ, axis=1, kind="stable")
    return perm, occ.sum(1).astype(np.int64)


def align_up(n: int, align: int) -> int:
    """``n`` rounded up to a multiple of ``align``."""
    return -(-int(n) // align) * align


def compact_store(planes: dict, occ: np.ndarray, *,
                  min_capacity: int = 1, align: int = 1) -> tuple[dict, int]:
    """Repack live slots to the front of each partition and shrink capacity
    to the max live count (rounded up to a multiple of ``align``): tombstones
    and free holes are squeezed out, dead tail slots reset to their pad
    sentinels. Returns (planes, new_cap)."""
    perm, live = pack_order(occ)
    new_cap = align_up(max(int(min_capacity), int(live.max(initial=0))), align)
    rows = np.arange(occ.shape[0])[:, None]
    dead = np.arange(new_cap)[None, :] >= live[:, None]     # [B, new_cap]
    out = {}
    for name, arr in planes.items():
        arr = np.asarray(arr)
        g = arr[rows, perm][:, :new_cap]
        if g.shape[1] < new_cap:        # min_capacity floor exceeds the old
            g = grow_store({name: g}, new_cap)[name]        # capacity: widen
        mask = dead.reshape(dead.shape + (1,) * (g.ndim - 2))
        out[name] = np.where(mask, np.asarray(fill_value(name), g.dtype), g)
    return out, new_cap


def layout_rows(assign: np.ndarray, n_partitions: int):
    """Contiguous slot layout for a full rebuild: rows with the same
    partition get slots 0..count-1 in stable input order. Returns
    (slots [n], counts [B])."""
    assign = np.asarray(assign, np.int64)
    order = np.argsort(assign, kind="stable")
    counts = np.bincount(assign, minlength=n_partitions).astype(np.int64)
    start = np.zeros(n_partitions + 1, np.int64)
    np.cumsum(counts, out=start[1:])
    slots = np.empty(len(assign), np.int64)
    slots[order] = np.arange(len(assign)) - start[assign[order]]
    return slots, counts
