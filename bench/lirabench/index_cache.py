"""Build a configuration's index once per checkout, then load it.

``LiraEngine.build`` takes minutes on the host, far more than a run may
spend, so the first run of a configuration in a checkout builds the index
and keeps it under ``bench/.cache/index/<config>/<key>/``; later runs load
it. The key digests the configuration's dataset and index sections, the
program's source (``src/repro``), the device kind and the JAX version, so a
change to any of them builds anew. At most two indexes per configuration are
kept.

What is kept is every part of the built engine except the padded f32 vector
plane (6.4 GB at SIFT1M scale, 97% of the index): that plane is a pure
function of the corpus and the id plane (slot ``(b, s)`` holds
``base[ids[b, s]]``, or the build's pad row where ``ids < 0``), so a load
rebuilds it on the device from the regenerated corpus in one jitted gather
instead of writing and reading it. The pad row is read from the built plane
and kept in ``meta.json``; an index kept without one was padded with ``1e6``,
as ``core/partitions.build_store`` pads under squared L2. The build run
checks the rebuilt plane against the built one, bit for bit, before anything
is kept. Where they differ (a program whose store no longer gathers its plane
from the corpus this way), nothing is kept and the run serves the engine it
built, with a warning: every run of that program then pays the build.

This reads the store's layout (the plane names, the padding, how the
engine is put together from its parts). It belongs in ``LiraEngine`` as a
compact save; until the program has one, the benchmark keeps it here.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import pathlib
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

PAD_DEFAULT = 1e6        # the pad of an index kept before meta.json held one
KEEP = 2                 # indexes kept per configuration


def src_digest(root: pathlib.Path) -> str:
    h = hashlib.sha256()
    for p in sorted((root / "src" / "repro").rglob("*.py")):
        h.update(str(p.relative_to(root)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


def cache_key(root: pathlib.Path, cfg: dict, device_kind: str) -> str:
    blob = json.dumps({"dataset": cfg["dataset"], "index": cfg["index"],
                       "src": src_digest(root), "device": device_kind,
                       "jax": jax.__version__}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _flat(tree) -> dict:
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in leaves}


def _unflat(template, flat: dict):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(flat[jax.tree_util.keystr(path)]) for path, _ in leaves])


@functools.partial(jax.jit, static_argnames=("dtype", "sharding"))
def _vectors(base, ids, pad, dtype, sharding):
    # one gather from the corpus with the pad row appended: no [B, cap, d]
    # intermediate beside the result
    ext = jnp.concatenate([base, pad[None].astype(base.dtype)])
    out = ext[jnp.where(ids >= 0, ids, base.shape[0])].astype(dtype)
    return jax.lax.with_sharding_constraint(out, sharding)


def pad_row(ids: np.ndarray, vectors) -> np.ndarray:
    """The built plane's row at its first slot with an id < 0, as float32;
    ``PAD_DEFAULT`` where no slot is padded (the pad is then never read)."""
    holes = np.argwhere(ids < 0)
    if not len(holes):
        return np.full(vectors.shape[-1], PAD_DEFAULT, np.float32)
    b, s = holes[0]
    return np.asarray(vectors[b, s], np.float32)


def rebuild_vectors(base, ids, pad: np.ndarray, dtype: str, mesh):
    """The padded [B, capacity, d] vector plane, on the device, placed as the
    engine places it."""
    sharding = NamedSharding(mesh, P("model", None, None))
    return _vectors(base, jnp.asarray(ids), jnp.asarray(pad), jnp.dtype(dtype).name, sharding)


def _build(cfg: dict, base_np: np.ndarray, learn, mesh):
    """``LiraEngine.build`` on the corpus. A configuration whose
    ``dataset.metric`` is not ``l2`` also hands the build ``metric=``, and one
    that declares ``dataset.queries.n_learn`` the learn pool as
    ``train_queries=``; a program that takes no such keyword fails on the
    call, naming it. ``l2`` without a learn pool passes neither."""
    from repro.serving import BuildConfig, LiraEngine

    ds, ix = cfg["dataset"], cfg["index"]
    fields = {f.name for f in dataclasses.fields(BuildConfig)}
    kwargs = {}
    if ds["metric"] != "l2":
        kwargs["metric"] = ds["metric"]
    if ds.get("queries", {}).get("n_learn"):
        kwargs["train_queries"] = np.asarray(learn)
    return LiraEngine.build(mesh, base_np, BuildConfig(**{k: v for k, v in ix.items()
                                                          if k in fields}), **kwargs)


def _rebuilds(engine, base, pad: np.ndarray, mesh) -> bool:
    """Whether the rebuilt vector plane equals the built one, checked 64
    partitions at a time."""
    ids = np.asarray(engine.store["ids"])
    built = engine.store["vectors"]
    step = min(64, ids.shape[0])
    for b0 in range(0, ids.shape[0], step):
        got = _vectors(base, jnp.asarray(ids[b0:b0 + step]), jnp.asarray(pad), built.dtype.name,
                       NamedSharding(mesh, P()))
        if got.shape != built[b0:b0 + step].shape or not bool(
                jnp.array_equal(got, built[b0:b0 + step])):
            return False
    return True


def _save(engine, directory: pathlib.Path, meta: dict) -> None:
    tmp = directory.with_name(directory.name + ".tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    arrays = {"params" + k: v for k, v in _flat(engine.params).items()}
    arrays.update({"store/" + n: np.asarray(a) for n, a in engine.store.items()
                   if n != "vectors"})
    np.savez(tmp / "index.npz", **arrays)
    meta = dict(meta, config=dataclasses.asdict(engine.cfg), sigma=float(engine.sigma))
    (tmp / "meta.json").write_text(json.dumps(meta, indent=1, default=list))
    shutil.rmtree(directory, ignore_errors=True)
    tmp.rename(directory)


def _prune(parent: pathlib.Path, keep: pathlib.Path) -> None:
    done = [d for d in parent.iterdir() if (d / "meta.json").exists() and d != keep]
    done.sort(key=lambda d: d.stat().st_mtime, reverse=True)
    for d in done[KEEP - 1:]:
        shutil.rmtree(d, ignore_errors=True)
    for d in parent.glob("*.tmp"):
        shutil.rmtree(d, ignore_errors=True)


def load(directory: pathlib.Path, base, mesh):
    from repro.configs.base import LiraSystemConfig
    from repro.serving import LiraEngine
    from repro.serving.engine import probing_param_specs_cache

    t = time.perf_counter()
    meta = json.loads((directory / "meta.json").read_text())
    raw = {k: tuple(v) if isinstance(v, list) else v for k, v in meta["config"].items()}
    cfg = LiraSystemConfig(**raw)
    with np.load(directory / "index.npz") as z:
        flat = {k[len("params"):]: z[k] for k in z.files if k.startswith("params")}
        host = {k[len("store/"):]: z[k] for k in z.files if k.startswith("store/")}
    read_s = time.perf_counter() - t
    t = time.perf_counter()
    store = {k: jnp.asarray(v) for k, v in host.items()}
    params = _unflat(probing_param_specs_cache(cfg), flat)
    pad = np.broadcast_to(np.asarray(meta.get("pad", PAD_DEFAULT), np.float32), (cfg.dim,))
    store["vectors"] = rebuild_vectors(base, store["ids"], pad, cfg.store_dtype, mesh)
    engine = LiraEngine(cfg=cfg, params=params, store=store, mesh=mesh,
                        sigma=float(meta["sigma"])).place()
    jax.block_until_ready(engine.store)
    return engine, {"read_s": read_s, "place_s": time.perf_counter() - t}


def load_or_build(root: pathlib.Path, cfg: dict, base, base_np: np.ndarray, learn, mesh,
                  corpus_fp: str, device_kind: str, log) -> tuple:
    """(engine, info): info holds ``built``, the seconds of the build and of
    keeping it (if the index was built), of reading the kept index and of
    placing it on the device."""
    parent = root / "bench" / ".cache" / "index" / cfg["name"]
    directory = parent / cache_key(root, cfg, device_kind)
    info = {"built": False}
    if (directory / "meta.json").exists():
        meta = json.loads((directory / "meta.json").read_text())
        if meta.get("corpus") != corpus_fp:
            raise RuntimeError(f"cached index {directory} was built from another corpus")
    else:
        t = time.perf_counter()
        engine = _build(cfg, base_np, learn, mesh)
        build_s = time.perf_counter() - t
        info["built"] = True
        log(f"index build: {build_s:.3f} s; capacity {engine.cfg.capacity} slots "
            f"per partition ({cfg['index']['n_partitions']} partitions)")
        pad = pad_row(np.asarray(engine.store["ids"]), engine.store["vectors"])
        if not _rebuilds(engine, base, pad, mesh):
            log("WARNING: the vector plane rebuilt from the corpus differs from the "
                "built one; the index is not kept and every run builds it")
            t = time.perf_counter()
            engine = engine.place()
            jax.block_until_ready(engine.store)
            info.update(build_s=build_s, read_s=0.0, place_s=time.perf_counter() - t)
            return engine, info
        parent.mkdir(parents=True, exist_ok=True)
        _save(engine, directory, {"corpus": corpus_fp, "build_s": build_s,
                                  "pad": pad.tolist()})
        del engine
        _prune(parent, directory)
        info["build_s"] = time.perf_counter() - t
        log(f"index build and keep: {info['build_s']:.3f} s (a checkout's first run "
            f"only; not in setup_s)")
    engine, split = load(directory, base, mesh)
    info.update(split)
    return engine, info
