"""DimeNet (Klicpera et al., arXiv:2003.03123) — directional message passing
with triplet interactions, adapted to TPU pods.

Kernel regime: triplet gather (kernel_taxonomy §GNN) — NOT expressible as SpMM.
Message passing is implemented with jax.ops.segment_sum over edge/triplet index
lists (this IS part of the system: JAX sparse is BCOO-only).

Distribution (DESIGN.md §5):
  * node arrays REPLICATED (≤2.4M·128 f32 ≈ 1.2 GB — fits every assigned shape);
  * edge arrays sharded over the flattened mesh (all axes);
  * triplets sharded ALIGNED WITH THEIR ji EDGE (data layer sorts triplets by
    ji), so the triplet→edge segment_sum is collective-free;
  * the edge→triplet gather m[kj] crosses shards: shard_map partial-gather
    (local-range rows, zeros elsewhere) + psum — memory O(E/shards), collective
    O(T·H) per block (the dominant roofline term for big graphs; §Perf
    hillclimbs it with locality-aware edge ordering);
  * edge→node segment_sum: local partial [N, H] + psum.

Simplifications vs the paper (noted per DESIGN.md §7): the spherical basis uses
a Chebyshev angular × sinc radial product instead of spherical Bessel roots —
identical shapes/compute pattern, same n_spherical × n_radial feature count.
Non-molecular graph shapes synthesize 3D positions (DimeNet needs geometry;
the assignment pairs it with citation/product graphs).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import GNNConfig
from repro.models.api import ModelBundle, ShapeSpec, StepDef, adamw_state_pspecs, adamw_state_specs, sds
from repro.train import optimizer as opt


# ----------------------------------------------------------------- bases

def envelope(d, cutoff, p: int = 6):
    x = d / cutoff
    return (1.0 - (p + 1) * (p + 2) / 2 * x**p + p * (p + 2) * x ** (p + 1)
            - p * (p + 1) / 2 * x ** (p + 2)) * (x < 1.0)


def radial_basis(d, n_radial: int, cutoff: float = 5.0):
    """sin(nπ d/c)/d with smooth envelope. [E] -> [E, n_radial]."""
    d = jnp.maximum(d, 1e-6)[:, None]
    n = jnp.arange(1, n_radial + 1, dtype=jnp.float32)
    return envelope(d, cutoff) * jnp.sqrt(2.0 / cutoff) * jnp.sin(n * jnp.pi * d / cutoff) / d


def spherical_basis(angle, d, n_spherical: int, n_radial: int, cutoff: float = 5.0):
    """Chebyshev(cos θ) × radial product basis. [T] -> [T, n_spherical*n_radial]."""
    cosang = jnp.clip(jnp.cos(angle), -1.0, 1.0)[:, None]
    ls = jnp.arange(n_spherical, dtype=jnp.float32)
    ang = jnp.cos(ls * jnp.arccos(cosang))                       # [T, S]
    rad = radial_basis(d, n_radial, cutoff)                      # [T, R]
    return (ang[:, :, None] * rad[:, None, :]).reshape(d.shape[0], -1)


# ----------------------------------------------------------------- sharded ops

def _flat_axes(mesh):
    return tuple(mesh.axis_names)


def sharded_edge_gather(edge_feat, idx, mesh):
    """m[idx] where edge_feat [E, H] and idx [T] are both sharded over the
    flattened mesh: partial local gather + psum (no replication of edge_feat)."""
    axes = _flat_axes(mesh)

    def f(m_loc, idx_loc):
        e_loc = m_loc.shape[0]
        fi = jnp.zeros((), jnp.int32)
        for ax in axes:
            fi = fi * mesh.shape[ax] + jax.lax.axis_index(ax)
        e0 = fi * e_loc
        rel = idx_loc - e0
        ok = (rel >= 0) & (rel < e_loc)
        part = jnp.where(ok[:, None], m_loc[jnp.clip(rel, 0, e_loc - 1)], 0.0)
        return jax.lax.psum(part, axes)

    spec = P(axes if len(axes) > 1 else axes[0])
    return jax.shard_map(f, mesh=mesh, in_specs=(P(spec[0], None), spec), out_specs=P(spec[0], None),
                         check_vma=False)(edge_feat, idx)


def sharded_segment_to_nodes(edge_feat, dst, n_nodes: int, mesh):
    """segment_sum sharded-edges -> replicated nodes: local partial + psum."""
    axes = _flat_axes(mesh)

    def f(m_loc, dst_loc):
        part = jax.ops.segment_sum(m_loc, dst_loc, num_segments=n_nodes)
        return jax.lax.psum(part, axes)

    spec = axes if len(axes) > 1 else axes[0]
    return jax.shard_map(f, mesh=mesh, in_specs=(P(spec, None), P(spec)), out_specs=P(None, None),
                         check_vma=False)(edge_feat, dst)


def local_segment_to_edges(trip_feat, ji_local, n_edges_local_total: int, mesh):
    """Triplet->edge segment_sum; triplets are pre-aligned to their ji shard so
    this is collective-free (ids are LOCAL edge offsets)."""
    axes = _flat_axes(mesh)
    nshard = int(np.prod([mesh.shape[a] for a in axes]))
    e_loc = n_edges_local_total // nshard

    def f(t_loc, ji_loc):
        return jax.ops.segment_sum(t_loc, ji_loc, num_segments=e_loc)

    spec = axes if len(axes) > 1 else axes[0]
    return jax.shard_map(f, mesh=mesh, in_specs=(P(spec, None), P(spec)), out_specs=P(spec, None),
                         check_vma=False)(trip_feat, ji_local)


# ----------------------------------------------------------------- params

def _param_defs(cfg: GNNConfig, d_feat: int) -> dict:
    h, nb, ns, nr = cfg.d_hidden, cfg.n_blocks, cfg.n_spherical, cfg.n_radial
    nbl = cfg.n_bilinear
    d_in = d_feat if d_feat > 0 else 16  # atom-type embedding width
    return {
        "node_proj": ((d_in, h), None),
        "atom_embed": ((100, 16), None),          # used when d_feat == 0
        "rbf_proj": ((nr, h), None),
        "edge_w": ((3 * h, h), None),
        "blocks.w_sbf": ((nb, ns * nr, nbl), None),
        "blocks.w_kj": ((nb, h, h), None),
        "blocks.w_bil": ((nb, nbl, h, h), None),
        "blocks.w_e1": ((nb, h, h), None),
        "blocks.w_e2": ((nb, h, h), None),
        "blocks.out_rbf": ((nb, nr, h), None),
        "blocks.out_w": ((nb, h, h), None),
        "readout1": ((h, h), None),
        "readout2": ((h, 1), None),
    }


def _nest(flat):
    out = {}
    for k, v in flat.items():
        node = out
        parts = k.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def param_specs(cfg: GNNConfig, d_feat: int):
    return _nest({k: sds(s, jnp.float32) for k, (s, _) in _param_defs(cfg, d_feat).items()})


def param_pspecs(cfg: GNNConfig, d_feat: int, mesh):
    return _nest({k: P() for k in _param_defs(cfg, d_feat)})  # params replicated (tiny)


def init_params(rng, cfg: GNNConfig, d_feat: int):
    defs = _param_defs(cfg, d_feat)
    keys = jax.random.split(rng, len(defs))
    flat = {}
    for key, (path, (shape, _)) in zip(keys, defs.items()):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        flat[path] = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    return _nest(flat)


# ----------------------------------------------------------------- forward

def forward(params, batch, cfg: GNNConfig, mesh, *, n_nodes: int, d_feat: int):
    """batch: pos [N,3], feat [N,d_feat] or z [N], edge src/dst [E], triplet
    kj [T] (global edge ids), ji_local [T] (edge offset within owning shard),
    edge_mask [E], trip_mask [T]. Returns per-node scalar predictions [N]."""
    pos = batch["pos"]
    src, dst = batch["src"], batch["dst"]
    emask = batch["edge_mask"].astype(jnp.float32)[:, None]
    tmask = batch["trip_mask"].astype(jnp.float32)[:, None]

    if d_feat > 0:
        hx = batch["feat"] @ params["node_proj"]
    else:
        hx = params["atom_embed"][batch["z"]] @ params["node_proj"]
    hx = jax.nn.silu(hx)                                        # [N, H] replicated

    vec = pos[dst] - pos[src]                                   # [E, 3] sharded
    dist = jnp.linalg.norm(vec + 1e-9, axis=-1)
    rbf = radial_basis(dist, cfg.n_radial)                      # [E, R]

    m = jax.nn.silu(
        jnp.concatenate([hx[src], hx[dst], rbf @ params["rbf_proj"]], -1) @ params["edge_w"]
    ) * emask                                                   # [E, H]

    # triplet geometry: angle between edge ji and edge kj at vertex j
    kj = batch["trip_kj"]
    ji_glob = batch["trip_ji"]
    v_ji = sharded_edge_gather(vec, ji_glob, mesh)              # [T, 3]
    v_kj = sharded_edge_gather(vec, kj, mesh)
    cos_t = jnp.sum(-v_ji * v_kj, -1) / (
        jnp.linalg.norm(v_ji, axis=-1) * jnp.linalg.norm(v_kj, axis=-1) + 1e-9)
    angle = jnp.arccos(jnp.clip(cos_t, -1 + 1e-6, 1 - 1e-6))
    d_kj = sharded_edge_gather(dist[:, None], kj, mesh)[:, 0]
    sbf = spherical_basis(angle, d_kj, cfg.n_spherical, cfg.n_radial)  # [T, S*R]

    node_out = jnp.zeros((n_nodes, cfg.d_hidden), jnp.float32)
    n_edges = m.shape[0]

    def block(carry, bp):
        m, node_out = carry
        a = sbf @ bp["w_sbf"]                                   # [T, nbl]
        u = sharded_edge_gather(m, kj, mesh) @ bp["w_kj"]       # [T, H]
        msg = jnp.zeros_like(u)
        for b in range(cfg.n_bilinear):                         # unrolled bilinear
            msg = msg + a[:, b:b + 1] * (u @ bp["w_bil"][b])
        msg = msg * tmask
        agg = local_segment_to_edges(msg, batch["trip_ji_local"], n_edges, mesh)
        m = (m + jax.nn.silu(jax.nn.silu((m + agg) @ bp["w_e1"]) @ bp["w_e2"])) * emask
        contrib = sharded_segment_to_nodes((rbf @ bp["out_rbf"]) * m, dst, n_nodes, mesh)
        node_out = node_out + contrib @ bp["out_w"]
        return (m, node_out), None

    blk = block
    if cfg.remat == "full":
        blk = jax.checkpoint(block, policy=jax.checkpoint_policies.nothing_saveable)
    (m, node_out), _ = jax.lax.scan(blk, (m, node_out), params["blocks"])
    return (jax.nn.silu(node_out @ params["readout1"]) @ params["readout2"])[:, 0]  # [N]


# ----------------------------------------------------------------- steps

def make_train_step(cfg: GNNConfig, mesh, tx, *, n_nodes: int, d_feat: int):
    def train_step(state, batch):
        params, opt_state = state

        def loss_fn(p):
            pred = forward(p, batch, cfg, mesh, n_nodes=n_nodes, d_feat=d_feat)
            mask = batch["node_mask"].astype(jnp.float32)
            return jnp.sum(((pred - batch["target"]) ** 2) * mask) / jnp.maximum(mask.sum(), 1.0)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, gnorm = opt.clip_by_global_norm(grads, 1.0)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = opt.apply_updates(params, updates)
        return (params, opt_state), {"loss": loss, "grad_norm": gnorm}

    return train_step


def _pad_to(n, mult):
    return int(-(-n // mult) * mult)


def make_bundle(cfg: GNNConfig, mesh) -> ModelBundle:
    axes = tuple(mesh.axis_names)
    nshard = int(np.prod([mesh.shape[a] for a in axes]))
    espec = P(axes if len(axes) > 1 else axes[0])
    tx = opt.adamw(opt.cosine_schedule(1e-3, 100, 10_000))

    def step(shape: ShapeSpec) -> StepDef:
        assert shape.kind == "graph_train"
        n_graphs = shape.dims.get("batch", 1)
        n_nodes = shape["n_nodes"] * n_graphs
        n_edges = _pad_to(shape["n_edges"] * n_graphs, max(nshard, 256))
        n_trip = _pad_to(shape["n_edges"] * n_graphs * shape["triplet_mult"], max(nshard, 256))
        d_feat = shape["d_feat"]
        fn = make_train_step(cfg, mesh, tx, n_nodes=n_nodes, d_feat=d_feat)
        specs = {
            "pos": sds((n_nodes, 3)),
            "src": sds((n_edges,), jnp.int32),
            "dst": sds((n_edges,), jnp.int32),
            "trip_kj": sds((n_trip,), jnp.int32),
            "trip_ji": sds((n_trip,), jnp.int32),
            "trip_ji_local": sds((n_trip,), jnp.int32),
            "edge_mask": sds((n_edges,), jnp.int32),
            "trip_mask": sds((n_trip,), jnp.int32),
            "node_mask": sds((n_nodes,), jnp.int32),
            "target": sds((n_nodes,)),
        }
        if d_feat > 0:
            specs["feat"] = sds((n_nodes, d_feat))
        else:
            specs["z"] = sds((n_nodes,), jnp.int32)
        pspecs = {
            "pos": P(None, None), "node_mask": P(None), "target": P(None),
            "src": espec, "dst": espec, "edge_mask": espec,
            "trip_kj": espec, "trip_ji": espec, "trip_ji_local": espec, "trip_mask": espec,
        }
        pspecs["feat" if d_feat > 0 else "z"] = P(None, None) if d_feat > 0 else P(None)
        return StepDef(fn=fn, input_specs=specs, input_pspecs=pspecs, out_pspecs=None)

    # node_proj input width follows the shape's d_feat (non-molecular graphs
    # project raw features; molecules use the atom-type embedding).
    def _dfeat(shape):
        return shape["d_feat"] if shape is not None else 0

    return ModelBundle(
        name=cfg.arch,
        config=cfg,
        init=lambda rng, shape=None: init_params(rng, cfg, _dfeat(shape)),
        param_specs=lambda shape=None: param_specs(cfg, _dfeat(shape)),
        param_pspecs=lambda shape=None: param_pspecs(cfg, _dfeat(shape), mesh),
        step=step,
        opt_specs=lambda shape=None: adamw_state_specs(param_specs(cfg, _dfeat(shape))),
        opt_pspecs=lambda shape=None: adamw_state_pspecs(param_pspecs(cfg, _dfeat(shape), mesh)),
    )
