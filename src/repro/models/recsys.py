"""RecSys architectures: DeepFM, AutoInt, MIND, DLRM-RM2.

JAX has no native EmbeddingBag or CSR sparse — the sharded EmbeddingBag here
(take + segment/bag-sum inside shard_map, tables row-sharded over "model",
psum combine) IS part of the system (kernel_taxonomy §RecSys).

Distribution: embedding tables [F, V, dim] sharded P(None, "model", None) —
each model shard owns a contiguous V-range of every field's table; lookups
mask to the local range and psum over "model". Dense MLPs are data-parallel
with replicated weights. ``retrieval_cand`` scores 1M candidates through the
FULL interaction model (batch = candidates) and finishes with a global top-k;
the LIRA-accelerated variant (the paper's technique applied to this arch) is
in repro/serving and §Perf.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import RecsysConfig
from repro.models.api import ModelBundle, ShapeSpec, StepDef, adamw_state_pspecs, adamw_state_specs, sds
from repro.train import optimizer as opt


# ------------------------------------------------------------ embedding bag

def embedding_bag(tables, ids, mesh, batch_axes):
    """tables: [F, V, dim] sharded P(None, 'model', None); ids: [B, F, nnz]
    sharded on batch. Returns [B, F, dim] (bag-sum over nnz)."""
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    model_n = mesh.shape.get("model", 1)
    v = tables.shape[1]
    v_loc = v // model_n

    def f(tab_loc, ids_loc):
        # tab_loc: [F, V_loc, dim]; ids_loc: [B_loc, F, nnz]
        v0 = jax.lax.axis_index("model") * v_loc if model_n > 1 else 0
        rel = ids_loc - v0
        ok = (rel >= 0) & (rel < v_loc)
        g = _gather_fields(tab_loc, jnp.clip(rel, 0, v_loc - 1))  # [B, F, nnz, dim]
        g = jnp.where(ok[..., None], g, 0.0)
        out = g.sum(2)  # bag-sum over nnz -> [B_loc, F, dim]
        if model_n > 1:
            out = jax.lax.psum(out, "model")
        return out

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(None, "model", None), P(bspec, None, None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(tables, ids)


def _gather_fields(tab_loc, rel):
    """tab_loc [F, V_loc, dim], rel [B, F, nnz] -> [B, F, nnz, dim]."""
    def per_field(tab_f, ids_f):  # [V_loc, dim], [B, nnz]
        return tab_f[ids_f]       # [B, nnz, dim]
    out = jax.vmap(per_field, in_axes=(0, 1), out_axes=1)(tab_loc, rel)
    return out  # [B, F, nnz, dim]


def _mlp(params, x, act=jax.nn.relu, final_act=False):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if final_act or i + 1 < len(params):
            x = act(x)
    return x


def _mlp_defs(prefix, sizes):
    out = {}
    for i, (fi, fo) in enumerate(zip(sizes[:-1], sizes[1:])):
        out[f"{prefix}.{i}.w"] = ((fi, fo), None)
        out[f"{prefix}.{i}.b"] = ((fo,), None)
    return out


# ------------------------------------------------------------ interactions

def fm_interaction(emb):
    """emb [B, F, dim] -> scalar FM 2nd-order term (sum-square trick)."""
    s = emb.sum(1)
    return 0.5 * (s * s - (emb * emb).sum(1)).sum(-1)


def dot_interaction(z):
    """z [B, F, dim] -> lower-triangle pairwise dots [B, F(F-1)/2]."""
    b, f, d = z.shape
    g = jnp.einsum("bfd,bgd->bfg", z, z)
    iu, ju = np.tril_indices(f, k=-1)
    return g[:, iu, ju]


def autoint_layer(x, wq, wk, wv, wres, n_heads: int):
    """x [B, F, dim] -> multi-head field self-attention (AutoInt eq. 6-8)."""
    b, f, d = x.shape
    q = (x @ wq).reshape(b, f, n_heads, -1)
    k = (x @ wk).reshape(b, f, n_heads, -1)
    v = (x @ wv).reshape(b, f, n_heads, -1)
    att = jax.nn.softmax(jnp.einsum("bfhd,bghd->bhfg", q, k) / math.sqrt(q.shape[-1]), -1)
    o = jnp.einsum("bhfg,bghd->bfhd", att, v).reshape(b, f, -1)
    return jax.nn.relu(o + x @ wres)


def capsule_routing(hist_emb, hist_mask, s_bilinear, n_interests: int, iters: int):
    """MIND B2I dynamic routing. hist_emb [B, T, dim] -> interests [B, K, dim]."""
    b, t, d = hist_emb.shape
    u = hist_emb @ s_bilinear                                    # [B, T, dim]
    blogit = jnp.zeros((b, n_interests, t), jnp.float32)
    neg = jnp.where(hist_mask[:, None, :] > 0, 0.0, -1e30)
    caps = jnp.zeros((b, n_interests, d), u.dtype)
    for _ in range(iters):
        w = jax.nn.softmax(blogit + neg, axis=1)                 # over interests
        caps = jnp.einsum("bkt,btd->bkd", w, u)
        norm2 = jnp.sum(caps * caps, -1, keepdims=True)
        caps = caps * (norm2 / (1 + norm2)) / jnp.sqrt(norm2 + 1e-9)  # squash
        blogit = blogit + jnp.einsum("bkd,btd->bkt", caps, u)
    return caps


# ------------------------------------------------------------ param defs

def _param_defs(cfg: RecsysConfig) -> dict:
    f, v, d = cfg.n_sparse, cfg.vocab_per_field, cfg.embed_dim
    defs = {"tables": ((f, v, d), (None, "rows", None))}
    if cfg.interaction == "fm":               # DeepFM
        defs["wide"] = ((f, v, 1), (None, "rows", None))
        defs.update(_mlp_defs("deep", (f * d, *cfg.mlp, 1)))
    elif cfg.interaction == "self-attn":      # AutoInt
        da = cfg.d_attn * cfg.n_heads
        for i in range(cfg.n_attn_layers):
            d_in = d if i == 0 else da
            defs.update({
                f"attn.{i}.wq": ((d_in, da), None), f"attn.{i}.wk": ((d_in, da), None),
                f"attn.{i}.wv": ((d_in, da), None), f"attn.{i}.wres": ((d_in, da), None),
            })
        defs.update(_mlp_defs("head", (f * da, 1)))
    elif cfg.interaction == "multi-interest":  # MIND
        defs["s_bilinear"] = ((d, d), None)
        defs.update(_mlp_defs("head", (d, 2 * d, d)))
    elif cfg.interaction == "dot":            # DLRM
        defs.update(_mlp_defs("bot", tuple(cfg.bot_mlp)))
        n_f = cfg.n_sparse + 1
        d_int = n_f * (n_f - 1) // 2 + cfg.bot_mlp[-1]
        defs.update(_mlp_defs("top", (d_int, *cfg.top_mlp)))
    else:
        raise ValueError(cfg.interaction)
    return defs


def _nest(flat):
    out = {}
    for k, val in flat.items():
        node = out
        parts = k.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return out


def param_specs(cfg: RecsysConfig):
    return _nest({k: sds(s, jnp.float32) for k, (s, _) in _param_defs(cfg).items()})


def param_pspecs(cfg: RecsysConfig, mesh):
    from repro.distributed.sharding import logical_to_pspec

    out = {}
    for k, (shape, ax) in _param_defs(cfg).items():
        if ax is None:
            out[k] = P()
        else:
            out[k] = logical_to_pspec(ax, mesh)
    return _nest(out)


def init_params(rng, cfg: RecsysConfig):
    defs = _param_defs(cfg)
    keys = jax.random.split(rng, len(defs))
    flat = {}
    for key, (path, (shape, _)) in zip(keys, defs.items()):
        if path.endswith(".b"):
            flat[path] = jnp.zeros(shape, jnp.float32)
        else:
            scale = 0.01 if path in ("tables", "wide") else 1.0 / math.sqrt(shape[-2] if len(shape) > 1 else shape[-1])
            flat[path] = jax.random.normal(key, shape, jnp.float32) * scale
    return _nest(flat)


def _collect_mlp(params, prefix):
    node = params.get(prefix, {})
    layers = []
    i = 0
    while str(i) in node:
        layers.append(node[str(i)])
        i += 1
    return layers


# ------------------------------------------------------------ forward

def forward(params, batch, cfg: RecsysConfig, mesh, batch_axes):
    """Returns per-example score [B]."""
    emb = embedding_bag(params["tables"], batch["sparse_ids"], mesh, batch_axes)  # [B, F, d]
    b = emb.shape[0]
    if cfg.interaction == "fm":
        wide = embedding_bag(params["wide"], batch["sparse_ids"], mesh, batch_axes)[..., 0].sum(-1)
        fm = fm_interaction(emb)
        deep = _mlp(_collect_mlp(params, "deep"), emb.reshape(b, -1))[:, 0]
        return wide + fm + deep
    if cfg.interaction == "self-attn":
        x = emb
        for i in range(cfg.n_attn_layers):
            a = params["attn"][str(i)]
            x = autoint_layer(x, a["wq"], a["wk"], a["wv"], a["wres"], cfg.n_heads)
        return _mlp(_collect_mlp(params, "head"), x.reshape(b, -1))[:, 0]
    if cfg.interaction == "multi-interest":
        hist = embedding_bag(
            params["tables"], batch["hist_ids"][:, None, :], mesh, batch_axes
        )  # [B, 1, T(dim?)] — hist_ids as one "field" of nnz=T WITHOUT bag-sum:
        raise RuntimeError("MIND uses mind_forward")
    if cfg.interaction == "dot":
        dense = _mlp(_collect_mlp(params, "bot"), batch["dense"], final_act=True)  # [B, d]
        z = jnp.concatenate([dense[:, None, :], emb], 1)
        inter = dot_interaction(z)
        top_in = jnp.concatenate([dense, inter], -1)
        return _mlp(_collect_mlp(params, "top"), top_in)[:, 0]
    raise ValueError(cfg.interaction)


def embedding_seq(tables, ids, mesh, batch_axes, field: int = 0):
    """Sequence lookup WITHOUT bag-sum: ids [B, T] -> [B, T, dim] (MIND hist)."""
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    model_n = mesh.shape.get("model", 1)
    v = tables.shape[1]
    v_loc = v // model_n

    def f(tab_loc, ids_loc):
        v0 = jax.lax.axis_index("model") * v_loc if model_n > 1 else 0
        rel = ids_loc - v0
        ok = (rel >= 0) & (rel < v_loc)
        g = tab_loc[field][jnp.clip(rel, 0, v_loc - 1)]
        g = jnp.where(ok[..., None], g, 0.0)
        if model_n > 1:
            g = jax.lax.psum(g, "model")
        return g

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(None, "model", None), P(bspec, None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(tables, ids)


def mind_forward(params, batch, cfg: RecsysConfig, mesh, batch_axes):
    """MIND: behaviour seq -> K interests; score = max_k <interest, target>."""
    hist = embedding_seq(params["tables"], batch["hist_ids"], mesh, batch_axes)   # [B, T, d]
    caps = capsule_routing(hist, batch["hist_mask"], params["s_bilinear"],
                           cfg.n_interests, cfg.capsule_iters)                     # [B, K, d]
    caps = _mlp(_collect_mlp(params, "head"), caps, final_act=False)
    target = embedding_seq(params["tables"], batch["target_id"][:, None], mesh, batch_axes)[:, 0]
    return jnp.max(jnp.einsum("bkd,bd->bk", caps, target), -1)                     # [B]


# ------------------------------------------------------------ steps

def make_train_step(cfg: RecsysConfig, mesh, tx, batch_axes):
    fwd = mind_forward if cfg.interaction == "multi-interest" else forward

    def train_step(state, batch):
        params, opt_state = state

        def loss_fn(p):
            score = fwd(p, batch, cfg, mesh, batch_axes)
            y = batch["label"]
            return -jnp.mean(y * jax.nn.log_sigmoid(score) + (1 - y) * jax.nn.log_sigmoid(-score))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, gnorm = opt.clip_by_global_norm(grads, 1.0)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = opt.apply_updates(params, updates)
        return (params, opt_state), {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_serve_step(cfg: RecsysConfig, mesh, batch_axes, *, topk: int = 0):
    fwd = mind_forward if cfg.interaction == "multi-interest" else forward

    def serve_step(params, batch):
        score = fwd(params, batch, cfg, mesh, batch_axes)
        if topk:
            vals, idx = jax.lax.top_k(score, topk)
            return vals, idx.astype(jnp.int32)
        return score

    return serve_step


def _batch_specs(cfg: RecsysConfig, b: int, bspec):
    specs = {
        "sparse_ids": sds((b, cfg.n_sparse, cfg.nnz), jnp.int32),
        "label": sds((b,)),
    }
    pspecs = {"sparse_ids": P(bspec, None, None), "label": P(bspec)}
    if cfg.n_dense:
        specs["dense"] = sds((b, cfg.n_dense))
        pspecs["dense"] = P(bspec, None)
    if cfg.interaction == "multi-interest":
        specs.update({
            "hist_ids": sds((b, cfg.hist_len), jnp.int32),
            "hist_mask": sds((b, cfg.hist_len)),
            "target_id": sds((b,), jnp.int32),
        })
        pspecs.update({"hist_ids": P(bspec, None), "hist_mask": P(bspec, None), "target_id": P(bspec)})
    return specs, pspecs


def make_bundle(cfg: RecsysConfig, mesh) -> ModelBundle:
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    tx = opt.adamw(opt.cosine_schedule(1e-3, 100, 100_000))

    def step(shape: ShapeSpec) -> StepDef:
        if shape.kind == "rec_train":
            b = shape["batch"]
            specs, pspecs = _batch_specs(cfg, b, bspec)
            return StepDef(fn=make_train_step(cfg, mesh, tx, batch_axes),
                           input_specs=specs, input_pspecs=pspecs, out_pspecs=None)
        if shape.kind == "rec_serve":
            b = shape["batch"]
            specs, pspecs = _batch_specs(cfg, b, bspec)
            return StepDef(fn=make_serve_step(cfg, mesh, batch_axes),
                           input_specs=specs, input_pspecs=pspecs, out_pspecs=None)
        if shape.kind == "retrieval":
            b = shape["n_candidates"]  # score every candidate through the model
            specs, pspecs = _batch_specs(cfg, b, bspec)
            return StepDef(fn=make_serve_step(cfg, mesh, batch_axes, topk=100),
                           input_specs=specs, input_pspecs=pspecs, out_pspecs=None)
        raise ValueError(shape.kind)

    return ModelBundle(
        name=cfg.arch,
        config=cfg,
        init=lambda rng, shape=None: init_params(rng, cfg),
        param_specs=lambda shape=None: param_specs(cfg),
        param_pspecs=lambda shape=None: param_pspecs(cfg, mesh),
        step=step,
        opt_specs=lambda shape=None: adamw_state_specs(param_specs(cfg)),
        opt_pspecs=lambda shape=None: adamw_state_pspecs(param_pspecs(cfg, mesh)),
    )
