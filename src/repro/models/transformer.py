"""Dense + MoE GQA transformer LM (the 5 assigned LM architectures).

Execution design (DESIGN.md §5):
  * scan-over-layers with configurable remat — HLO size and live memory are
    O(1) in depth;
  * activations sharded [batch→("pod","data"), seq→"model"] uniformly;
  * weights: flat head layouts [D, H·Dh] (model-axis never divides head
    counts), fsdp("data") × tensor("model") 2D sharding;
  * attention: online-softmax scan over KV blocks (no [S,S] matrix);
  * MoE: shard_map expert parallelism — tokens all-gathered over "model",
    sort-based token-choice dispatch to the local expert shard, psum_scatter
    combine (baseline; `moe_impl="a2a"` is the hillclimbed variant);
  * decode: shard_map flash-decode over a sequence-sharded KV cache with
    logsumexp psum merge (supports 500k-token caches; long_500k shards the
    cache over every mesh axis).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import LMConfig
from repro.distributed.sharding import logical_to_pspec
from repro.models import layers as L
from repro.models.api import ModelBundle, ShapeSpec, StepDef, adamw_state_pspecs, adamw_state_specs, sds
from repro.train import optimizer as opt


# --------------------------------------------------------------- param layout

def _param_defs(cfg: LMConfig) -> dict:
    """path -> (shape, logical_axes). Layer params carry a leading 'stack' axis."""
    d, v = cfg.d_model, cfg.vocab
    h_flat = cfg.n_heads * cfg.head_dim
    kv_flat = cfg.n_kv_heads * cfg.head_dim
    l = cfg.n_layers
    defs = {
        "embed": ((v, d), (None, "fsdp")),
        "unembed": ((d, v), ("fsdp", "vocab")),
        "ln_f": ((d,), (None,)),
        "layers.ln1": ((l, d), ("stack", None)),
        "layers.ln2": ((l, d), ("stack", None)),
        "layers.wq": ((l, d, h_flat), ("stack", "fsdp", "heads_flat")),
        "layers.wk": ((l, d, kv_flat), ("stack", "fsdp", "heads_flat")),
        "layers.wv": ((l, d, kv_flat), ("stack", "fsdp", "heads_flat")),
        "layers.wo": ((l, h_flat, d), ("stack", "heads_flat", "fsdp")),
    }
    if cfg.moe is None:
        f = cfg.d_ff
        defs.update({
            "layers.wi": ((l, d, f), ("stack", "fsdp", "mlp")),
            "layers.wg": ((l, d, f), ("stack", "fsdp", "mlp")),
            "layers.wo_ff": ((l, f, d), ("stack", "mlp", "fsdp")),
        })
    else:
        e, fe = cfg.moe.n_experts, cfg.moe.d_ff_expert
        defs.update({
            "layers.router": ((l, d, e), ("stack", None, None)),
            "layers.wi_e": ((l, e, d, fe), ("stack", "expert", "fsdp", None)),
            "layers.wg_e": ((l, e, d, fe), ("stack", "expert", "fsdp", None)),
            "layers.wo_e": ((l, e, fe, d), ("stack", "expert", None, "fsdp")),
        })
        if cfg.moe.n_shared:
            fs = cfg.moe.n_shared * fe
            defs.update({
                "layers.ws_i": ((l, d, fs), ("stack", "fsdp", "mlp")),
                "layers.ws_g": ((l, d, fs), ("stack", "fsdp", "mlp")),
                "layers.ws_o": ((l, fs, d), ("stack", "mlp", "fsdp")),
            })
    return defs


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, val in flat.items():
        node = out
        parts = path.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return out


def _dtype(cfg: LMConfig):
    return jnp.dtype(cfg.dtype)


def param_specs(cfg: LMConfig):
    return _nest({k: sds(s, _dtype(cfg)) for k, (s, _) in _param_defs(cfg).items()})


def param_pspecs(cfg: LMConfig, mesh):
    return _nest({k: logical_to_pspec(ax, mesh) for k, (_, ax) in _param_defs(cfg).items()})


def init_params(rng: jax.Array, cfg: LMConfig):
    defs = _param_defs(cfg)
    keys = jax.random.split(rng, len(defs))
    flat = {}
    for key, (path, (shape, _)) in zip(keys, defs.items()):
        if path.endswith(("ln1", "ln2", "ln_f")):
            flat[path] = jnp.ones(shape, _dtype(cfg))
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            flat[path] = (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(_dtype(cfg))
    return _nest(flat)


# --------------------------------------------------------------- MoE block

def _moe_block(h, lp, cfg: LMConfig, mesh, batch_axes, *, seq_sharded: bool):
    """shard_map expert parallelism. h: [B, S, D] (S sharded over 'model' when
    seq_sharded). Returns (out, aux_loss)."""
    moe = cfg.moe
    model_n = mesh.shape["model"]
    data_n = mesh.shape.get("data", 1)
    e_loc = moe.n_experts // model_n
    b, s, d = h.shape
    b_loc = b // int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else b
    s_loc = s // model_n if seq_sharded else s
    t_gathered = b_loc * (s if seq_sharded else s_loc)
    capacity = max(1, int(math.ceil(t_gathered * moe.top_k / moe.n_experts * moe.capacity_factor)))

    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    h_spec = P(bspec, "model" if seq_sharded else None, None)
    has_data = "data" in mesh.axis_names
    w_in_spec = P("model", "data" if has_data else None, None)    # per-layer [E, D, F]
    w_out_spec = P("model", None, "data" if has_data else None)   # per-layer [E, F, D]

    use_a2a = cfg.moe_impl == "a2a" and seq_sharded and model_n > 1
    t_loc = b_loc * s_loc
    c_send = max(1, int(math.ceil(t_loc * moe.top_k / model_n * moe.capacity_factor)))
    c_exp = max(1, int(math.ceil(model_n * c_send / e_loc * moe.capacity_factor)))

    def f(h_loc, router_w, wi, wg, wo):
        # h_loc: [B_loc, S_loc, D]; wi/wg: [E_loc, D/data, F]; wo: [E_loc, F, D/data]
        e0_ = jax.lax.axis_index("model") * e_loc
        if data_n > 1:
            wi_f = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
            wg_f = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wo_f = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        else:
            wi_f, wg_f, wo_f = wi, wg, wo
        if use_a2a:
            x_flat = h_loc.reshape(-1, d)
            out = L.moe_a2a_local(x_flat, router_w, e0_, e_loc, model_n, moe.top_k,
                                  c_send, c_exp, wi_f, wg_f, wo_f)
            # aux from local routing stats (approximate under a2a: per-shard)
            probs = jax.nn.softmax(
                jnp.einsum("td,de->te", x_flat, router_w).astype(jnp.float32), -1)
            aux = moe.n_experts * jnp.sum(
                probs.mean(0) * jax.nn.one_hot(jnp.argmax(probs, -1), moe.n_experts).mean(0))
            aux = jax.lax.pmean(aux, "model")
            return out.reshape(h_loc.shape).astype(h_loc.dtype), aux
        if seq_sharded:
            x_all = jax.lax.all_gather(h_loc, "model", axis=1, tiled=True)  # [B_loc, S, D]
        else:
            x_all = h_loc
        tt = x_all.shape[0] * x_all.shape[1]
        x_flat = x_all.reshape(tt, d)
        buf, gbuf, tbuf = L.moe_dispatch_local(x_flat, router_w, e0_, e_loc, moe.top_k, capacity)
        eout = L.moe_expert_ffn(buf, wi_f, wg_f, wo_f)
        out = L.moe_combine_local(eout, gbuf, tbuf, tt).reshape(x_all.shape)
        # load-balance aux (Switch): E * sum_e f_e * p_e over local experts
        probs = jax.nn.softmax(
            jnp.einsum("td,de->te", x_flat, router_w).astype(jnp.float32), -1)
        p_e = probs.mean(0)  # [E] (full E — fine, router replicated)
        assigned = (tbuf < tt).sum(-1).astype(jnp.float32)  # [E_loc]
        f_loc = assigned / jnp.maximum(tt * moe.top_k, 1)
        p_loc = jax.lax.dynamic_slice_in_dim(p_e, e0_, e_loc)
        aux = moe.n_experts * jnp.sum(f_loc * p_loc)
        aux = jax.lax.psum(aux, "model")
        if seq_sharded:
            out = jax.lax.psum_scatter(out, "model", scatter_dimension=1, tiled=True)
        else:
            out = jax.lax.psum(out, "model")
        return out.astype(h_loc.dtype), aux

    out, aux = jax.shard_map(
        f, mesh=mesh,
        in_specs=(h_spec, P(None, None), w_in_spec, w_in_spec, w_out_spec),
        out_specs=(h_spec, P()),
        check_vma=False,
    )(h, lp["router"], lp["wi_e"], lp["wg_e"], lp["wo_e"])

    if moe.n_shared:
        out = out + L.swiglu_mlp(h, lp["ws_i"], lp["ws_g"], lp["ws_o"])
    return out, aux


# --------------------------------------------------------------- forward

def _constrain(x, mesh, spec):
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, spec))


def _tree_constrain(tree, pspec_tree, mesh):
    """with_sharding_constraint a pytree against a matching PartitionSpec tree
    (P is a tuple, so flatten each side with its own is_leaf)."""
    leaves, tdef = jax.tree.flatten(tree)
    specs = jax.tree.flatten(pspec_tree, is_leaf=lambda x: isinstance(x, P))[0]
    return tdef.unflatten(_constrain(l, mesh, s) for l, s in zip(leaves, specs))


def _sp_ffn(h2, lp, cfg: LMConfig, mesh, bspec, act):
    """Megatron-SP FFN: all-gather ACTIVATIONS over the seq('model') axis,
    compute with F model-sharded (weights gathered over 'data' only — 16×
    less than full replication), reduce-scatter the output back to
    seq-sharded. Activation AG+RS ≪ full weight gathers at ≥33B scale."""
    h2g = _constrain(h2, mesh, P(bspec, None, None))          # AG over model (seq)
    gate = jnp.einsum("bsd,df->bsf", h2g, lp["wg"])
    up = jnp.einsum("bsd,df->bsf", h2g, lp["wi"])
    gate = _constrain(gate, mesh, P(bspec, None, "model"))    # F stays sharded
    up = _constrain(up, mesh, P(bspec, None, "model"))
    ff = jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, lp["wo_ff"])
    return _constrain(ff, mesh, act)                          # RS over model (seq)


def _layer_pspecs(cfg: LMConfig, mesh):
    """Per-layer weight PartitionSpecs (stack axis stripped) — applied INSIDE
    the scan body so gradient cotangents are constrained to the param sharding
    at production (reduce-scatter instead of full-tensor all-reduce)."""
    full = param_pspecs(cfg, mesh)["layers"]
    return {k: P(*v[1:]) for k, v in full.items()}


def forward(params, tokens, cfg: LMConfig, mesh, *, q_offset: int = 0):
    """Causal forward: tokens [B, S] -> final hidden [B, S, D] (pre-unembed)."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    act = P(bspec, "model", None)
    b, s = tokens.shape
    lspecs = _layer_pspecs(cfg, mesh)

    x = params["embed"][tokens].astype(_dtype(cfg))
    x = _constrain(x, mesh, act)
    positions = q_offset + jnp.arange(s)

    def layer(carry, lp):
        x, aux = carry
        lp = {k: _constrain(v, mesh, lspecs[k]) for k, v in lp.items()}
        h = L.rmsnorm(x, lp["ln1"])
        q = jnp.einsum("bsd,dq->bsq", h, lp["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = jnp.einsum("bsd,dq->bsq", h, lp["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = jnp.einsum("bsd,dq->bsq", h, lp["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        # replicate K/V over the seq ("model") axis once per layer (explicit
        # all-gather; the flash scan then slices locally)
        k = _constrain(k, mesh, P(bspec, None, None, None))
        v = _constrain(v, mesh, P(bspec, None, None, None))
        o = L.flash_attention(q, k, v, causal=True, block=min(cfg.attn_block, s), q_offset=q_offset,
                              score_dtype=jnp.dtype(cfg.attn_score_dtype))
        o = jnp.einsum("bsq,qd->bsd", o.reshape(b, s, -1), lp["wo"])
        x = _constrain(x + o, mesh, act)
        h2 = L.rmsnorm(x, lp["ln2"])
        if cfg.moe is None:
            if cfg.ffn_impl == "sp":
                ff = _sp_ffn(h2, lp, cfg, mesh, bspec, act)
            else:
                ff = L.swiglu_mlp(h2, lp["wi"], lp["wg"], lp["wo_ff"])
        else:
            ff, aux_l = _moe_block(h2, lp, cfg, mesh, batch_axes, seq_sharded=s > 1)
            aux = aux + aux_l
        x = _constrain(x + ff, mesh, act)
        return (x, aux), None

    if cfg.remat == "full":
        layer = jax.checkpoint(layer, policy=jax.checkpoint_policies.nothing_saveable)
    elif cfg.remat == "dots":
        layer = jax.checkpoint(layer, policy=jax.checkpoint_policies.checkpoint_dots)

    (x, aux), _ = jax.lax.scan(layer, (x, jnp.zeros((), jnp.float32)), params["layers"])
    return L.rmsnorm(x, params["ln_f"]), aux


def _softmax_ce(hidden, unembed, labels, chunks: int):
    """Next-token CE; optionally chunked over seq with rematerialized logits."""
    b, s, d = hidden.shape

    def chunk_loss(h_c, y_c):
        logits = jnp.einsum("bsd,dv->bsv", h_c, unembed).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, y_c[..., None].astype(jnp.int32), -1)[..., 0]
        return (lse - gold).sum()

    if chunks <= 1:
        return chunk_loss(hidden, labels) / (b * s)
    assert s % chunks == 0
    hc = hidden.reshape(b, chunks, s // chunks, d).swapaxes(0, 1)
    yc = labels.reshape(b, chunks, s // chunks).swapaxes(0, 1)
    loss, _ = jax.lax.scan(
        lambda acc, xs: (acc + jax.checkpoint(chunk_loss)(*xs), None),
        jnp.zeros((), jnp.float32), (hc, yc))
    return loss / (b * s)


# --------------------------------------------------------------- train step

def make_train_step(cfg: LMConfig, mesh, tx):
    pspecs = param_pspecs(cfg, mesh)

    def loss_fn(p, tokens, labels):
        hidden, aux = forward(p, tokens, cfg, mesh)
        ce = _softmax_ce(hidden, p["unembed"], labels, cfg.logits_chunk)
        return ce + 0.01 * aux, (ce, aux)

    def train_step(state, batch):
        params, opt_state = state
        accum = max(1, cfg.grad_accum)
        if accum == 1:
            (loss, (ce, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch["tokens"], batch["labels"])
        else:
            # microbatched gradient accumulation: live activation footprint
            # shrinks by `accum` at the cost of an f32 grad accumulator
            b = batch["tokens"].shape[0]
            assert b % accum == 0
            toks = batch["tokens"].reshape(accum, b // accum, -1)
            labs = batch["labels"].reshape(accum, b // accum, -1)

            def micro(carry, mb):
                gacc, lacc, ceacc, auxacc = carry
                (l, (ce_i, aux_i)), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb[0], mb[1])
                # keep microbatch grads in the PARAM sharding — otherwise XLA
                # replicates the accumulator and all-reduces full grads every
                # microbatch (4 TB/step at mistral-123b scale)
                g = _tree_constrain(g, pspecs, mesh)
                gacc = jax.tree.map(lambda a, gi: a + gi.astype(jnp.float32), gacc, g)
                return (gacc, lacc + l, ceacc + ce_i, auxacc + aux_i), None

            gacc0 = _tree_constrain(
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params), pspecs, mesh)
            zero = jnp.zeros((), jnp.float32)
            (gacc, loss, ce, aux), _ = jax.lax.scan(micro, (gacc0, zero, zero, zero), (toks, labs))
            grads = jax.tree.map(lambda g, p: (g / accum).astype(p.dtype), gacc, params)
            loss, ce, aux = loss / accum, ce / accum, aux / accum
        grads, gnorm = opt.clip_by_global_norm(grads, 1.0)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = opt.apply_updates(params, updates)
        return (params, opt_state), {"loss": loss, "ce": ce, "moe_aux": aux, "grad_norm": gnorm}

    return train_step


# --------------------------------------------------------------- prefill

def make_prefill_step(cfg: LMConfig, mesh):
    """Forward + emit KV cache and last-position logits (inference prefill)."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

    def prefill_step(params, tokens):
        b, s = tokens.shape
        x = params["embed"][tokens].astype(_dtype(cfg))
        x = _constrain(x, mesh, P(bspec, "model", None))
        positions = jnp.arange(s)

        def layer(x, lp):
            h = L.rmsnorm(x, lp["ln1"])
            q = jnp.einsum("bsd,dq->bsq", h, lp["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
            k = jnp.einsum("bsd,dq->bsq", h, lp["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            v = jnp.einsum("bsd,dq->bsq", h, lp["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            # pin the cache ys to seq-sharded BEFORE the replicated copy exists,
            # or sharding propagation merges them and the ys buffer replicates
            # the full sequence per device (20 GiB at 32k for MHA archs)
            k = _constrain(k, mesh, P(bspec, "model", None, None))
            v = _constrain(v, mesh, P(bspec, "model", None, None))
            kg = _constrain(k, mesh, P(bspec, None, None, None))
            vg = _constrain(v, mesh, P(bspec, None, None, None))
            o = L.flash_attention(q, kg, vg, causal=True, block=min(cfg.attn_block, s),
                                  score_dtype=jnp.dtype(cfg.attn_score_dtype))
            o = jnp.einsum("bsq,qd->bsd", o.reshape(b, s, -1), lp["wo"])
            x = _constrain(x + o, mesh, P(bspec, "model", None))
            h2 = L.rmsnorm(x, lp["ln2"])
            if cfg.moe is None:
                if cfg.ffn_impl == "sp":
                    ff = _sp_ffn(h2, lp, cfg, mesh, bspec, P(bspec, "model", None))
                else:
                    ff = L.swiglu_mlp(h2, lp["wi"], lp["wg"], lp["wo_ff"])
            else:
                ff, _ = _moe_block(h2, lp, cfg, mesh, batch_axes, seq_sharded=True)
            x = _constrain(x + ff, mesh, P(bspec, "model", None))
            return x, (k, v)

        if cfg.remat == "full":
            layer = jax.checkpoint(layer, policy=jax.checkpoint_policies.nothing_saveable)
        x, (kc, vc) = jax.lax.scan(layer, x, params["layers"])
        x = L.rmsnorm(x, params["ln_f"])
        last = x[:, -1]
        logits = jnp.einsum("bd,dv->bv", last, params["unembed"]).astype(jnp.float32)
        return logits, {"k": kc, "v": vc}

    return prefill_step


# --------------------------------------------------------------- decode

def _decode_seq_axes(mesh, global_batch: int):
    """Which mesh axes shard the KV-cache sequence dim (DESIGN.md §5)."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bprod = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    if global_batch % max(bprod, 1) == 0 and global_batch >= bprod:
        return batch_axes, ("model",)
    # tiny batch (long-context): replicate batch, shard seq over everything
    return (), tuple(a for a in (*batch_axes, "model") if a in mesh.axis_names)


def _flash_decode(q, k_cache, v_cache, layer, cache_len, mesh, bspec, seq_axes, n_heads):
    """q: [B, 1, H, Dh]; caches: STACKED [L, B, S, KV, Dh] seq-sharded over
    seq_axes. Reads layer `layer` — the cache stays in the scan carry so the
    donated input buffer is updated in place (no xs/ys double buffering)."""

    def f(q_l, k_c, v_c):
        k_l = jax.lax.dynamic_index_in_dim(k_c, layer, 0, keepdims=False)
        v_l = jax.lax.dynamic_index_in_dim(v_c, layer, 0, keepdims=False)
        b, s_loc, kv, dh = k_l.shape
        g = n_heads // kv
        idx = jnp.zeros((), jnp.int32)
        for ax in seq_axes:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        off = idx * s_loc
        # grouped-GQA einsum; cache stays in storage dtype (an astype(f32)
        # here becomes a hoisted full-cache f32 copy)
        qg = q_l.astype(k_l.dtype).reshape(b, 1, kv, g, dh)
        scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_l,
                       preferred_element_type=jnp.float32) * scale
        valid = (off + jnp.arange(s_loc)) < cache_len
        s = jnp.where(valid[None, None, None, None, :], s, -1e30)
        m_l = s.max(-1)
        p = jnp.exp(s - m_l[..., None])
        l_l = p.sum(-1)
        o_l = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(k_l.dtype), v_l,
                         preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m_l, seq_axes)
        corr = jnp.exp(m_l - m_g)
        l_g = jax.lax.psum(l_l * corr, seq_axes)
        o_g = jax.lax.psum(o_l * corr[..., None], seq_axes)
        o = o_g / jnp.maximum(l_g, 1e-30)[..., None]           # [B, KV, G, 1, Dh]
        return o.transpose(0, 3, 1, 2, 4).reshape(b, 1, n_heads, dh)

    cache_spec = P(None, bspec, seq_axes if len(seq_axes) > 1 else seq_axes[0], None, None)
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(bspec, None, None, None), cache_spec, cache_spec),
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache)


def _cache_insert(cache, new, layer, pos, mesh, bspec, seq_axes):
    """Write new [B, 1, KV, Dh] at (layer, pos) of the STACKED sharded cache."""
    def f(c_l, n_l):
        s_loc = c_l.shape[2]
        idx = jnp.zeros((), jnp.int32)
        for ax in seq_axes:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        off = idx * s_loc
        owner = (pos >= off) & (pos < off + s_loc)
        li = jnp.clip(pos - off, 0, s_loc - 1)
        # DUS writes garbage on non-owners, second where-DUS restores: express
        # as select on the inserted row only to keep the update in place
        cur = jax.lax.dynamic_slice(c_l, (layer, 0, li, 0, 0),
                                    (1, *n_l.shape))[0]
        row = jnp.where(owner, n_l.astype(c_l.dtype), cur)
        return jax.lax.dynamic_update_slice(c_l, row[None], (layer, 0, li, 0, 0))

    cache_spec = P(None, bspec, seq_axes if len(seq_axes) > 1 else seq_axes[0], None, None)
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(cache_spec, P(bspec, None, None, None)),
        out_specs=cache_spec,
        check_vma=False,
    )(cache, new)


def make_decode_step(cfg: LMConfig, mesh, global_batch: int, seq_len: int):
    batch_axes, seq_axes = _decode_seq_axes(mesh, global_batch)
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

    def decode_step(params, cache, tokens, pos):
        """tokens: [B, 1] int32; pos: [] int32 (current length). Returns
        (next_token [B], new cache). The cache rides in the scan CARRY so the
        donated buffer is updated in place (no xs/ys double buffering)."""
        b = tokens.shape[0]
        x = params["embed"][tokens].astype(_dtype(cfg))
        x = _constrain(x, mesh, P(bspec, None, None))

        def layer(carry, xs):
            x, kcache, vcache = carry
            lp, li = xs
            h = L.rmsnorm(x, lp["ln1"])
            q = jnp.einsum("bsd,dq->bsq", h, lp["wq"]).reshape(b, 1, cfg.n_heads, cfg.head_dim)
            k = jnp.einsum("bsd,dq->bsq", h, lp["wk"]).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
            v = jnp.einsum("bsd,dq->bsq", h, lp["wv"]).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
            posv = jnp.full((b, 1), pos, jnp.int32)
            q = L.apply_rope(q, posv, cfg.rope_theta)
            k = L.apply_rope(k, posv, cfg.rope_theta)
            kcache = _cache_insert(kcache, k, li, pos, mesh, bspec, seq_axes)
            vcache = _cache_insert(vcache, v, li, pos, mesh, bspec, seq_axes)
            o = _flash_decode(q, kcache, vcache, li, pos + 1, mesh, bspec, seq_axes, cfg.n_heads)
            o = jnp.einsum("bsq,qd->bsd", o.astype(_dtype(cfg)).reshape(b, 1, -1), lp["wo"])
            x = (x + o).astype(_dtype(cfg))
            h2 = L.rmsnorm(x, lp["ln2"])
            if cfg.moe is None:
                ff = L.swiglu_mlp(h2, lp["wi"], lp["wg"], lp["wo_ff"])
            else:
                ff, _ = _moe_block(h2, lp, cfg, mesh, batch_axes, seq_sharded=False)
            return ((x + ff).astype(_dtype(cfg)), kcache, vcache), None

        (x, k_new, v_new), _ = jax.lax.scan(
            layer, (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(cfg.n_layers)))
        x = L.rmsnorm(x[:, 0], params["ln_f"])
        logits = jnp.einsum("bd,dv->bv", x, params["unembed"]).astype(jnp.float32)
        next_tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return next_tok, {"k": k_new, "v": v_new}

    return decode_step, batch_axes, seq_axes


# --------------------------------------------------------------- bundle

def cache_specs(cfg: LMConfig, global_batch: int, seq_len: int):
    shape = (cfg.n_layers, global_batch, seq_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": sds(shape, _dtype(cfg)), "v": sds(shape, _dtype(cfg))}


def cache_pspecs(cfg: LMConfig, mesh, global_batch: int):
    batch_axes, seq_axes = _decode_seq_axes(mesh, global_batch)
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    spec = P(None, bspec, seq_axes if len(seq_axes) > 1 else seq_axes[0], None, None)
    return {"k": spec, "v": spec}


def make_bundle(cfg: LMConfig, mesh) -> ModelBundle:
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    tx = opt.adamw(opt.cosine_schedule(3e-4, warmup=100, total=10_000), weight_decay=0.1)

    def step(shape: ShapeSpec) -> StepDef:
        if shape.kind == "train":
            s, gb = shape["seq_len"], shape["global_batch"]
            fn = make_train_step(cfg, mesh, tx)
            return StepDef(
                fn=fn,
                input_specs={"tokens": sds((gb, s), jnp.int32), "labels": sds((gb, s), jnp.int32)},
                input_pspecs={"tokens": P(bspec, None), "labels": P(bspec, None)},
                out_pspecs=None,
            )
        if shape.kind == "prefill":
            s, gb = shape["seq_len"], shape["global_batch"]
            fn = make_prefill_step(cfg, mesh)
            cache_spec = P(None, bspec, "model", None, None)
            return StepDef(
                fn=fn,
                input_specs={"tokens": sds((gb, s), jnp.int32)},
                input_pspecs={"tokens": P(bspec, None)},
                out_pspecs=(P(bspec, None), {"k": cache_spec, "v": cache_spec}),
            )
        if shape.kind == "decode":
            s, gb = shape["seq_len"], shape["global_batch"]
            fn, b_axes, seq_axes = make_decode_step(cfg, mesh, gb, s)
            dbspec = b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None)
            return StepDef(
                fn=fn,
                input_specs={
                    "cache": cache_specs(cfg, gb, s),
                    "tokens": sds((gb, 1), jnp.int32),
                    "pos": sds((), jnp.int32),
                },
                input_pspecs={
                    "cache": cache_pspecs(cfg, mesh, gb),
                    "tokens": P(dbspec, None),
                    "pos": P(),
                },
                # cache out == cache in so donation aliases the 2×TB buffers
                out_pspecs=(P(dbspec), cache_pspecs(cfg, mesh, gb)),
                donate=(1,),
            )
        raise ValueError(f"unknown shape kind {shape.kind} for LM arch")

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
