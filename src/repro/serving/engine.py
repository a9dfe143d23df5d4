"""Distributed LIRA serving engine — the paper's system on a TPU pod.

Key insight of the TPU mapping (DESIGN.md §3): the probing model's output is a
query→partition ROUTING problem, identical in structure to MoE token dispatch.
serve_step:

  1. queries sharded over ("pod","data"); partition store sharded over "model"
     (each chip owns B/16 partitions); probing model + centroids replicated;
  2. per chip: probing probabilities → top-`nprobe_max` partitions, σ-masked
     (query-adaptive nprobe, paper §3.4);
  3. sort-based dispatch of queries into per-local-partition buckets of static
     capacity `q_cap` (the MoE-dispatch trick applied to ANN — compute scales
     with Q·nprobe·cap, NOT Q·N: partition pruning materializes as real FLOP
     savings under static shapes). Batch-padding rows are masked out of
     dispatch via the `valid` operand so they never steal q_cap slots from
     real queries, and probes dropped by bucket overflow are COUNTED and
     returned (the serve step's 4th output; `LiraEngine.search` surfaces the
     total) instead of being silently swallowed;
  4. per local partition: the scan stage is backend-dispatched through
     serving/scan.py (cfg.impl: auto | ref | pallas | interpret). "ref" is the
     portable jnp path under lax.map; "pallas" runs the fused kernels
     grid-batched over the whole [b_loc, q_cap] dispatch buffer in one launch
     (kernels.l2_topk_qbuf for f32; Mosaic on TPU, "interpret" for the CPU).
     WHAT is scanned is declared by the serving tier (serving/tiers.py): the
     engine resolves cfg.tier from the registry and iterates the tier's store
     field + scan operand declarations — it never branches on tier-specific
     booleans, so a new storage/quantization strategy is one registered Tier
     class with zero edits here. The "pq" tier threads a shared ADC LUT +
     shortlist depth (two-stage scan, serving/quantized.py); "residual_pq"
     adds the residual ADC identity's cterm plane and per-(query, partition)
     offsets (core/pq.py);
  5. scatter back per query, local top-k, all-gather(k·shards) over "model",
     final merge. Collective volume is O(Q·k), independent of N.

Multi-pod: each pod holds a full index replica; the front-end routes query
batches to pods (repro.distributed.fault simulates replica failover).

Host-side callers use the typed surface in serving/api.py: LiraEngine.build
takes a BuildConfig, search takes queries or a SearchRequest and returns a
SearchResult (the legacy 4-tuple unpacking survives one release behind a
DeprecationWarning shim).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import LiraSystemConfig, ShapeSpec
from repro.core import probing
from repro.kernels import ops as kops
from repro.models.api import ModelBundle, StepDef, adamw_state_pspecs, adamw_state_specs, sds
from repro.train import optimizer as opt

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving import api
from repro.serving import scan
from repro.serving import tiers


def batch_mesh_info(mesh):
    """(batch_axes, bspec, bprod) for the query-batch axes of a mesh — the
    single source for how serve steps and batch bucketing split queries."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    bprod = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    return batch_axes, bspec, bprod


def probing_param_specs(cfg: LiraSystemConfig):
    pc = probing.ProbingConfig(dim=cfg.dim, n_partitions=cfg.n_partitions,
                               q_hidden=tuple(cfg.q_hidden), i_hidden=tuple(cfg.i_hidden),
                               p_hidden=tuple(cfg.p_hidden))
    return jax.eval_shape(lambda: probing.init(jax.random.PRNGKey(0), pc))


def store_specs(cfg: LiraSystemConfig):
    """Store field shape specs for cfg's serving tier — a pure delegation to
    the tier registry (serving/tiers.py declares WHAT each tier stores)."""
    return tiers.resolve(cfg.tier).store_specs(cfg)


def store_pspecs(mesh, cfg: LiraSystemConfig | None = None):
    """Mesh PartitionSpecs per store field; cfg=None means the base f32 tier.
    (mesh is unused — pspecs name axes symbolically; the parameter is kept
    only so existing callers' signatures stay valid.)"""
    del mesh
    tier = tiers.resolve(cfg.tier if cfg is not None else "f32")
    return tier.store_pspecs(cfg)


# ------------------------------------------------------------- serve step

def _dup_count(ids_pool):
    """Count duplicate id slots per candidate pool row ([nq, pool]): valid
    slots (id ≥ 0) minus distinct ids, summed over queries. This is the
    replica-dedup hit count — how many candidate slots the η-redundancy
    replicas burned on ids another partition already supplied.

    Counted at each merge the serve step actually runs (local pool, then the
    gathered cross-shard top-k), so under model sharding it is a lower bound
    on the full-pool duplicate count: a cross-shard duplicate pair where one
    copy misses its shard's local top-k is never observed (counting it would
    require gathering whole pools — O(Q·pool·shards) traffic instead of the
    O(Q·k) the merge is designed around). Results stay bit-identical across
    shardings; only this telemetry is merge-local."""
    s = jnp.sort(ids_pool, axis=1)
    valid = s >= 0
    first = jnp.concatenate(
        [jnp.ones_like(s[:, :1], jnp.bool_), s[:, 1:] != s[:, :-1]], axis=1)
    return (valid.sum(1) - (valid & first).sum(1)).sum().astype(jnp.int32)


def make_serve_step(cfg: LiraSystemConfig, mesh, n_queries: int, *, sigma: float = 0.5,
                    q_cap_factor: float | None = None,
                    tier: str | tiers.Tier | None = None,
                    impl: str | None = None,
                    k: int | None = None,
                    count_dedup: bool = False):
    _, bspec, bprod = batch_mesh_info(mesh)
    model_n = mesh.shape.get("model", 1)
    q_row = n_queries // bprod
    b_loc = cfg.n_partitions // model_n
    q_cap_factor = q_cap_factor if q_cap_factor is not None else getattr(cfg, "q_cap_factor", 2.0)
    q_cap = max(8, int(q_row * cfg.nprobe_max / cfg.n_partitions * q_cap_factor))
    k = cfg.k if k is None else int(k)
    tier = tiers.resolve(tier if tier is not None else cfg.tier)
    impl = getattr(cfg, "impl", "auto") if impl is None else impl
    scan_impl = scan.resolve_impl(impl)  # fail fast on typos, not at trace time
    # the tier declares its store fields; everything beyond the probing /
    # dispatch / rerank operands (BASE_FIELDS) is threaded through untouched
    # and handed back to the tier when it assembles the scan operands
    pspec_map = tier.store_pspecs(cfg)
    extra_fields = tuple(n for n in tier.store_specs(cfg)
                         if n not in tiers.BASE_FIELDS)

    def f(q_loc, valid_loc, params, cents, vecs_loc, ids_loc, occ_loc, *extras):
        # q_loc: [q_row, d]; valid_loc: [q_row] bool (False = batch padding);
        # vecs_loc: [b_loc, cap, d]; ids_loc/occ_loc: [b_loc, cap]
        # extras: the tier's non-base store fields, in declaration order
        # tombstoned/free slots must never surface ids: composing occupancy
        # into the id plane up front reuses the scan layer's universal id<0
        # invalid sentinel, so every impl × tier masks holes identically —
        # and a fully-occupied store is bit-identical to the static path
        ids_loc = jnp.where(occ_loc, ids_loc, -1)
        # jax.named_scope labels the serving stages in profiler captures
        # (TensorBoard op_profile groups HLO ops under these names — the
        # --profile-dir recipe in README "Observability"); it is a pure
        # metadata annotation with zero effect on the computation
        with jax.named_scope("lira.probing"):
            # HIGHEST: residual_pq derives its exact-distance offsets from cd
            cd = (
                jnp.sum(q_loc * q_loc, -1, keepdims=True)
                - 2.0 * jnp.dot(q_loc, cents.T, precision=jax.lax.Precision.HIGHEST)
                + jnp.sum(cents * cents, -1)[None, :]
            )
            p = jax.nn.sigmoid(probing.apply(params, q_loc, cd))    # [q_row, B]
            vals, pidx = jax.lax.top_k(p, cfg.nprobe_max)           # global partitions
            probe_ok = vals > sigma
            probe_ok = probe_ok.at[:, 0].set(True)                  # always ≥1 partition
            # batch-padding rows must not probe: a pad query occupying q_cap
            # slots can evict a real query's probes in small buckets
            probe_ok = probe_ok & valid_loc[:, None]

        # ---- dispatch (sort-based, local partition range only)
        with jax.named_scope("lira.dispatch"):
            b0 = jax.lax.axis_index("model") * b_loc if model_n > 1 else 0
            flat_p = pidx.reshape(-1) - b0
            flat_ok = probe_ok.reshape(-1) & (flat_p >= 0) & (flat_p < b_loc)
            flat_q = jnp.broadcast_to(jnp.arange(q_row)[:, None], pidx.shape).reshape(-1)
            key = jnp.where(flat_ok, flat_p, b_loc)
            order = jnp.argsort(key, stable=True)
            skey = key[order]
            start = jnp.searchsorted(skey, jnp.arange(b_loc + 1))
            pos = jnp.arange(skey.shape[0]) - start[jnp.clip(skey, 0, b_loc)]
            keep = (skey < b_loc) & (pos < q_cap)
            # probes beyond a hot partition's q_cap are dropped — count them so
            # recall degradation is reported, not silent (raise q_cap_factor or
            # rebalance partitions when this is persistently > 0)
            overflow = ((skey < b_loc) & (pos >= q_cap)).sum().astype(jnp.int32)
            row = jnp.where(keep, skey, b_loc)
            col = jnp.where(keep, pos, 0)
            qbuf = jnp.full((b_loc, q_cap), q_row, jnp.int32).at[row, col].set(
                flat_q[order], mode="drop")                          # q_row = invalid

        # ---- per-partition scan: backend-dispatched (serving/scan.py); the
        # tier derives its extra scan operands (ADC LUTs, shortlist depth,
        # residual offsets, …) from the serve-step context — {} = plain f32
        with jax.named_scope("lira.scan"):
            q_pad = jnp.concatenate([q_loc, jnp.full((1, q_loc.shape[1]), 1e9, q_loc.dtype)], 0)
            ctx = tiers.ScanContext(q_loc=q_loc, q_pad=q_pad, cd=cd, b0=b0,
                                    b_loc=b_loc, k=k)
            scan_kw = tier.scan_kwargs(cfg, ctx, dict(zip(extra_fields, extras)))
            dists, rids, blocks = scan.run(scan_impl, qbuf, q_pad, vecs_loc,
                                           ids_loc, k, **scan_kw)

        # ---- scatter back per query, local merge
        with jax.named_scope("lira.merge"):
            out_d = jnp.full((q_row + 1, b_loc, k), jnp.inf, jnp.float32)
            out_i = jnp.full((q_row + 1, b_loc, k), -1, jnp.int32)
            cols = jnp.broadcast_to(jnp.arange(b_loc)[:, None], qbuf.shape)
            out_d = out_d.at[qbuf, cols].set(dists, mode="drop")
            out_i = out_i.at[qbuf, cols].set(rids, mode="drop")
            pool_i = out_i[:q_row].reshape(q_row, -1)
        # replica-dedup hit rate (only when asked for: the extra output
        # changes the step signature, so make_bundle and direct callers keep
        # the 4-output form) — measured BEFORE each dedup pass so it counts
        # exactly the duplicate slots the merges collapse. Its own scope
        # beside lira.merge, never inside it, so a trace books the counter's
        # device time apart from the merge's
        dedup_hits = None
        if count_dedup:
            with jax.named_scope("lira.telemetry"):
                dedup_hits = _dup_count(pool_i)
        with jax.named_scope("lira.merge"):
            # replica-aware local merge: redundancy (η>0) stores the same id in
            # several partitions, so a plain top-k would return duplicate ids
            # and corrupt recall@k — dedup to best-distance-per-id instead
            # (the same backend as the scan: Pallas kernel or jnp reference)
            loc_d, loc_i = kops.dedup_topk(
                out_d[:q_row].reshape(q_row, -1), pool_i, k, impl=scan_impl)

        # ---- cross-shard merge (O(Q·k·shards) bytes — independent of N);
        # replicas of one id can live on different shards, so dedup again
        if model_n > 1:
            with jax.named_scope("lira.merge"):
                all_d = jax.lax.all_gather(loc_d, "model", axis=1, tiled=True)   # [q_row, 16k]
                all_i = jax.lax.all_gather(loc_i, "model", axis=1, tiled=True)
            if count_dedup:
                with jax.named_scope("lira.telemetry"):
                    # local hits differ per shard → psum; the gathered pool
                    # is identical on every model shard → count it once
                    dedup_hits = (jax.lax.psum(dedup_hits, "model")
                                  + _dup_count(all_i))
            with jax.named_scope("lira.merge"):
                loc_d, loc_i = kops.dedup_topk(all_d, all_i, k, impl=scan_impl)
                overflow = jax.lax.psum(overflow, "model")
            blocks = jax.lax.psum(blocks, "model")
        nprobe_eff = probe_ok.sum(-1).astype(jnp.float32)
        if count_dedup:
            # with the dedup hits rides the scan's (blocks streamed, blocks
            # of the whole capacity) pair: the engine books both
            return (loc_d, loc_i, nprobe_eff, overflow[None], dedup_hits[None],
                    blocks[None])
        return loc_d, loc_i, nprobe_eff, overflow[None]

    param_spec = jax.tree.map(lambda _: P(), probing_param_specs_cache(cfg))
    in_specs = (P(bspec, None), P(bspec), param_spec,
                pspec_map["centroids"], pspec_map["vectors"], pspec_map["ids"],
                pspec_map["occupancy"],
                *(pspec_map[n] for n in extra_fields))

    out_specs = (P(bspec, None), P(bspec, None), P(bspec), P(bspec))
    if count_dedup:
        out_specs = out_specs + (P(bspec), P(bspec, None))

    def serve_step(params, store, queries, valid=None):
        if valid is None:
            valid = jnp.ones((n_queries,), jnp.bool_)
        # stores built before the mutable-index refactor (and raw test store
        # dicts) carry no occupancy plane: a dense store's occupancy is
        # exactly its id validity, so synthesize it
        occ = store.get("occupancy")
        if occ is None:
            occ = store["ids"] >= 0
        args = (queries, valid, params, store["centroids"], store["vectors"],
                store["ids"], occ, *(store[n] for n in extra_fields))
        return jax.shard_map(
            f, mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )(*args)

    return serve_step


@functools.lru_cache(maxsize=None)
def _probing_specs_cached(dim, b, qh, ih, ph):
    pc = probing.ProbingConfig(dim=dim, n_partitions=b, q_hidden=qh, i_hidden=ih, p_hidden=ph)
    return jax.eval_shape(lambda: probing.init(jax.random.PRNGKey(0), pc))


def probing_param_specs_cache(cfg: LiraSystemConfig):
    return _probing_specs_cached(cfg.dim, cfg.n_partitions, tuple(cfg.q_hidden),
                                 tuple(cfg.i_hidden), tuple(cfg.p_hidden))


# ------------------------------------------------------------- train step

def make_probe_train_step(cfg: LiraSystemConfig, mesh, tx):
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def train_step(state, batch):
        params, opt_state = state

        def loss_fn(p):
            return probing.bce_loss(p, batch["q"], batch["cent_dist"], batch["labels"])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, gnorm = opt.clip_by_global_norm(grads, 1.0)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = opt.apply_updates(params, updates)
        return (params, opt_state), {"loss": loss, "grad_norm": gnorm}

    return train_step


# ------------------------------------------------------------- bundle

def make_bundle(cfg: LiraSystemConfig, mesh) -> ModelBundle:
    _, bspec, _ = batch_mesh_info(mesh)
    tx = opt.adamw(opt.cosine_schedule(1e-3, 50, 5000))
    pc = probing.ProbingConfig(dim=cfg.dim, n_partitions=cfg.n_partitions,
                               q_hidden=tuple(cfg.q_hidden), i_hidden=tuple(cfg.i_hidden),
                               p_hidden=tuple(cfg.p_hidden))

    def step(shape: ShapeSpec) -> StepDef:
        if shape.kind == "lira_serve":
            nq = shape["n_queries"]
            fn_inner = make_serve_step(cfg, mesh, nq)

            def fn(params, store, queries):
                return fn_inner(params, store, queries)

            return StepDef(
                fn=fn,
                input_specs={"store": store_specs(cfg), "queries": sds((nq, cfg.dim))},
                input_pspecs={"store": store_pspecs(mesh, cfg), "queries": P(bspec, None)},
                out_pspecs=None,
            )
        if shape.kind == "lira_train":
            b = shape["batch"]
            return StepDef(
                fn=make_probe_train_step(cfg, mesh, tx),
                input_specs={
                    "q": sds((b, cfg.dim)),
                    "cent_dist": sds((b, cfg.n_partitions)),
                    "labels": sds((b, cfg.n_partitions)),
                },
                input_pspecs={"q": P(bspec, None), "cent_dist": P(bspec, None),
                              "labels": P(bspec, None)},
                out_pspecs=None,
            )
        raise ValueError(shape.kind)

    return ModelBundle(
        name=cfg.arch,
        config=cfg,
        init=lambda rng, shape=None: probing.init(rng, pc),
        param_specs=lambda shape=None: probing_param_specs_cache(cfg),
        param_pspecs=lambda shape=None: jax.tree.map(lambda _: P(), probing_param_specs_cache(cfg)),
        step=step,
        opt_specs=lambda shape=None: adamw_state_specs(probing_param_specs_cache(cfg)),
        opt_pspecs=lambda shape=None: adamw_state_pspecs(
            jax.tree.map(lambda _: P(), probing_param_specs_cache(cfg))),
    )


# ------------------------------------------------------------- host engine

@dataclasses.dataclass
class LiraEngine:
    """End-to-end host-driven engine: build (k-means → train probe → redundancy
    → tier store construction) then serve batches via the distributed
    serve_step. The typed surface lives in serving/api.py — ``build`` takes a
    BuildConfig, ``search`` takes queries or a SearchRequest and returns a
    SearchResult; which store planes exist and what the scan reads is declared
    by the serving tier (serving/tiers.py).

    Jitted serve steps are cached per (bucket, σ, tier, impl, k, q_cap) key:
    query batches are padded to power-of-two buckets so repeated traffic of
    varying size hits the jit cache instead of recompiling every call, and the
    pad rows are masked out of dispatch (they never probe or take q_cap slots).
    With ``cfg.auto_q_cap`` the engine doubles ``q_cap_factor`` after
    ``_AUTO_Q_CAP_AFTER`` consecutive overflowing calls and drops the cache,
    so the next bucket recompiles with the extra dispatch slack.
    """

    cfg: LiraSystemConfig
    params: dict
    store: dict
    mesh: jax.sharding.Mesh
    sigma: float = 0.5
    # store epoch: bumped by every mutation (insert/delete/compact/
    # repartition). Searches stamp it into SearchStats.epoch; shape-changing
    # mutations additionally enter the serve-fn cache key via cfg.capacity —
    # same-shape mutations MUST keep hitting the compiled steps (new device
    # arrays of unchanged shape/dtype never retrace a jitted fn).
    epoch: int = 0
    # attached serving front-end (serving/frontend.py); search_one routes
    # through it when present. Not part of engine identity or checkpoints.
    frontend: Optional[object] = dataclasses.field(default=None, repr=False,
                                                   compare=False)
    # observability (repro.obs): tracer=None means spans are free no-ops
    # (obs_trace.NOOP); metrics=None records into the process-wide
    # default_registry(). Neither participates in identity or checkpoints.
    tracer: Optional[object] = dataclasses.field(default=None, repr=False,
                                                 compare=False)
    metrics: Optional[object] = dataclasses.field(default=None, repr=False,
                                                  compare=False)
    _serve_cache: dict = dataclasses.field(default_factory=dict, repr=False,
                                           compare=False)
    _overflow_streak: int = dataclasses.field(default=0, repr=False,
                                              compare=False)
    # per-partition count of inserts that landed OFF their argmin partition
    # (no free slot nearer): the drift half of the staleness signal, reset by
    # repartition. None = lazily zeros (stores built before this field).
    _stale_inserts: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False)

    def _tracer(self):
        return self.tracer if self.tracer is not None else obs_trace.NOOP

    def _registry(self) -> obs_metrics.MetricsRegistry:
        return (self.metrics if self.metrics is not None
                else obs_metrics.default_registry())

    @classmethod
    def build(cls, mesh, x: np.ndarray, config: api.BuildConfig | None = None,
              **legacy_kwargs):
        """Build an index over ``x`` per the BuildConfig recipe.

        Legacy surface (one release): keyword arguments matching BuildConfig
        fields are still accepted when no config object is given, and the
        retired ``quantized=`` / ``residual=`` booleans map onto ``tier=``
        with a DeprecationWarning.
        """
        from repro.core import build_store, ground_truth as gt, kmeans_fit
        from repro.core.redundancy import plan_redundancy, replica_rows
        from repro.core.train_probing import train_probing_model

        if "quantized" in legacy_kwargs or "residual" in legacy_kwargs:
            api.warn_deprecated(
                "build-tier-kwargs",
                "LiraEngine.build(quantized=, residual=) is deprecated; pass "
                "BuildConfig(tier='pq') / BuildConfig(tier='residual_pq')")
            residual = bool(legacy_kwargs.pop("residual", False))
            quantized = bool(legacy_kwargs.pop("quantized", False))
            legacy_kwargs.setdefault(
                "tier", tiers.legacy_tier_name(quantized, residual))
        if config is None:
            config = api.BuildConfig(**legacy_kwargs)
        elif legacy_kwargs:
            raise TypeError("pass either a BuildConfig or keyword arguments, "
                            f"not both (got {sorted(legacy_kwargs)})")

        tier = tiers.resolve(config.tier)
        rng = jax.random.PRNGKey(config.seed)
        host = np.random.default_rng(config.seed)
        n_partitions = config.n_partitions
        st = kmeans_fit(rng, jnp.asarray(x), n_clusters=n_partitions, n_iters=20)
        assign, cents = np.asarray(st.assign), np.asarray(st.centroids)

        sub = host.choice(len(x), int(len(x) * config.train_frac), replace=False)
        xs = x[sub]
        _, sti = gt.exact_knn(xs, xs, config.k, exclude_self=True)
        part_of = assign[sub]
        lab = np.zeros((len(sub), n_partitions), np.float32)
        rows = np.repeat(np.arange(len(sub)), sti.shape[1])
        np.add.at(lab, (rows, part_of[sti].reshape(-1)), 1.0)
        lab = (lab > 0).astype(np.float32)
        params, _ = train_probing_model(rng, xs, lab, cents,
                                        epochs=config.epochs, log=config.log)

        ids = np.arange(len(x), dtype=np.int32)
        plan = plan_redundancy(params, x, assign, cents, eta=config.eta)
        extra = replica_rows(plan, x, ids)
        # whole lane tiles of slots, so the scan kernels stream the store in
        # place (an unaligned capacity pads a copy of it on every call: 6 GB
        # at SIFT1M scale); growth and compaction keep the alignment
        store_h = build_store(x, ids, assign, cents, extra=extra,
                              align=kops.SLOT_ALIGN)
        dim = x.shape[1]
        cfg = LiraSystemConfig(
            arch="lira", dim=dim, n_partitions=n_partitions,
            capacity=store_h.capacity, k=config.k,
            nprobe_max=min(n_partitions,
                           config.nprobe_max or max(8, n_partitions // 8)),
            tier=tier.name, pq_m=config.pq_m or 0, pq_ks=config.pq_ks,
            rerank=config.rerank, impl=config.impl,
            store_dtype=config.store_dtype, q_cap_factor=config.q_cap_factor,
            auto_q_cap=config.auto_q_cap, eta=config.eta,
        )
        # the tier owns store construction (and may amend cfg: PQ resolves
        # pq_m, clamps pq_ks for tiny stores)
        store, cfg = tier.build_store(jax.random.fold_in(rng, 1), cfg, store_h)
        if not cfg.pq_m:  # tiers without PQ leave the knob at its default
            cfg = dataclasses.replace(cfg, pq_m=16)
        return cls(cfg=cfg, params=params, store=store, mesh=mesh,
                   sigma=config.sigma).place()

    def place(self) -> "LiraEngine":
        """Put the store on the mesh once, each field per its tier's
        PartitionSpec (partition planes split on "model", the rest
        replicated), and the probing params replicated — so a serve call on
        a multi-device mesh never reshards the store. Returns self."""
        pspecs = tiers.resolve(self.cfg.tier).store_pspecs(self.cfg)

        def put(a, spec):
            return jax.device_put(a, NamedSharding(self.mesh, spec))

        self.store = {n: put(a, pspecs.get(n, P())) for n, a in self.store.items()}
        self.params = jax.tree.map(lambda a: put(a, P()), self.params)
        return self

    def _slot_align(self) -> int:
        """What a new capacity is rounded up to: whole lane tiles while the
        store holds them (the build aligns it, so the scan kernels stream the
        store in place), else 1 — a hand-built store keeps exact sizes."""
        return kops.SLOT_ALIGN if self.cfg.capacity % kops.SLOT_ALIGN == 0 else 1

    def _batch_bucket(self, nq: int) -> int:
        """Pad batch sizes to power-of-two buckets (≥8, rounded up to a
        multiple of the batch-mesh product so shard_map can split the batch)
        so the jitted serve step is reused across nearby batch sizes."""
        _, _, bprod = batch_mesh_info(self.mesh)
        bucket = max(8, 1 << max(0, nq - 1).bit_length())
        return -(-bucket // bprod) * bprod

    _SERVE_CACHE_MAX = 32  # σ sweeps must not accumulate compiled steps forever
    _AUTO_Q_CAP_AFTER = 2  # consecutive overflowing calls before a bump

    def serve_fn(self, nq_pad: int, sigma: float, tier: str = "f32",
                 impl: Optional[str] = None, k: Optional[int] = None):
        """The cached jitted serve step for one (bucket, σ, tier, impl, k,
        q_cap) key. Returns (fn, cache_hit, resolved_impl)."""
        # normalize before keying: None, "auto" and the resolved backend name
        # must share one compiled step; ditto tier aliases and k=None
        impl = scan.resolve_impl(
            impl if impl is not None else getattr(self.cfg, "impl", "auto"))
        tier = tiers.resolve(tier).name
        k = self.cfg.k if k is None else int(k)
        # capacity is the store-shape lever mutations move: growing/compacting
        # changes every per-slot plane's shape (and PQ's rerank clamp), so it
        # must key the cache — while same-shape mutations (insert into free
        # slots, delete) leave the key intact and keep hitting compiled steps
        key = (nq_pad, float(sigma), tier, impl, k,
               float(self.cfg.q_cap_factor), int(self.cfg.capacity))
        fn = self._serve_cache.pop(key, None)
        cache_hit = fn is not None
        if fn is None:
            fn = jax.jit(make_serve_step(self.cfg, self.mesh, nq_pad,
                                         sigma=float(sigma), tier=tier,
                                         impl=impl, k=k, count_dedup=True))
        self._serve_cache[key] = fn  # re-insert: dict order doubles as LRU
        while len(self._serve_cache) > self._SERVE_CACHE_MAX:
            self._serve_cache.pop(next(iter(self._serve_cache)))
        return fn, cache_hit, impl

    def search(self, queries, sigma: Optional[float] = None,
               quantized: Optional[bool] = None, impl: Optional[str] = None,
               *, tier: Optional[str] = None,
               k: Optional[int] = None) -> api.SearchResult:
        """Serve one query batch; see serving/api.py for the typed contract.

        ``queries`` is an [nq, dim] array or a SearchRequest (then no other
        arguments are allowed). Plain keywords mirror the request fields;
        ``quantized=`` is the retired boolean knob, mapped onto ``tier=`` with
        a DeprecationWarning for one release."""
        if isinstance(queries, api.SearchRequest):
            if any(a is not None for a in (sigma, quantized, impl, tier, k)):
                raise TypeError(
                    "pass either a SearchRequest or keyword overrides, not both")
            req = queries
        else:
            queries = np.asarray(queries)
            if queries.ndim == 1 or queries.shape[0] == 1:
                # single-query traffic belongs on the canonical entry point
                # (it routes through the batching front-end when one is
                # attached); raw 1-row arrays + loose kwargs survive one
                # release behind the shim
                api.warn_deprecated(
                    "search-single-query",
                    "passing a single query as a raw array to "
                    "LiraEngine.search is deprecated; use "
                    "search_one(SearchRequest(queries=q, ...))")
                if queries.ndim == 1:
                    queries = queries[None, :]
            if quantized is not None:
                api.warn_deprecated(
                    "search-quantized-kwarg",
                    "LiraEngine.search(quantized=) is deprecated; pass "
                    "tier='f32' / 'pq' / 'residual_pq' (or a SearchRequest)")
                if tier is None:
                    tier = tiers.legacy_tier_name(
                        quantized, quantized and self.cfg.residual_pq)
            req = api.SearchRequest(queries=queries, k=k, sigma=sigma,
                                    tier=tier, impl=impl)

        tr = self._tracer()
        # tracing wraps host-side stage boundaries in spans but never alters
        # the computation: the device call and the unconditional
        # block_until_ready run identically traced or not, which is what
        # makes tracing-on bit-identical to tracing-off (pinned in
        # tests/test_obs.py)
        with tr.span("engine.search") as sp_root:
            with tr.span("engine.prepare") as sp_prep:
                sigma = self.sigma if req.sigma is None else req.sigma
                tier_obj = tiers.resolve(
                    req.tier if req.tier is not None else self.cfg.tier)
                k = self.cfg.k if req.k is None else int(req.k)
                self._ensure_occupancy()
                missing = [f for f in tier_obj.store_specs(self.cfg)
                           if f not in self.store]
                if missing:
                    raise ValueError(
                        f"engine store lacks {missing} required by tier "
                        f"{tier_obj.name!r}; build with tier={tier_obj.name!r}")
                tier_obj.check_servable(self.cfg)  # e.g. pq refuses residual codes
                nq = req.queries.shape[0]
                nq_pad = self._batch_bucket(nq)
                fn, cache_hit, impl = self.serve_fn(nq_pad, sigma,
                                                    tier_obj.name, req.impl, k)
                qp = np.zeros((nq_pad, self.cfg.dim), np.float32)
                qp[:nq] = req.queries
                # pad rows are masked out of dispatch: they must not probe
                # partitions or occupy q_cap slots that real queries need
                valid = np.zeros((nq_pad,), bool)
                valid[:nq] = True
            with tr.span("engine.device", tier=tier_obj.name, impl=impl,
                         bucket=nq_pad, cache_hit=cache_hit) as sp_dev:
                # dispatch returns once the step is enqueued; wait is the
                # device running it (split so a stall names its side)
                with tr.span("engine.dispatch"), self.mesh:
                    out = fn(self.params, self.store, jnp.asarray(qp),
                             jnp.asarray(valid))
                with tr.span("engine.wait"):
                    d, i, npb, ovf, dups, blk = jax.block_until_ready(out)
            with tr.span("engine.post") as sp_post:
                npb_np = np.asarray(npb)[:nq]
                overflow = int(np.asarray(ovf).sum())
                dedup_hits = int(np.asarray(dups).sum())
                scan_blocks, dense_blocks = np.asarray(blk).sum(0).tolist()
                dists = np.asarray(d)[:nq]
                ids_np = np.asarray(i)[:nq]
            sp_root.set(tier=tier_obj.name, impl=impl, rows=nq)

        stages = None
        if tr.enabled:
            stages = {"prepare": sp_prep.duration_ms,
                      "device": sp_dev.duration_ms,
                      "post": sp_post.duration_ms}

        lbl = {"tier": tier_obj.name, "impl": impl}
        m = self._registry()
        m.counter("lira_engine_searches_total",
                  "engine.search calls").inc(**lbl)
        m.counter("lira_engine_rows_total",
                  "query rows served (pre-padding)").inc(nq, **lbl)
        m.counter("lira_engine_probes_total",
                  "partition probes attempted (pre q_cap drops — includes "
                  "any counted by overflow_probes_total)").inc(
                      float(npb_np.sum()), **lbl)
        m.counter("lira_engine_overflow_probes_total",
                  "probes dropped by q_cap bucket overflow").inc(
                      overflow, **lbl)
        m.counter("lira_engine_dedup_hits_total",
                  "replica-duplicate candidate slots merged away").inc(
                      dedup_hits, **lbl)
        m.counter("lira_engine_scan_blocks_total",
                  "candidate blocks the scan streamed (occupied partitions, "
                  "up to each one's last live slot)").inc(scan_blocks, **lbl)
        m.counter("lira_engine_scan_blocks_dense_total",
                  "candidate blocks of every local partition's whole "
                  "capacity").inc(dense_blocks, **lbl)
        m.counter("lira_engine_jit_cache_hits_total" if cache_hit
                  else "lira_engine_jit_cache_misses_total",
                  "serve-step jit cache").inc(**lbl)
        m.histogram("lira_engine_nprobe_eff",
                    "effective probes per query (σ-adaptive fan-out)",
                    buckets=obs_metrics.NPROBE_BUCKETS).observe_many(
                        npb_np, **lbl)
        m.gauge("lira_engine_q_cap_factor",
                "current dispatch-slack factor").set(
                    float(self.cfg.q_cap_factor))

        result = api.SearchResult(
            dists=dists, ids=ids_np,
            nprobe_eff=npb_np, overflow=overflow,
            stats=api.SearchStats(
                tier=tier_obj.name, impl=impl, k=k, sigma=float(sigma),
                bucket=nq_pad, cache_hit=cache_hit, dedup_hits=dedup_hits,
                latency_ms=sp_root.duration_ms, stages=stages,
                epoch=self.epoch))
        if getattr(self.cfg, "auto_q_cap", False):
            self._maybe_bump_q_cap(result.overflow)
        return result

    def overflow_rate(self) -> float:
        """Cumulative q_cap overflow rate: dropped probes / attempted probes,
        across every tier/impl this engine's registry has seen. 0.0 until any
        search ran. ``lira_engine_probes_total`` counts ATTEMPTED probes —
        ``nprobe_eff`` is summed from ``probe_ok`` before q_cap drops — so it
        is the denominator by itself; adding ``dropped`` to it would count
        every dropped probe twice and under-report the rate."""
        m = self._registry()
        dropped = m.counter("lira_engine_overflow_probes_total").total()
        attempted = m.counter("lira_engine_probes_total").total()
        return dropped / attempted if attempted > 0 else 0.0

    # ------------------------------------------------------------ front-end

    def search_one(self, request: api.SearchRequest) -> api.SearchResult:
        """The canonical single-query entry point. With a front-end attached
        (``attach_frontend``) the request joins the dynamic-batching queue and
        ``result()`` is demanded immediately — coalescing with whatever
        compatible traffic is already waiting; without one it falls back to a
        1-row batch through ``search``. ``request.queries`` is one query:
        ``[dim]`` or ``[1, dim]``."""
        if not isinstance(request, api.SearchRequest):
            raise TypeError("search_one takes a SearchRequest; for raw query "
                            "batches use search()")
        q = np.asarray(request.queries)
        if q.ndim == 1:
            request = dataclasses.replace(request, queries=q[None, :])
        elif q.ndim != 2 or q.shape[0] != 1:
            raise ValueError("search_one serves exactly one query "
                             f"(got shape {q.shape}); use search() for batches")
        if self.frontend is not None:
            return self.frontend.submit(request).result()
        return self.search(request)

    def attach_frontend(self, config=None, **kwargs):
        """Create and attach a ``ServingFrontend`` over this engine (see
        serving/frontend.py for the batching/admission/telemetry contract);
        returns it. Detach with ``engine.frontend = None``."""
        from repro.serving.frontend import ServingFrontend

        self.frontend = ServingFrontend(self, config, **kwargs)
        return self.frontend

    def _maybe_bump_q_cap(self, overflow: int) -> None:
        """Adaptive dispatch slack: after _AUTO_Q_CAP_AFTER consecutive
        overflowing calls, double q_cap_factor and drop the serve cache so the
        next call compiles with the wider buckets (the overflow counter the
        PR 4 dispatch fix surfaced, closed into a control loop)."""
        if overflow <= 0:
            self._overflow_streak = 0
            return
        self._overflow_streak += 1
        if self._overflow_streak >= self._AUTO_Q_CAP_AFTER:
            self.cfg = dataclasses.replace(
                self.cfg, q_cap_factor=self.cfg.q_cap_factor * 2.0)
            self._serve_cache.clear()
            self._overflow_streak = 0
            # adaptation events are observable, not silent cache drops: the
            # bump counter + gauge pair shows WHEN the control loop fired and
            # WHERE the slack factor ended up
            m = self._registry()
            m.counter("lira_engine_q_cap_bumps_total",
                      "auto_q_cap adaptations (doubled q_cap_factor, "
                      "dropped serve cache)").inc()
            m.gauge("lira_engine_q_cap_factor",
                    "current dispatch-slack factor").set(
                        float(self.cfg.q_cap_factor))

    # ------------------------------------------------------------- mutation
    #
    # The store lifecycle is epoch-versioned: every mutation drains the
    # front-end (no coalesced batch may span two epochs), rewrites the
    # per-slot planes the tier declares (tiers.Tier.slot_fields), and bumps
    # ``epoch``. Shape is the only thing that invalidates compiled serve
    # steps: growing or compacting ``capacity`` changes plane shapes (and
    # PQ's rerank clamp), so it enters the serve-fn cache key and clears the
    # cache; same-shape mutations swap in new device arrays of identical
    # shape/dtype, which jitted fns accept without retracing.

    def _ensure_occupancy(self) -> None:
        """Stores predating the mutable-index refactor (and raw test store
        dicts) carry no occupancy plane — a dense store's occupancy is
        exactly its id validity."""
        if "occupancy" not in self.store:
            self.store = dict(self.store)
            self.store["occupancy"] = self.store["ids"] >= 0

    def _staleness_counters(self) -> np.ndarray:
        if (self._stale_inserts is None
                or len(self._stale_inserts) != self.cfg.n_partitions):
            self._stale_inserts = np.zeros(self.cfg.n_partitions, np.int64)
        return self._stale_inserts

    def _quiesce_frontend(self) -> None:
        """Epoch-swap atomicity: flush the front-end's in-flight coalesced
        batches BEFORE mutating, so every batch is served wholly within one
        epoch (its results carry the pre-mutation SearchStats.epoch; requests
        submitted after the mutation see the bumped one)."""
        if self.frontend is not None:
            self.frontend.quiesce()

    def _bump_epoch(self, *, shape_changed: bool = False) -> None:
        self.epoch += 1
        if shape_changed:
            self._serve_cache.clear()
        m = self._registry()
        m.counter("lira_engine_epoch_bumps_total",
                  "store mutations (insert/delete/compact/repartition)").inc()
        if shape_changed:
            m.counter("lira_engine_shape_epoch_bumps_total",
                      "shape-changing mutations (capacity moved; compiled "
                      "serve steps invalidated)").inc()
        m.gauge("lira_engine_epoch", "current store epoch").set(
            float(self.epoch))

    def _tombstones_per_partition(self) -> np.ndarray:
        """A tombstone is a cleared-occupancy slot still holding an id ≥ 0
        (delete leaves the id plane behind; reuse or compaction heals it)."""
        occ = np.asarray(self.store["occupancy"])
        ids = np.asarray(self.store["ids"])
        return (~occ & (ids >= 0)).sum(1).astype(np.int64)

    def _update_store_gauges(self) -> None:
        occ = np.asarray(self.store["occupancy"])
        live = int(occ.sum())
        tomb = int(self._tombstones_per_partition().sum())
        m = self._registry()
        m.gauge("lira_engine_live_slots", "occupied store slots").set(live)
        m.gauge("lira_engine_tombstone_slots",
                "deleted-but-uncompacted slots (insertable, id not yet "
                "healed)").set(tomb)
        m.gauge("lira_engine_free_slots",
                "never-written or compacted-away slots").set(
                    occ.size - live - tomb)

    _GROW_SLACK = 1.5  # capacity overshoot per grow, so steady insert
    #                    streams amortize recompiles instead of growing (and
    #                    recompiling) once per insert batch

    def insert(self, x, ids) -> int:
        """Append rows to the live index. Each row takes a free slot in the
        nearest partition that has one (within ``mutable.PLACE_WINDOW``
        nearest); rows that land off their argmin partition count toward the
        staleness that triggers ``maybe_repartition``. When some row finds no
        slot, every per-slot plane grows (with ``_GROW_SLACK``) — a shape
        change that invalidates compiled serve steps; otherwise the mutation
        is same-shape and the jit cache keeps hitting. New rows get no η
        replicas until the next repartition refreshes the whole replica set.
        Callers own id uniqueness (an id inserted twice becomes two live
        rows, deduped at merge time like a replica). Returns rows inserted."""
        from repro.serving import mutable

        x = np.asarray(x, np.float32)
        if x.ndim == 1:
            x = x[None, :]
        ids = np.atleast_1d(np.asarray(ids, np.int32))
        if x.shape[0] != ids.shape[0]:
            raise ValueError(f"{x.shape[0]} rows but {ids.shape[0]} ids")
        if x.shape[1] != self.cfg.dim:
            raise ValueError(f"rows have dim {x.shape[1]}, index has "
                             f"dim {self.cfg.dim}")
        if x.shape[0] == 0:
            return 0
        self._ensure_occupancy()
        self._quiesce_frontend()
        tier = tiers.resolve(self.cfg.tier)
        tr = self._tracer()
        with tr.span("engine.insert", rows=int(x.shape[0])) as sp:
            occ = np.asarray(self.store["occupancy"])
            cents = np.asarray(self.store["centroids"], np.float32)
            d2 = ((x * x).sum(1)[:, None] - 2.0 * x @ cents.T
                  + (cents * cents).sum(1)[None, :])
            plan = mutable.plan_insert(occ, d2)
            parts, slots, mis = plan.parts, plan.slots, plan.misassigned
            shape_changed = not bool(plan.ok.all())
            if shape_changed:
                # grow so every unplaced row fits in its argmin partition
                occ_w = occ.copy()
                occ_w[parts[plan.ok], slots[plan.ok]] = True
                fail = ~plan.ok
                demand = occ_w.sum(1) + np.bincount(
                    d2[fail].argmin(1), minlength=self.cfg.n_partitions)
                new_cap = mutable.align_up(
                    max(int(demand.max()),
                        int(np.ceil(self.cfg.capacity * self._GROW_SLACK))),
                    self._slot_align())
                planes = mutable.grow_store(
                    {n: self.store[n] for n in tier.slot_fields(self.cfg)},
                    new_cap)
                self.store = dict(self.store)
                self.store.update(
                    {n: jnp.asarray(a) for n, a in planes.items()})
                self.cfg = dataclasses.replace(self.cfg, capacity=new_cap)
                occ_w = mutable.grow_store({"occupancy": occ_w},
                                           new_cap)["occupancy"]
                replan = mutable.plan_insert(occ_w, d2[fail])
                assert bool(replan.ok.all()), "grown store must fit all rows"
                parts = np.where(plan.ok, parts, -1)
                slots = np.where(plan.ok, slots, -1)
                parts[fail], slots[fail] = replan.parts, replan.slots
                mis = mis.copy()
                mis[fail] = replan.misassigned
            # the tier re-encodes content planes for the destination
            # partitions; ids/occupancy are engine bookkeeping
            rows = tier.encode_rows(self.cfg, self.store, x, parts)
            store = dict(self.store)
            p, s = jnp.asarray(parts), jnp.asarray(slots)
            for name, vals in rows.items():
                store[name] = store[name].at[p, s].set(
                    jnp.asarray(vals).astype(store[name].dtype))
            store["ids"] = store["ids"].at[p, s].set(jnp.asarray(ids))
            store["occupancy"] = store["occupancy"].at[p, s].set(True)
            self.store = store
            np.add.at(self._staleness_counters(), parts[mis], 1)
            sp.set(misassigned=int(mis.sum()), grew=shape_changed)
        self._bump_epoch(shape_changed=shape_changed)
        m = self._registry()
        m.counter("lira_engine_inserts_total", "rows inserted").inc(
            int(x.shape[0]))
        m.counter("lira_engine_misassigned_inserts_total",
                  "inserts placed off their argmin partition (staleness "
                  "source)").inc(int(mis.sum()))
        if shape_changed:
            m.counter("lira_engine_capacity_grows_total",
                      "insert-driven capacity growths").inc()
        self._update_store_gauges()
        return int(x.shape[0])

    def delete(self, ids) -> int:
        """Tombstone every live slot holding one of ``ids`` (replicas
        included): occupancy clears, the id plane keeps the id until the slot
        is reused or compacted. Same-shape — zero recompiles. Returns the
        number of slots tombstoned (0 for wholly unknown ids, no epoch
        bump)."""
        self._ensure_occupancy()
        ids = np.unique(np.atleast_1d(np.asarray(ids, np.int64)))
        occ = np.asarray(self.store["occupancy"])
        hit = occ & np.isin(np.asarray(self.store["ids"]), ids)
        removed = int(hit.sum())
        m = self._registry()
        m.counter("lira_engine_deletes_total", "ids passed to delete").inc(
            len(ids))
        m.counter("lira_engine_deleted_slots_total",
                  "live slots tombstoned by delete").inc(removed)
        if not removed:
            return 0
        self._quiesce_frontend()
        tr = self._tracer()
        with tr.span("engine.delete", slots=removed):
            self.store = dict(self.store)
            self.store["occupancy"] = jnp.asarray(occ & ~hit)
        self._bump_epoch()
        self._update_store_gauges()
        return removed

    def compact(self) -> int:
        """Repack live slots to the front of every partition and shrink
        capacity to the max live count (floored at cfg.k — the scan's top-k
        needs that many candidate slots): tombstones and holes are erased,
        dead tails reset to pad sentinels. Usually a shape change (compiled
        serve steps invalidated). Returns reclaimed slots (Δcapacity · B)."""
        from repro.serving import mutable

        self._ensure_occupancy()
        self._quiesce_frontend()
        tier = tiers.resolve(self.cfg.tier)
        tr = self._tracer()
        with tr.span("engine.compact",
                     capacity=int(self.cfg.capacity)) as sp:
            occ = np.asarray(self.store["occupancy"])
            packed, new_cap = mutable.compact_store(
                {n: self.store[n] for n in tier.slot_fields(self.cfg)}, occ,
                min_capacity=self.cfg.k, align=self._slot_align())
            shape_changed = new_cap != self.cfg.capacity
            reclaimed = (self.cfg.capacity - new_cap) * self.cfg.n_partitions
            store = dict(self.store)
            store.update({n: jnp.asarray(a) for n, a in packed.items()})
            self.store = store
            if shape_changed:
                self.cfg = dataclasses.replace(self.cfg, capacity=new_cap)
            sp.set(new_capacity=new_cap, reclaimed=reclaimed)
        self._bump_epoch(shape_changed=shape_changed)
        m = self._registry()
        m.counter("lira_engine_compactions_total", "compaction passes").inc()
        m.counter("lira_engine_reclaimed_slots_total",
                  "slots reclaimed by compaction").inc(reclaimed)
        self._update_store_gauges()
        return reclaimed

    def staleness(self) -> float:
        """(misassigned inserts + tombstoned slots) / live rows — the drift
        measure ``maybe_repartition`` gates on (cfg.repartition_threshold).
        Tombstones count because holes dilute every probe of their partition;
        misassigned inserts because the probing model ranks partitions by
        content the argmin says belongs elsewhere (the boundary drift IRLI's
        re-assignment loop repairs)."""
        self._ensure_occupancy()
        live = int(np.asarray(self.store["occupancy"]).sum())
        tomb = int(self._tombstones_per_partition().sum())
        return (int(self._staleness_counters().sum()) + tomb) / max(1, live)

    def maybe_repartition(self, *, force: bool = False,
                          max_moves: Optional[int] = None) -> bool:
        """IRLI-style iterative re-assignment (arxiv 2103.09944), gated on
        staleness: when (misassigned inserts + tombstones) / live rows
        reaches ``cfg.repartition_threshold`` (or ``force=True``), re-assign
        every live row to its argmin partition (``max_moves`` caps the pass
        to the most-misassigned rows, by margin), re-encode through the tier,
        refresh the η replica set via core.redundancy.plan_redundancy, and
        rebuild the slot layout — erasing tombstones and resetting staleness.
        Centroids, codebooks and the probing model are unchanged: drift is
        repaired by moving rows, not retraining. Returns True iff a
        repartition ran."""
        self._ensure_occupancy()
        occ = np.asarray(self.store["occupancy"])
        frac = ((self._staleness_counters()
                 + self._tombstones_per_partition())
                / np.maximum(1, occ.sum(1)))
        m = self._registry()
        m.histogram("lira_engine_partition_staleness",
                    "per-partition staleness fraction at repartition checks",
                    buckets=obs_metrics.STALENESS_BUCKETS).observe_many(frac)
        if not force and self.staleness() < getattr(
                self.cfg, "repartition_threshold", 0.25):
            return False
        self._repartition(max_moves=max_moves)
        return True

    def _repartition(self, max_moves: Optional[int] = None) -> None:
        from repro.core.redundancy import plan_redundancy, replica_rows
        from repro.serving import mutable

        self._quiesce_frontend()
        tier = tiers.resolve(self.cfg.tier)
        tr = self._tracer()
        with tr.span("engine.repartition") as sp:
            occ = np.asarray(self.store["occupancy"])
            ids = np.asarray(self.store["ids"])
            cents = np.asarray(self.store["centroids"], np.float32)
            nb, cap = occ.shape
            pb, ps = np.nonzero(occ)
            if len(pb) == 0:
                return
            x = np.asarray(self.store["vectors"])[pb, ps].astype(np.float32)
            rid = ids[pb, ps]
            # one primary copy per id (η replicas are regenerated below):
            # keep the copy nearest its own partition's centroid
            d_own = ((x - cents[pb]) ** 2).sum(1)
            order = np.lexsort((d_own, rid))
            keep_first = np.ones(len(order), bool)
            keep_first[1:] = rid[order][1:] != rid[order][:-1]
            keep = order[keep_first]
            xu, idu, cur = x[keep], rid[keep], pb[keep].astype(np.int64)
            d2 = ((xu * xu).sum(1)[:, None] - 2.0 * xu @ cents.T
                  + (cents * cents).sum(1)[None, :])
            best = d2.argmin(1).astype(np.int64)
            assign, mis = best, best != cur
            if max_moves is not None and int(mis.sum()) > int(max_moves):
                # partial pass: only the most-misassigned rows move, ranked
                # by how much closer their argmin centroid is
                rows_i = np.arange(len(xu))
                margin = d2[rows_i, cur] - d2[rows_i, best]
                cand = np.flatnonzero(mis)
                top = cand[np.argsort(-margin[cand],
                                      kind="stable")[:int(max_moves)]]
                assign = cur.copy()
                assign[top] = best[top]
            moved = int((assign != cur).sum())
            x_all, id_all, a_all = xu, idu, assign
            if getattr(self.cfg, "eta", 0.0) > 0:
                # replica refresh: boundary points re-picked by the probing
                # model against the DRIFTED assignment, so replicas track
                # the boundaries the churn moved
                plan = plan_redundancy(self.params, xu,
                                       assign.astype(np.int32), cents,
                                       eta=self.cfg.eta, sigma=self.sigma)
                rv, ri, ra = replica_rows(plan, xu, idu)
                x_all = np.concatenate([xu, rv], 0)
                id_all = np.concatenate([idu, ri], 0)
                a_all = np.concatenate([assign, ra.astype(np.int64)], 0)
            slots, counts = mutable.layout_rows(a_all, nb)
            needed = mutable.align_up(max(int(counts.max(initial=1)), self.cfg.k),
                                      self._slot_align())
            # capacity only grows when the new layout demands it — a layout
            # that still fits keeps the shape (and the compiled serve steps)
            shape_changed = needed > cap
            new_cap = needed if shape_changed else cap
            if shape_changed:
                self.cfg = dataclasses.replace(self.cfg, capacity=new_cap)
            # full re-encode through the tier: codebooks/centroids/probing
            # are unchanged, so unmoved rows keep bit-identical codes
            rows = tier.encode_rows(self.cfg, self.store, x_all, a_all)
            rows["ids"] = id_all.astype(np.int32)
            store = dict(self.store)
            for name in tier.slot_fields(self.cfg):
                old = np.asarray(self.store[name])
                plane = np.full((nb, new_cap, *old.shape[2:]),
                                mutable.fill_value(name), old.dtype)
                if name == "occupancy":
                    plane[a_all, slots] = True
                else:
                    plane[a_all, slots] = np.asarray(
                        rows[name]).astype(old.dtype)
                store[name] = jnp.asarray(plane)
            self.store = store
            self._stale_inserts = np.zeros(nb, np.int64)
            sp.set(rows=len(xu), moved=moved, replicas=len(x_all) - len(xu),
                   capacity=new_cap)
        self._bump_epoch(shape_changed=shape_changed)
        m = self._registry()
        m.counter("lira_engine_repartitions_total",
                  "IRLI-style re-assignment passes").inc()
        m.counter("lira_engine_repartition_moved_rows_total",
                  "rows moved to their argmin partition").inc(moved)
        self._update_store_gauges()

    # ------------------------------------------------------------ persistence

    def save(self, directory, step: int = 0):
        """Persist params + store + config via repro.ckpt (atomic, crash-safe)
        so built indexes stop being rebuilt per process. bfloat16 planes are
        upcast to f32 on disk (npy has no bf16); ``load`` restores the tier
        dtype from the config."""
        from repro.ckpt import CheckpointManager

        def _savable(leaf):
            if jnp.dtype(getattr(leaf, "dtype", np.float32)) == jnp.bfloat16:
                return np.asarray(jnp.asarray(leaf).astype(jnp.float32))
            return np.asarray(leaf)

        self._ensure_occupancy()  # mutable-index state always round-trips
        tree = jax.tree.map(_savable, {"params": self.params,
                                       "store": dict(self.store)})
        extra = {"config": dataclasses.asdict(self.cfg), "sigma": self.sigma,
                 "epoch": int(self.epoch),
                 "stale_inserts": [int(v) for v in
                                   self._staleness_counters()]}
        return CheckpointManager(directory).save(step, tree, extra=extra)

    @classmethod
    def load(cls, directory, mesh, step: Optional[int] = None):
        """Rebuild an engine from a ``save`` checkpoint: config comes from the
        manifest, the restore template (tree structure + dtypes) is derived
        from the config's tier declarations."""
        import json
        import pathlib

        from repro.ckpt import CheckpointManager

        if not pathlib.Path(directory).is_dir():
            # check before CheckpointManager, whose constructor mkdirs — a
            # typo'd path must not leave an empty directory tree behind
            raise FileNotFoundError(f"no engine checkpoint under {directory}")
        mgr = CheckpointManager(directory)
        step = step if step is not None else mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no engine checkpoint under {directory}")
        meta = json.loads(
            (mgr.dir / f"step_{step:010d}" / "manifest.json").read_text())
        raw = {key: tuple(val) if isinstance(val, list) else val
               for key, val in meta["extra"]["config"].items()}
        cfg = LiraSystemConfig(**raw)
        template = {
            "params": jax.tree.map(lambda s: jnp.zeros((), s.dtype),
                                   probing_param_specs_cache(cfg)),
            "store": {name: jnp.zeros((), spec.dtype)
                      for name, spec in store_specs(cfg).items()},
        }
        tree, _, extra = mgr.restore(template, step=step)
        stale = extra.get("stale_inserts")
        return cls(cfg=cfg, params=tree["params"], store=tree["store"],
                   mesh=mesh, sigma=float(extra.get("sigma", 0.5)),
                   epoch=int(extra.get("epoch", 0)),
                   _stale_inserts=(np.asarray(stale, np.int64)
                                   if stale is not None else None)).place()
