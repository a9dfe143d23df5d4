import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# host devices only: every --all child would otherwise try to take the TPU
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run (deliverable e): lower + compile EVERY (arch × shape)
cell on the production meshes — 16×16 single-pod and 2×16×16 multi-pod —
recording memory analysis, HLO/analytic cost terms, and the collective
schedule for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

The XLA_FLAGS / JAX_PLATFORMS lines above MUST precede every other import (jax locks the device
count at first init). Run one cell:

    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-coder-33b \
        --shape train_4k --mesh single

or everything (subprocess per cell, failures isolated):

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import pathlib
import subprocess
import sys
import time

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def model_flops(config, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train / 2·N·D inference (+ attention terms);
    MoE counts active params only (EXPERIMENTS.md §Roofline)."""
    from repro.configs.base import GNNConfig, LiraSystemConfig, LMConfig, RecsysConfig

    if isinstance(config, LMConfig):
        n_act = config.active_param_count
        l, h, dh = config.n_layers, config.n_heads, config.head_dim
        if shape.kind == "train":
            t = shape["global_batch"] * shape["seq_len"]
            attn = 6 * l * shape["global_batch"] * shape["seq_len"] ** 2 * h * dh  # causal-adjusted (×0.5 of full)
            return 6.0 * n_act * t + attn
        if shape.kind == "prefill":
            t = shape["global_batch"] * shape["seq_len"]
            attn = 2 * l * shape["global_batch"] * shape["seq_len"] ** 2 * h * dh
            return 2.0 * n_act * t + attn
        if shape.kind == "decode":
            b, s = shape["global_batch"], shape["seq_len"]
            attn = 4 * l * b * s * h * dh
            return 2.0 * n_act * b + attn
    if isinstance(config, GNNConfig):
        e = shape["n_edges"] * shape.dims.get("batch", 1)
        t = e * shape["triplet_mult"]
        hdim = config.d_hidden
        per_block = 2 * t * hdim * hdim * (config.n_bilinear + 1) + 6 * e * hdim * hdim
        fwd = config.n_blocks * per_block + 2 * e * (2 * hdim) * hdim
        return 3.0 * fwd  # train
    if isinstance(config, RecsysConfig):
        b = shape["batch"] if shape.kind != "retrieval" else shape["n_candidates"]
        d = config.embed_dim
        f = config.n_sparse
        per = 0.0
        if config.interaction == "fm":
            sizes = (f * d, *config.mlp, 1)
            per = sum(2 * a * bb for a, bb in zip(sizes[:-1], sizes[1:]))
        elif config.interaction == "self-attn":
            da = config.d_attn * config.n_heads
            d_in = d
            for _ in range(config.n_attn_layers):
                per += 2 * f * d_in * da * 4 + 4 * f * f * da
                d_in = da
            per += 2 * f * da
        elif config.interaction == "multi-interest":
            per = config.capsule_iters * (4 * config.hist_len * config.n_interests * d) + 2 * config.hist_len * d * d
        elif config.interaction == "dot":
            sizes = tuple(config.bot_mlp)
            per += sum(2 * a * bb for a, bb in zip(sizes[:-1], sizes[1:]))
            nf = config.n_sparse + 1
            per += 2 * nf * nf * d
            d_int = nf * (nf - 1) // 2 + config.bot_mlp[-1]
            sizes = (d_int, *config.top_mlp)
            per += sum(2 * a * bb for a, bb in zip(sizes[:-1], sizes[1:]))
        mult = 3.0 if shape.kind == "rec_train" else 1.0
        return mult * b * per
    if isinstance(config, LiraSystemConfig):
        if shape.kind == "lira_serve":
            q = shape["n_queries"]
            return q * config.nprobe_max * config.capacity * 2.0 * config.dim
        if shape.kind == "lira_train":
            import jax

            from repro.serving.engine import probing_param_specs_cache

            import numpy as np
            n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(probing_param_specs_cache(config)))
            return 6.0 * n_params * shape["batch"]
    return 0.0


def top_buffers(text: str, n: int = 15):
    """Largest HLO result buffers with op names — the memory 'profile'."""
    import re

    from repro.launch.hlo_cost import _DTYPE_BYTES, _SHAPE_RE

    best = []
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(", line)
        if not m or m.group(3) in ("parameter", "tuple", "get-tuple-element"):
            continue
        b = 0
        for dt, dims in _SHAPE_RE.findall(m.group(2)):
            if dt in _DTYPE_BYTES:
                cnt = 1
                for d in (dims.split(",") if dims else []):
                    cnt *= int(d)
                b += cnt * _DTYPE_BYTES[dt]
        meta = re.search(r'op_name="([^"]*)"', line)
        best.append((b, m.group(3), (meta.group(1) if meta else m.group(1))[:110]))
    best.sort(reverse=True)
    return best[:n]


def _lower_cell(config, shape, mesh):
    """Build + lower + compile one cell. Returns (compiled, t_lower, t_compile)."""
    import jax

    from repro.models import build_bundle
    from repro.models.api import named_shardings

    bundle = build_bundle(config, mesh)
    sd = bundle.step(shape)
    pspecs = bundle.param_specs(shape)
    pshard = named_shardings(mesh, bundle.param_pspecs(shape))
    in_shard_named = {k: named_shardings(mesh, v) for k, v in sd.input_pspecs.items()}
    ispecs = sd.input_specs

    train_kinds = ("train", "graph_train", "rec_train", "lira_train")
    t0 = time.time()
    with mesh:
        if shape.kind in train_kinds:
            oshard = named_shardings(mesh, bundle.opt_pspecs(shape))
            ospecs = bundle.opt_specs(shape)
            args = ((pspecs, ospecs), ispecs)
            shardings = ((pshard, oshard), in_shard_named)
            fn = jax.jit(sd.fn, in_shardings=shardings, donate_argnums=(0,))
            lowered = fn.lower(*args)
        elif shape.kind == "prefill":
            okw = {}
            if sd.out_pspecs is not None:
                okw["out_shardings"] = named_shardings(mesh, sd.out_pspecs)
            lowered = jax.jit(sd.fn, in_shardings=(pshard, in_shard_named["tokens"]), **okw).lower(
                pspecs, ispecs["tokens"])
        elif shape.kind == "decode":
            okw = {}
            if sd.out_pspecs is not None:
                okw["out_shardings"] = named_shardings(mesh, sd.out_pspecs)
            fn = jax.jit(sd.fn,
                         in_shardings=(pshard, in_shard_named["cache"],
                                       in_shard_named["tokens"], in_shard_named["pos"]),
                         donate_argnums=(1,), **okw)
            lowered = fn.lower(pspecs, ispecs["cache"], ispecs["tokens"], ispecs["pos"])
        elif shape.kind == "rec_serve" or shape.kind == "retrieval":
            lowered = jax.jit(sd.fn, in_shardings=(pshard, in_shard_named)).lower(pspecs, ispecs)
        elif shape.kind == "lira_serve":
            lowered = jax.jit(sd.fn,
                              in_shardings=(pshard, in_shard_named["store"], in_shard_named["queries"])
                              ).lower(pspecs, ispecs["store"], ispecs["queries"])
        else:
            raise ValueError(shape.kind)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, t_lower, t_compile


def run_cell(arch: str, shape_name: str, mesh_kind: str, variant: str = "baseline",
             out_path: str | None = None, verbose: bool = True, show_buffers: bool = False) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch import hlo_cost
    from repro.launch.mesh import HBM_PER_CHIP, make_production_mesh
    from repro.models import build_bundle  # noqa: F401 (re-exported for callers)

    config, shapes = get_config(arch)
    if variant != "baseline":
        config = apply_variant(config, variant)
    shape = next(s for s in shapes if s.name == shape_name)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))

    compiled, t_lower, t_compile = _lower_cell(config, shape, mesh)

    # Temp-memory probe: XLA:CPU FloatNormalization shadows bf16 buffers with
    # f32 copies (don't exist on TPU). Recompile with dtype=f32 — artifact-free
    # buffer accounting — and estimate the TPU bf16 temp as half of it
    # (activations halve; minority f32 accumulators make this conservative-ish).
    temp_probe = None
    if getattr(config, "dtype", "float32") == "bfloat16":
        cfg_f32 = dataclasses.replace(config, dtype="float32")
        probe_compiled, _, _ = _lower_cell(cfg_f32, shape, mesh)
        temp_probe = probe_compiled.memory_analysis()
        del probe_compiled

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    hc = hlo_cost.analyze(text)
    shadows = hlo_cost.f32_shadow_bytes(text)
    mf = model_flops(config, shape)

    per_dev_hbm = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # State (args/out/alias) keeps declared dtypes — exact. Temp from the f32
    # probe (artifact-free) halved for bf16 on TPU; f32-native archs unchanged.
    if temp_probe is not None:
        adj_temp = temp_probe.temp_size_in_bytes // 2
    else:
        adj_temp = mem.temp_size_in_bytes
    per_dev_tpu = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + adj_temp - mem.alias_size_in_bytes)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "variant": variant,
        "kind": shape.kind, "n_chips": n_chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
            "per_device_total": int(per_dev_hbm),
            "f32_shadow_bytes": shadows["bytes_total"],
            "f32_shadow_count": shadows["count"],
            "temp_f32_probe": (temp_probe.temp_size_in_bytes if temp_probe else None),
            "temp_tpu_estimate": int(adj_temp),
            "per_device_tpu_adjusted": int(per_dev_tpu),
            "fits_16g": bool(per_dev_tpu <= HBM_PER_CHIP),
            "fits_16g_cpu_raw": bool(per_dev_hbm <= HBM_PER_CHIP),
        },
        "xla_cost_analysis": {"flops": ca.get("flops", 0.0), "bytes": ca.get("bytes accessed", 0.0)},
        "hlo": {
            "flops_per_device": hc["flops"],
            "bytes_per_device": hc["bytes"],
            "collective_bytes_per_device": hc["collective_bytes"],
            "collectives": hc["collectives"],
            "top_flops": hc["top_flops"][:8],
        },
        "model_flops_global": mf,
        "model_flops_per_device": mf / n_chips,
    }
    if verbose:
        print(json.dumps({k: result[k] for k in
                          ("arch", "shape", "mesh", "variant", "n_chips", "compile_s")}))
        print(f"  memory/device: {per_dev_hbm/2**30:.2f} GiB raw | "
              f"{per_dev_tpu/2**30:.2f} GiB tpu-adj (shadows {shadows['bytes_total']/2**30:.2f} GiB) "
              f"fits16G={result['memory']['fits_16g']}")
        print(f"  hlo flops/dev: {hc['flops']:.3e}  bytes/dev: {hc['bytes']:.3e}  "
              f"coll/dev: {hc['collective_bytes']:.3e}")
        print(f"  model flops/dev: {mf/n_chips:.3e}  useful-ratio: "
              f"{(mf/n_chips)/max(hc['flops'],1):.3f}")
    if show_buffers:
        for b, op, name in top_buffers(text):
            print(f"  {b/2**30:7.2f} GiB {op:22s} {name}")
    if out_path:
        pathlib.Path(out_path).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(out_path).write_text(json.dumps(result, indent=1))
    return result


def apply_variant(config, variant: str):
    """Named perf variants for §Perf hillclimbing. Supports one level of
    nesting for sub-configs (e.g. moe.capacity_factor=1.0)."""
    import dataclasses
    if variant == "baseline":
        return config
    overrides = {}
    for kv in variant.split(","):
        k, v = kv.split("=")
        if "." in k:
            outer, inner = k.split(".", 1)
            sub = overrides.get(outer, getattr(config, outer))
            cur = getattr(sub, inner)
            overrides[outer] = dataclasses.replace(
                sub, **{inner: type(cur)(v) if not isinstance(cur, bool) else v == "True"})
        else:
            cur = getattr(config, k)
            overrides[k] = type(cur)(v) if not isinstance(cur, bool) else v == "True"
    return dataclasses.replace(config, **overrides)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--timeout", type=int, default=1200)
    ap.add_argument("--out")
    ap.add_argument("--buffers", action="store_true", help="print largest HLO buffers")
    args = ap.parse_args()

    if args.all:
        from repro.configs import ARCH_IDS, get_config

        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        failures, done = [], 0
        cells = []
        for arch in ARCH_IDS:
            _, shapes = get_config(arch)
            for shape in shapes:
                for mk in meshes:
                    cells.append((arch, shape.name, mk))
        print(f"dry-run: {len(cells)} cells")
        for arch, shape_name, mk in cells:
            out = RESULTS_DIR / f"{arch}__{shape_name}__{mk}__{args.variant}.json"
            if out.exists():
                done += 1
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape_name, "--mesh", mk, "--variant", args.variant,
                   "--out", str(out)]
            t0 = time.time()
            try:
                r = subprocess.run(cmd, capture_output=True, text=True, timeout=args.timeout,
                                   env={**os.environ, "PYTHONPATH": "src"})
                if r.returncode != 0:
                    failures.append((arch, shape_name, mk, r.stderr[-2000:]))
                    print(f"FAIL {arch}/{shape_name}/{mk} ({time.time()-t0:.0f}s)")
                else:
                    done += 1
                    print(f"ok   {arch}/{shape_name}/{mk} ({time.time()-t0:.0f}s)")
            except subprocess.TimeoutExpired:
                failures.append((arch, shape_name, mk, "timeout"))
                print(f"TIMEOUT {arch}/{shape_name}/{mk}")
        print(f"\n{done}/{len(cells)} cells passed, {len(failures)} failures")
        for f in failures:
            print("-" * 60)
            print(f[0], f[1], f[2])
            print(f[3][:1500])
        sys.exit(1 if failures else 0)

    out = args.out or str(RESULTS_DIR / f"{args.arch}__{args.shape}__{args.mesh}__{args.variant}.json")
    run_cell(args.arch, args.shape, args.mesh, args.variant, out, show_buffers=args.buffers)


if __name__ == "__main__":
    main()
