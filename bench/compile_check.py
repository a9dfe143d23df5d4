#!/usr/bin/env python3
"""Compile each cell's serve steps for a described TPU v5e and print their
``memory_analysis()``. Nothing runs and no chip is needed: the TPU compiler
lowers the jitted serve step (Mosaic kernels included) for a chip that is
described, not attached, and refuses what would not fit or lower there.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/compile_check.py [--capacity 12160]

Shapes follow the cells: the f32 step at the batch cell's bucket (1024
queries) and the residual_pq steps at every bucket the online cell's
front-end can flush (8 to 128 queries).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

STEPS = (("f32", 1024),) + tuple(("residual_pq", b) for b in (8, 16, 32, 64, 128))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--capacity", type=int, default=12160,
                    help="slots per partition (the build's largest partition, "
                         "rounded up to whole 128-lane tiles)")
    ap.add_argument("--hlo-out", default="",
                    help="directory to write each step's optimized HLO text to")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.lira_ann import CONFIG, CONFIG_QUANTIZED
    from repro.serving import tiers
    from repro.serving.engine import make_serve_step, probing_param_specs_cache, store_specs

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, P())
    for tier, bucket in STEPS:
        base = CONFIG if tier == "f32" else CONFIG_QUANTIZED
        cfg = dataclasses.replace(base, capacity=args.capacity, tier=tier)
        step = make_serve_step(cfg, mesh, bucket, tier=tier, impl="pallas", count_dedup=True)
        params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
                              probing_param_specs_cache(cfg))
        pspecs = tiers.resolve(tier).store_pspecs(cfg)
        store = {n: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, pspecs[n]))
                 for n, s in store_specs(cfg).items()}
        compiled = jax.jit(step).lower(
            params, store, jax.ShapeDtypeStruct((bucket, cfg.dim), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((bucket,), jnp.bool_, sharding=rep)).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        if args.hlo_out:
            out = pathlib.Path(args.hlo_out)
            out.mkdir(parents=True, exist_ok=True)
            (out / f"{tier}_{bucket}.hlo.txt").write_text(text)
        print(json.dumps({
            "tier": tier, "bucket": bucket, "capacity": args.capacity,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "kernels": text.count("tpu_custom_call")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
