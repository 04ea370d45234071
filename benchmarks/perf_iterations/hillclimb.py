"""§Perf hillclimb harness: re-lower a dry-run cell under a candidate
change, re-derive the roofline terms, and log hypothesis → before → after.

Each iteration is a named variant of ``lower_cell`` knobs (mesh-config /
ctx / model-config overrides).  Results append to
results/hillclimb.jsonl; EXPERIMENTS.md §Perf narrates them.

Run (one cell per process — jax device count locks at init):
    PYTHONPATH=src python -m benchmarks.perf_iterations.hillclimb \
        --cell deepseek-train --variant baseline
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses as dc
import json
import sys

import jax
import jax.numpy as jnp


def measure(arch, shape, multi_pod=False, mesh_overrides=None,
            ctx_overrides=None, cfg_overrides=None, microbatch=8):
    from repro.launch import dryrun as D
    from repro.launch.mesh import (make_production_mesh, multi_pod_config,
                                   single_pod_config)
    from repro.config import get_model_config, get_shape

    cfg = dc.replace(get_model_config(arch), param_dtype="bfloat16",
                     **(cfg_overrides or {}))
    sh = get_shape(shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_cfg = (multi_pod_config if multi_pod else single_pod_config)(
        **(mesh_overrides or {}))
    ctx = D.build_ctx(cfg, mesh, mesh_cfg)
    if ctx_overrides:
        ctx = dc.replace(ctx, **ctx_overrides)

    full = D._build_lowered(cfg, sh, mesh, mesh_cfg, ctx,
                            microbatch=microbatch).compile()
    mem = full.memory_analysis()
    pctx = dc.replace(ctx, scan_layers=False, remat=False,
                      attn_impl=ctx.attn_impl + "!"
                      if ctx.attn_impl == "flashref" else ctx.attn_impl)
    cs = []
    for k in (1, 2):
        pcfg = dc.replace(cfg, **D._probe_layers(cfg, k))
        cs.append(D._costs(D._build_lowered(pcfg, sh, mesh, mesh_cfg, pctx,
                                            microbatch=0).compile()))
    n = D._n_units(cfg)
    agg = {
        "flops": cs[0]["flops"] + (n - 1) * max(cs[1]["flops"] - cs[0]["flops"], 0),
        "bytes": cs[0]["bytes"] + (n - 1) * max(cs[1]["bytes"] - cs[0]["bytes"], 0),
    }
    kinds = set(cs[0]["coll"]) | set(cs[1]["coll"])
    coll = {k: cs[0]["coll"].get(k, 0.0) + (n - 1) * max(
        cs[1]["coll"].get(k, 0.0) - cs[0]["coll"].get(k, 0.0), 0.0)
        for k in kinds}
    from repro.roofline.analysis import roofline_terms

    terms = roofline_terms(agg["flops"], agg["bytes"], sum(coll.values()))
    return {
        "temp_gb": mem.temp_size_in_bytes / 1e9,
        "flops": agg["flops"], "bytes": agg["bytes"],
        "collective_bytes": sum(coll.values()), "collectives": coll,
        **terms,
    }


CELLS = {
    # most collective-bound candidate: EP MoE (psum per layer)
    "deepseek-train": dict(arch="deepseek-v2-lite-16b", shape="train_4k"),
    # worst roofline fraction candidate: memory-bound MHA decode
    "qwen-decode": dict(arch="qwen1.5-32b", shape="decode_32k"),
    # other bases used by iterations
    "granite-train": dict(arch="granite-34b", shape="train_4k"),
    "gemma2-train": dict(arch="gemma2-2b", shape="train_4k"),
}

VARIANTS = {
    "baseline": {},
    # decode: serving has no optimizer state — keep params TP-resident
    # instead of FSDP-sharded, killing the per-step weight all-gather
    "serve-fsdp-off": dict(mesh_overrides={"fsdp": False}),
    # qwen-decode: fp8 KV cache halves the cache traffic (memory term)
    "fp8-cache": dict(ctx_overrides={"cache_dtype": jnp.float8_e4m3fn}),
    "fp8-cache-fsdp-off": dict(
        ctx_overrides={"cache_dtype": jnp.float8_e4m3fn},
        mesh_overrides={"fsdp": False}),
    # qwen-decode: multi-pod doubles aggregate HBM bandwidth
    "pod2": dict(multi_pod=True),
    "pod2-fp8-fsdp-off": dict(
        multi_pod=True, ctx_overrides={"cache_dtype": jnp.float8_e4m3fn},
        mesh_overrides={"fsdp": False}),
    # deepseek-train: all-to-all expert dispatch (sequence sharded over the
    # EP axis, fixed-capacity a2a buffers) instead of replicate+psum
    "moe-a2a": dict(ctx_overrides={"moe_impl": "a2a"}),
    # trains: no-remat trade (memory for flops)
    "no-remat": dict(mesh_overrides={"remat": "none"}),
    # trains: microbatch sweep
    "micro16": dict(microbatch=16),
    "micro4": dict(microbatch=4),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=list(CELLS))
    ap.add_argument("--variant", required=True, choices=list(VARIANTS))
    ap.add_argument("--out", default="results/hillclimb.jsonl")
    args = ap.parse_args()

    spec = dict(CELLS[args.cell])
    spec.update(VARIANTS[args.variant])
    res = measure(**spec)
    rec = {"cell": args.cell, "variant": args.variant, **res}
    print(json.dumps(rec))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
