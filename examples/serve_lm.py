"""Serving driver: batched prefill + greedy decode with KV caches.

Exercises the real serve path (prefill -> cached decode steps) on a smoke
config; prints per-phase throughput.  The same Model/serve code lowers the
decode_32k / long_500k dry-run cells on the production mesh.

    PYTHONPATH=src python examples/serve_lm.py --arch gemma2-2b --tokens 32
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.config import ARCHS, get_smoke_config
from repro.models import build_model
from repro.models.api import Ctx
from repro.launch.lm_engine import ServeLoop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-20b", choices=list(ARCHS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    model = build_model(cfg, Ctx(attn_impl="ref", cache_dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0))

    extra = cfg.num_patch_tokens if cfg.family == "vlm" else 0
    max_len = args.prompt_len + extra + args.tokens + 1
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.encoder_seq_len, cfg.d_model))
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(3), (args.batch, cfg.num_patch_tokens, 1024))

    loop = ServeLoop(model, params, args.batch, max_len)
    t0 = time.time()
    out = loop.generate(batch, args.tokens)
    dt = time.time() - t0
    print(f"{args.arch}: generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s incl. prefill+compile)")
    print("first sequence:", out[0].tolist())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
