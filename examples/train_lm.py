"""End-to-end LM training driver: ~100M-param model, a few hundred steps.

Uses the full production stack at CPU scale: any --arch's family with a
rescaled ~100M config (or the arch's smoke config with --smoke), the
synthetic token pipeline, AdamW + cosine schedule, gradient clipping,
checkpoint/restart (resumes automatically if --ckpt dir has state).

    PYTHONPATH=src python examples/train_lm.py --steps 200
    PYTHONPATH=src python examples/train_lm.py --arch mamba2-780m --smoke
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.config import ARCHS, TrainConfig, get_model_config, get_smoke_config
from repro.data import LMTokenPipeline
from repro.models import build_model, param_count
from repro.models.api import Ctx
from repro.optim import make_optimizer
from repro.optim.optimizers import apply_updates


def config_100m(arch: str):
    cfg = get_model_config(arch)
    if cfg.family in ("dense", "vlm"):
        return dataclasses.replace(
            cfg, family="dense", num_layers=8, d_model=640, num_heads=10,
            num_kv_heads=2, head_dim=64, d_ff=1792, vocab_size=32000,
            local_global_pattern=0, sliding_window=0, num_patch_tokens=0,
            param_dtype="float32")
    return get_smoke_config(arch)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-20b", choices=list(ARCHS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config")
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else config_100m(args.arch)
    model = build_model(cfg, Ctx(attn_impl="ref", cache_dtype=jnp.float32))
    print(f"{args.arch} ({cfg.family}): {param_count(cfg)/1e6:.1f}M params, "
          f"{args.steps} steps @ batch {args.batch} x seq {args.seq}")

    tc = TrainConfig(learning_rate=args.lr, warmup_steps=20,
                     total_steps=args.steps)
    opt = make_optimizer(tc)
    pipe = LMTokenPipeline(cfg.vocab_size, args.seq, args.batch, seed=0)

    @jax.jit
    def step_fn(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(model.loss)(
            params, {"tokens": tokens, "targets": targets})
        updates, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, loss

    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    mgr = CheckpointManager(args.ckpt)
    start = 0
    restored = mgr.restore(jax.eval_shape(
        lambda: {"params": params, "opt": opt_state}))
    if restored:
        start, tree = restored
        params, opt_state = tree["params"], tree["opt"]
        print(f"resumed from checkpoint at step {start}")

    t0 = time.time()
    for i in range(start, args.steps):
        tok, tgt = pipe.batch_at(i)
        params, opt_state, loss = step_fn(params, opt_state,
                                          jnp.asarray(tok), jnp.asarray(tgt))
        if (i + 1) % 10 == 0 or i == start:
            tps = (i + 1 - start) * args.batch * args.seq / (time.time() - t0)
            print(f"step {i+1:>5d}  loss {float(loss):.4f}  ({tps:,.0f} tok/s)")
        if (i + 1) % args.ckpt_every == 0:
            mgr.save(i + 1, {"params": params, "opt": opt_state})
    mgr.save(args.steps, {"params": params, "opt": opt_state})
    print("done; final checkpoint saved")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
