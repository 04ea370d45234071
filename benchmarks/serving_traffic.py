"""Serving traffic bench: Poisson arrivals against the AOT bucket engine
vs the jit-on-first-call ``RecommendService`` baseline.

One request schedule — exponential inter-arrival times at ``--rate`` and
mixed request sizes (log-uniform across the bucket ladder) — is replayed
twice through the same queue discipline (``repro.serving.queue``'s
worker):

* **baseline**: ``RecommendService`` behind a dispatcher thread — every
  request pads to one fixed batch and the first request pays the jit
  compile *inside* its latency (exactly what a naive deployment ships);
* **engine**: ``ServingEngine`` — requests submitted at arrival, every
  bucket compiled before the first request arrived.

Per-request latency is completion − submit (stamped by a done-callback,
so queue wait counts — it's what a client sees).  The payload reports
p50/p99/mean latency, achieved QPS, and compile counts for both phases;
the envelope ``metrics`` key snapshots the **engine** phase, so the
``serving-smoke`` CI job and the ``obs_report.py`` tripwire can pin
``serve_compiles_total == len(buckets)`` — zero serve-time compiles.

``--quant`` switches the comparison to the int8 factor cache
(DESIGN.md §16): the same tape replays through an f32 ``ServingEngine``
and then a ``ServingEngine(quant="int8")``, and the payload adds the
int8 story — ``index_bytes`` (f32 vs int8 and their ratio, also stamped
as the ``serve_index_bytes`` gauges in ``metrics``), per-request answer
``overlap_at_k`` between the two phases (**asserted ≥ 0.99 in-bench** —
the run fails, not just reports, when quantization degrades the
answers), and ``method_sweep_ms`` — the full-query timing of each
``kernels/quant`` scoring method at this geometry, which is exactly the
table ``kernels/quant/autotune.py`` resolves ``method=None`` from once
this file is committed as ``benchmarks/BENCH_quant.json``.  The default
``--k`` rises to 100 under ``--quant``: the int8 cache is a retrieval
stage (serve a candidate set, not the final ranking), and the overlap
gate is calibrated to that contract.

    PYTHONPATH=src python benchmarks/serving_traffic.py \
        [--users 4000] [--items 2000] [--rank 16] [--density 0.02] \
        [--buckets 16,64,256] [--k 10] [--requests 200] [--rate 100] \
        [--seed 0] [--baseline-batch 256] [--quant] [--quant-method M] \
        [--json PATH]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.serve.recommend import (RecommendIndex, RecommendService,
                                   build_seen_table)
from repro.serving import ServingEngine
from repro.serving.queue import ServeWorker

try:                                   # package mode (python -m benchmarks.x)
    from benchmarks.run import emit_json
except ImportError:                    # script mode (python benchmarks/x.py)
    from run import emit_json


def _random_index(args) -> RecommendIndex:
    rng = np.random.default_rng(args.seed)
    u = jnp.asarray(rng.normal(size=(args.users, args.rank)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(args.items, args.rank)), jnp.float32)
    mask = (rng.random((args.users, args.items)) < args.density)
    seen = jnp.asarray(build_seen_table(mask.astype(np.float32), args.items))
    return RecommendIndex(u, w, seen)


def _make_schedule(args, buckets):
    """One shared traffic tape: (inter-arrival seconds, user-id arrays)."""

    rng = np.random.default_rng(args.seed + 1)
    gaps = rng.exponential(1.0 / args.rate, size=args.requests)
    # log-uniform sizes spanning the ladder: plenty of small requests,
    # some full-bucket ones, a few oversize multi-chunk ones
    log_hi = np.log(buckets[-1] * 1.25)
    sizes = np.exp(rng.uniform(0.0, log_hi, size=args.requests))
    sizes = np.maximum(1, sizes.astype(int))
    reqs = [rng.integers(0, args.users, size=s).astype(np.int32)
            for s in sizes]
    return gaps, reqs


def _drive(submit, gaps, reqs):
    """Replay the tape: submit at arrival, stamp completion via callback.

    Returns (per-request latency seconds, achieved QPS, per-request
    recommended-item arrays) — the answers let the ``--quant`` arm score
    overlap@k between two phases of the same tape."""

    n = len(reqs)
    t_done = [0.0] * n
    t_sub = [0.0] * n
    futures = []
    for i in range(n):
        time.sleep(gaps[i])
        t_sub[i] = time.perf_counter()
        f = submit(reqs[i])
        f.add_done_callback(
            lambda f, i=i: t_done.__setitem__(i, time.perf_counter())
        )
        futures.append(f)
    answers = [np.asarray(f.result()[0]) for f in futures]
    lats = np.array([d - s for s, d in zip(t_sub, t_done)])
    window = max(t_done) - t_sub[0]
    qps = n / window if window > 0 else 0.0
    return lats, qps, answers


def _mean_overlap(answers_a, answers_b, k: int) -> float:
    """Mean per-user overlap@k between two phases' answers on one tape."""

    per_user = []
    for a, b in zip(answers_a, answers_b):
        for row_a, row_b in zip(a, b):
            per_user.append(len(set(row_a) & set(row_b)) / k)
    return float(np.mean(per_user))


def _summ(lats, qps, compiles):
    return {
        "p50_ms": float(np.percentile(lats, 50) * 1e3),
        "p99_ms": float(np.percentile(lats, 99) * 1e3),
        "mean_ms": float(lats.mean() * 1e3),
        "max_ms": float(lats.max() * 1e3),
        "qps": float(qps),
        "compiles": float(compiles),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, default=4000)
    ap.add_argument("--items", type=int, default=2000)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--density", type=float, default=0.02)
    ap.add_argument("--buckets", type=str, default="16,64,256")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--baseline-batch", type=int, default=256)
    ap.add_argument("--quant", action="store_true",
                    help="compare f32 vs int8 engines on the same tape")
    ap.add_argument("--quant-method", type=str, default=None,
                    choices=("fused", "dequant"),
                    help="int8 scoring method (default: per-backend autotune)")
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args()
    if args.quant and args.k == ap.get_default("k"):
        args.k = 100          # retrieval-stage contract (module docstring)
    buckets = tuple(int(b) for b in args.buckets.split(","))

    index = _random_index(args)
    gaps, reqs = _make_schedule(args, buckets)
    total_users = sum(len(r) for r in reqs)
    print(f"index: {args.users} users x {args.items} items rank {args.rank} "
          f"(backend={jax.default_backend()}); traffic: {args.requests} "
          f"requests, {total_users} users, rate {args.rate}/s, "
          f"sizes 1..{max(len(r) for r in reqs)}")

    baseline = None
    if not args.quant:
        # ---- baseline: jit-on-first-call service behind the queue ----- #
        obs.reset()
        service = RecommendService(index, batch=args.baseline_batch,
                                   k=args.k)
        worker = ServeWorker(lambda req: service.recommend(req.user_ids),
                             name="baseline-service")
        base_lats, base_qps, _ = _drive(worker.submit, gaps, reqs)
        worker.shutdown()
        # compiles the baseline paid in-band (= compile-carrying batches)
        base_compiles = obs.counter("serve_warmup_batches_total").value
        baseline = _summ(base_lats, base_qps, base_compiles)
        print(f"baseline (batch={args.baseline_batch}, compile in-band): "
              f"p50={baseline['p50_ms']:.2f}ms "
              f"p99={baseline['p99_ms']:.2f}ms "
              f"qps={baseline['qps']:.1f} compiles={base_compiles:.0f}")

    # ---- engine: AOT buckets, compiled before the first arrival ------- #
    obs.reset()                 # envelope metrics == engine phase only
    t0 = time.perf_counter()
    eng = ServingEngine(index, buckets=buckets, k=args.k)
    startup_s = time.perf_counter() - t0
    eng_lats, eng_qps, eng_answers = _drive(eng.submit, gaps, reqs)
    eng.drain()
    engine = _summ(eng_lats, eng_qps,
                   obs.counter("serve_compiles_total").value)
    engine["startup_compile_s"] = float(startup_s)
    em = eng.metrics()
    print(f"engine (buckets={buckets}, startup compile {startup_s:.2f}s): "
          f"p50={engine['p50_ms']:.2f}ms p99={engine['p99_ms']:.2f}ms "
          f"qps={engine['qps']:.1f} compiles={engine['compiles']:.0f} "
          f"(all at startup)")
    if baseline is not None:
        print(f"engine p99 / baseline p99 = "
              f"{engine['p99_ms'] / baseline['p99_ms']:.3f}")
    eng.shutdown()

    # ---- quant: the int8 engine replays the identical tape ------------ #
    quant = overlap = index_bytes = sweep = None
    if args.quant:
        from repro.kernels.quant import METHODS, resolve_method
        from repro.serve.quant import index_nbytes, quantize_index
        from repro.serve.recommend import recommend_topk

        qidx = quantize_index(index)
        index_bytes = {
            "f32": index_nbytes(index),
            "int8": index_nbytes(qidx),
            "ratio": index_nbytes(qidx) / index_nbytes(index),
        }
        # full-query method sweep at this geometry — the autotune table
        # (kernels/quant/autotune.py) reads this key from the committed
        # BENCH_quant.json for the envelope's backend
        sweep = {}
        uids = jnp.asarray(
            np.random.default_rng(args.seed + 2)
            .integers(0, args.users, buckets[-1]).astype(np.int32))
        for m in METHODS:
            fn = lambda: recommend_topk(qidx, uids, k=args.k, method=m)
            fn()[0].block_until_ready()          # compile outside timing
            ts = []
            for _ in range(30):
                t1 = time.perf_counter()
                fn()[0].block_until_ready()
                ts.append(time.perf_counter() - t1)
            sweep[m] = float(np.median(ts) * 1e3)
        method = resolve_method(args.quant_method)
        print("method sweep (full query, ms): "
              + ", ".join(f"{m}={v:.3f}" for m, v in sweep.items())
              + f"; serving method={method}")

        obs.reset()             # envelope metrics == the int8 phase
        t0 = time.perf_counter()
        qeng = ServingEngine(index, buckets=buckets, k=args.k,
                             quant="int8", quant_method=method)
        q_startup_s = time.perf_counter() - t0
        q_lats, q_qps, q_answers = _drive(qeng.submit, gaps, reqs)
        qeng.drain()
        quant = _summ(q_lats, q_qps,
                      obs.counter("serve_compiles_total").value)
        quant["startup_compile_s"] = float(q_startup_s)
        quant["method"] = method
        qeng.shutdown()

        overlap = _mean_overlap(eng_answers, q_answers, args.k)
        print(f"quant engine (int8, {method}): "
              f"p50={quant['p50_ms']:.2f}ms p99={quant['p99_ms']:.2f}ms "
              f"qps={quant['qps']:.1f}; "
              f"index bytes {index_bytes['int8']}/{index_bytes['f32']} "
              f"= {index_bytes['ratio']:.3f}x; overlap@{args.k}={overlap:.4f}")
        # the accuracy gate IS the bench: a quant run that degrades the
        # answers must fail loudly, never land as a green JSON
        assert overlap >= 0.99, (
            f"int8 overlap@{args.k} = {overlap:.4f} < 0.99 accuracy gate"
        )

    if args.json:
        payload = dict(
            engine=engine,
            engine_metrics={"queue_wait": em["queue_wait"],
                            "buckets": {str(b): s for b, s in
                                        em["buckets"].items()},
                            "refreshes": em["refreshes"]},
        )
        if baseline is not None:
            payload["baseline"] = baseline
        if args.quant:
            payload.update(
                quant=quant,
                overlap_at_k=overlap,
                index_bytes=index_bytes,
                method_sweep_ms=sweep,
            )
        emit_json(args.json, "serving_traffic",
                  {"users": args.users, "items": args.items,
                   "rank": args.rank, "density": args.density,
                   "buckets": list(buckets), "k": args.k,
                   "requests": args.requests, "rate": args.rate,
                   "seed": args.seed,
                   "baseline_batch": args.baseline_batch,
                   "quant": bool(args.quant)},
                  **payload)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
