"""Fit -> serve smoke run of the whole system on one TPU chip.

Runs the path a MovieLens user runs, through the public entry points, at
MovieLens-1M shape: 6,040 users x 3,706 items, 1,000,000 ratings with Zipf
item popularity (exponent 0.8) and Zipf user activity (0.6), split 80/20,
generated from ``--seed`` by ``repro.data.movielens_proxy``.  Rank 32 on a
4x4 block grid, sparse layout, mean-centred.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips of one host

One chip, four phases in one process, each printing one JSON line:

1. ``ingest``   ``CompletionProblem.from_dataset``;
2. ``train``    ``Trainer.fit`` with ``Wave`` rounds on the XLA segment
   path, then ``kernel_fit``: a short fit with ``use_kernel=True`` on an
   8x8 grid (where the segment kernel's resident layout fits VMEM) checked
   against the XLA path;
3. ``serve_f32`` / ``serve_int8``  ``FitResult.to_engine`` answering
   mixed-size requests, checked against a float32 numpy top-k built on the
   host from ``to_recommend_index()``;
4. ``ingest_while_serving``  ``problem.append`` -> ``trainer.refit`` ->
   ``engine.refresh`` while requests are in flight.

``--chips 4`` runs only what exists across chips, and what it is compared
with: ``Gossip`` on a 2x2 ``MeshPlan`` (2x2 blocks per chip) against
``FullGD`` on one chip, and a ``ServingEngine`` whose catalog is sharded
over the four chips against the one-chip engine.

Every check that fails raises, and the run exits non-zero.  The last line
of standard output is ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}``.  Without a TPU the script says so on
standard error and exits 1 before any work.  It starts no other process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro import obs  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.config import GossipMCConfig  # noqa: E402
from repro.core import waves  # noqa: E402
from repro.data import movielens_proxy  # noqa: E402
from repro.kernels.sddmm.autotune import resolve_chunk  # noqa: E402
from repro.mc import (CompletionProblem, FullGD, Gossip,  # noqa: E402
                      Incremental, Trainer, Wave)
from repro.mesh import MeshPlan, build_mesh  # noqa: E402
from repro.serve.recommend import _SEEN_PAD_QUANTUM  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Size:
    """What one run does; ``ML1M`` is the deployment the script runs."""

    users: int
    items: int
    ratings: int
    grid: int           # p = q of the fit, the refit and the gossip phase
    kernel_grid: int    # p = q of the use_kernel=True fit
    rank: int
    rounds: int         # Wave rounds of the main fit
    kernel_rounds: int
    refit_rounds: int
    gossip_rounds: int
    appends: int
    requests: int
    max_request: int
    buckets: tuple
    k: int


ML1M = Size(users=6040, items=3706, ratings=1_000_000, grid=4, kernel_grid=8,
            rank=32, rounds=300, kernel_rounds=3, refit_rounds=20,
            gossip_rounds=40, appends=10_000, requests=48, max_request=1500,
            buckets=(16, 64, 256, 1024), k=100)

# benchmarks/table3_rmse.py fits this data with FullGD at rho=1e3,
# lam=1e-6, a=2e-4, b=5e-7 over 800 rounds.  A Wave fit of a few hundred
# rounds needs a larger step: a=1e-3 with rho=1e2 keeps the per-pair
# consensus step gamma*2*rho at 0.2, well below the 1 at which it
# oscillates.
HPARAMS = dict(rho=1e2, lam=1e-6, a=1e-3, b=5e-7)

# kernel vs XLA gradients and costs: the same f32 arithmetic, but the
# kernel sums a segment as a difference of running prefix sums
KERNEL_RTOL = 1e-4
# f32 engine vs the numpy oracle, relative to the largest |score|
F32_RTOL = 1e-5
INT8_MIN_OVERLAP = 0.99
# Gossip on four chips vs FullGD on one: the same rounds, summed in
# another order on each device
GOSSIP_RTOL = 1e-4
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer TPU chips than asked for."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(chips: int) -> None:
    """Raise :class:`NoChip` unless JAX's default backend is a TPU with at
    least ``chips`` devices: the smoke never carries on on the CPU."""

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (default devices: "
                     f"{devs[0].platform} x{len(devs)})")
    if len(devs) < chips:
        raise NoChip(f"asked for {chips} chips, JAX sees {len(devs)}")


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


class CompileClock:
    """Seconds XLA spent compiling, or loading from the persistent cache,
    and cache hits, summed from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)


class Phase:
    """Times one phase and prints its JSON line on success."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock
        self.fields: dict = {}

    def __enter__(self) -> "Phase":
        self._t0 = time.perf_counter()
        self._c0 = self.clock.seconds
        self._h0 = self.clock.cache_hits
        return self

    def __exit__(self, exc_type, *_) -> None:
        if exc_type is not None:
            return
        line = {"phase": self.name,
                "seconds": time.perf_counter() - self._t0,
                "compile_seconds": self.clock.seconds - self._c0,
                "compile_cache_hits": self.clock.cache_hits - self._h0,
                **self.fields,
                "peak_bytes_in_use": peak_bytes()}
        print(json.dumps(line), flush=True)


def fallbacks(kernel: str) -> float:
    """Trace-time fallbacks of ``kernel`` counted so far (any reason)."""

    counters = obs.snapshot()["counters"]
    return sum(v for key, v in counters.items()
               if key.startswith("kernel_fallbacks_total{")
               and f"kernel={kernel}" in key)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------- #
# numpy reference
# ---------------------------------------------------------------------- #


def oracle_scores(u, w, seen, user_ids):
    """float32 numpy scores with each user's seen items at -inf."""

    s = np.concatenate([u[user_ids] @ w.T,
                        np.zeros((len(user_ids), 1), np.float32)], axis=1)
    s[np.arange(len(user_ids))[:, None], seen[user_ids]] = -np.inf
    return s[:, :-1]                       # pad id n lands in the dropped col


def compare_topk(items, scores, u, w, seen, user_ids, k):
    """(overlap@k, worst shortfall below the k-th oracle score, worst score
    error), the last two relative to the largest |oracle score|."""

    ref = oracle_scores(u, w, seen, user_ids)
    scale = float(np.abs(ref[np.isfinite(ref)]).max())
    kth = -np.partition(-ref, k - 1, axis=1)[:, k - 1]
    got = np.take_along_axis(ref, items, axis=1)
    shortfall = float(np.max(kth[:, None] - got)) / scale
    err = float(np.max(np.abs(scores - got))) / scale
    top = np.argpartition(-ref, k - 1, axis=1)[:, :k]
    hits = sum(len(set(a) & set(b)) for a, b in zip(items.tolist(),
                                                    top.tolist()))
    return hits / (k * len(user_ids)), shortfall, err


def same_topk(items, scores, ref_items, ref_scores, rtol):
    """Item ids equal wherever no neighbour's score ties within ``rtol``,
    and scores within ``rtol`` of the largest |score|."""

    atol = rtol * float(np.abs(ref_scores).max())
    if np.max(np.abs(scores - ref_scores)) > atol:
        return False
    close = np.abs(np.diff(ref_scores, axis=1)) <= atol
    tied = np.zeros(ref_scores.shape, bool)
    tied[:, 1:] |= close
    tied[:, :-1] |= close
    return bool(np.array_equal(items[~tied], ref_items[~tied]))


def make_requests(size: Size, rng):
    """Mixed request sizes, log-uniform on [1, max_request]."""

    lens = np.exp(rng.uniform(0.0, math.log(size.max_request),
                              size.requests)).astype(int)
    return [rng.integers(0, size.users, n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------- #
# one chip
# ---------------------------------------------------------------------- #


def deployment(size: Size, seed: int):
    """The ratings, the appends that arrive while serving, and the
    per-block headroom the store pre-allocates for them.

    Returns ``(ds, app, keep, headroom)``: ``app`` is a seeded share of the
    held-out ratings as (rows, cols, vals), ``keep`` indexes the held-out
    ratings that stay held out, and ``headroom`` is the most appends any
    block of the ``size.grid`` grid receives."""

    rng = np.random.default_rng(seed)
    ds = movielens_proxy(size.users, size.items, size.ratings, seed=seed)
    pick = rng.choice(len(ds.test_vals), size.appends, replace=False)
    keep = np.setdiff1d(np.arange(len(ds.test_vals)), pick)
    app = (ds.test_rows[pick], ds.test_cols[pick], ds.test_vals[pick])
    g = size.grid
    mb, nb = -(-size.users // g), -(-size.items // g)
    per_block = np.bincount(app[0] // mb * g + app[1] // nb, minlength=g * g)
    return ds, app, keep, int(per_block.max())


def run_one_chip(size: Size, seed: int, clock: CompileClock) -> None:
    g = size.grid

    with Phase("ingest", clock) as ph:
        ds, app, keep, headroom = deployment(size, seed)
        problem = CompletionProblem.from_dataset(
            ds, g, g, size.rank, layout="sparse", mean_center=True,
            headroom=headroom)
        jax.block_until_ready(problem.data)
        nnz = np.asarray(problem.data.nnz)
        ph.fields.update(
            users=size.users, items=size.items,
            train_ratings=int(nnz.sum()), test_ratings=len(ds.test_vals),
            grid=[g, g], block=[problem.spec.mb, problem.spec.nb],
            capacity=problem.data.capacity, headroom=headroom,
            padding_share=1.0 - float(nnz.sum()) / (g * g
                                                    * problem.data.capacity))

    with Phase("train", clock) as ph:
        spec = problem.spec
        cfg = GossipMCConfig(m=spec.m, n=spec.n, p=g, q=g, rank=size.rank,
                             **HPARAMS)
        trainer = Trainer(cfg)
        result = trainer.fit(problem, Wave(num_rounds=size.rounds), seed=seed)
        rmse = result.rmse()
        baseline = float(np.sqrt(np.mean((ds.test_vals - problem.mu) ** 2)))
        check(math.isfinite(rmse) and rmse < baseline,
              f"held-out RMSE {rmse} is not below the train-mean "
              f"baseline {baseline}")
        ph.fields.update(
            schedule="wave", rounds=size.rounds, hparams=HPARAMS,
            gradient_path={"use_kernel": False, "method": "segment",
                           "chunk": resolve_chunk(None)},
            fit_seconds=result.wall_time, final_cost=result.final_cost,
            rmse=rmse, train_mean_rmse=baseline)

    with Phase("kernel_fit", clock) as ph:
        ph.fields.update(kernel_fit(ds, size, seed))

    index = result.to_recommend_index()
    u, w = np.asarray(index.u), np.asarray(index.w)
    seen = np.asarray(index.seen)
    requests = make_requests(size, np.random.default_rng(seed + 1))
    app_users = np.bincount(app[0], minlength=size.users)
    seen_headroom = round_up(int(app_users.max()), _SEEN_PAD_QUANTUM)
    engines = {}
    for quant in (None, "int8"):
        name = "serve_int8" if quant else "serve_f32"
        with Phase(name, clock) as ph:
            before = obs.counter("serve_compiles_total").value
            fb0 = fallbacks("quant_fused")
            t0 = time.perf_counter()
            engine = result.to_engine(buckets=size.buckets, k=size.k,
                                      quant=quant, seen_headroom=seen_headroom)
            startup = time.perf_counter() - t0
            engines[quant] = engine
            t0 = time.perf_counter()
            answers = engine.recommend_many(requests)
            answer = time.perf_counter() - t0
            compiles = obs.counter("serve_compiles_total").value - before
            check(compiles == len(size.buckets),
                  f"{name}: {compiles} compiles for {len(size.buckets)} "
                  f"buckets")
            items = np.concatenate([a[0] for a in answers])
            scores = np.concatenate([a[1] for a in answers])
            overlap, shortfall, err = compare_topk(
                items, scores, u, w, seen, np.concatenate(requests), size.k)
            if quant:
                check(overlap >= INT8_MIN_OVERLAP,
                      f"int8 overlap@{size.k} {overlap} < {INT8_MIN_OVERLAP}")
                path = {"scores": "int8", "method": engine.quant_method,
                        "fallbacks": fallbacks("quant_fused") - fb0}
                check(path["fallbacks"] == 0
                      or jax.default_backend() != "tpu",
                      f"the int8 scoring kernel fell back: {path}")
            else:
                check(shortfall <= F32_RTOL and err <= F32_RTOL,
                      f"f32 top-{size.k} off the oracle: shortfall "
                      f"{shortfall}, score error {err} (limit {F32_RTOL})")
                path = {"scores": "f32", "precision": "highest"}
            ph.fields.update(
                startup_seconds=startup, answer_seconds=answer,
                compiles=compiles, buckets=list(size.buckets), k=size.k,
                requests=len(requests), users=int(len(items)),
                scoring_path=path, overlap=overlap, shortfall=shortfall,
                score_error=err)
    engines["int8"].shutdown()

    with Phase("ingest_while_serving", clock) as ph:
        engine = engines[None]
        compiles0 = obs.counter("serve_compiles_total").value
        inflight = [engine.submit(r) for r in requests]
        t0 = time.perf_counter()
        fresh = problem.append(*app)
        jax.block_until_ready(fresh.data)
        t_append = time.perf_counter() - t0
        refit = trainer.refit(result, fresh,
                              Incremental(num_rounds=size.refit_rounds))
        t0 = time.perf_counter()
        engine.refresh(refit)
        t_refresh = time.perf_counter() - t0
        served = sum(len(f.result()[0]) for f in inflight)
        rmse = refit.rmse(ds.test_rows[keep], ds.test_cols[keep],
                          ds.test_vals[keep])
        check(math.isfinite(rmse) and rmse < baseline,
              f"RMSE after refit {rmse} is not below {baseline}")
        # the refreshed engine answers from the refit, and no user is
        # recommended an item they just rated
        index = refit.to_recommend_index()
        u2, w2, seen2 = (np.asarray(index.u), np.asarray(index.w),
                         np.asarray(index.seen))
        users = np.unique(app[0])[: size.buckets[-1]].astype(np.int32)
        items, scores = engine.recommend(users)
        overlap, shortfall, err = compare_topk(items, scores, u2, w2, seen2,
                                               users, size.k)
        check(shortfall <= F32_RTOL and err <= F32_RTOL,
              f"refreshed engine off the oracle: shortfall {shortfall}, "
              f"score error {err}")
        rated = set(zip(app[0].tolist(), app[1].tolist()))
        check(not any((int(uid), int(it)) in rated
                      for uid, row in zip(users, items) for it in row),
              "an appended rating was recommended back to its user")
        check(obs.counter("serve_compiles_total").value == compiles0,
              "the refresh recompiled a bucket")
        ph.fields.update(
            appended=size.appends, append_seconds=t_append,
            refit_rounds=size.refit_rounds, refit_seconds=refit.wall_time,
            refresh_seconds=t_refresh, served_in_flight=served,
            rmse_after_refit=rmse, overlap=overlap)
        engine.shutdown()


def kernel_fit(ds, size: Size, seed: int) -> dict:
    """A short fit on the segment kernel against the same fit on the XLA
    path, on a grid where the kernel's resident layout fits VMEM."""

    g = size.kernel_grid
    problem = CompletionProblem.from_dataset(ds, g, g, size.rank,
                                             layout="sparse",
                                             mean_center=True)
    spec = problem.spec
    cfg = GossipMCConfig(m=spec.m, n=spec.n, p=g, q=g, rank=size.rank,
                         **HPARAMS)
    sched = Wave(num_rounds=size.kernel_rounds)
    kproblem = problem.with_engine(use_kernel=True)
    ref = Trainer(cfg).fit(problem, sched, seed=seed)
    fb0 = fallbacks("sddmm_segment")
    res = Trainer(cfg).fit(kproblem, sched, seed=seed)
    grads = [p.full_gradients(ref.state, rho=cfg.rho, lam=cfg.lam)
             for p in (problem, kproblem)]
    fb = fallbacks("sddmm_segment") - fb0
    check(fb == 0, f"the segment kernel fell back to XLA {fb} times")
    hlo = waves.full_gradients.lower(
        kproblem.data, ref.state.U, ref.state.W, rho=cfg.rho, lam=cfg.lam,
        use_kernel=True).compile().as_text()
    mosaic = "tpu_custom_call" in hlo
    interpret = jax.default_backend() != "tpu"
    check(mosaic or interpret,
          "use_kernel=True compiled no Mosaic kernel on the TPU")
    scale = max(float(np.abs(np.asarray(x)).max()) for x in grads[0])
    grad_err = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                   for a, b in zip(*grads)) / scale
    cost_err = abs(res.final_cost - ref.final_cost) / abs(ref.final_cost)
    check(grad_err <= KERNEL_RTOL and cost_err <= KERNEL_RTOL,
          f"kernel vs XLA: gradient error {grad_err}, cost error "
          f"{cost_err} (limit {KERNEL_RTOL})")
    return dict(grid=[g, g], block=[spec.mb, spec.nb],
                capacity=problem.data.capacity, rounds=size.kernel_rounds,
                gradient_path={"use_kernel": True, "method": "segment",
                               "mosaic_in_hlo": mosaic,
                               "interpret": interpret, "fallbacks": fb},
                xla_fit_seconds=ref.wall_time, kernel_fit_seconds=res.wall_time,
                gradient_rel_error=grad_err, cost_rel_error=cost_err,
                tolerance=KERNEL_RTOL)


# ---------------------------------------------------------------------- #
# four chips
# ---------------------------------------------------------------------- #


def shard_devices(x) -> set:
    return {s.device for s in x.addressable_shards}


def run_four_chips(size: Size, seed: int, clock: CompileClock) -> None:
    g = size.grid
    ds = movielens_proxy(size.users, size.items, size.ratings, seed=seed)
    plan = MeshPlan.build(g, g, mesh=build_mesh((2, 2), ("data", "model")))

    with Phase("gossip_vs_fullgd", clock) as ph:
        one = CompletionProblem.from_dataset(ds, g, g, size.rank,
                                             layout="sparse",
                                             mean_center=True)
        spec = one.spec
        cfg = GossipMCConfig(m=spec.m, n=spec.n, p=g, q=g, rank=size.rank,
                             **HPARAMS)
        ref = Trainer(cfg).fit(one, FullGD(num_rounds=size.gossip_rounds),
                               seed=seed)
        four = CompletionProblem.from_dataset(ds, g, g, size.rank,
                                              layout="sparse",
                                              mean_center=True, mesh=plan)
        res = Trainer(cfg).fit(four, Gossip(num_rounds=size.gossip_rounds,
                                            plan=plan), seed=seed)
        placed = {
            "entries": len(shard_devices(four.data.entries.vals)),
            "U": len(shard_devices(res.state.U)),
            "W": len(shard_devices(res.state.W)),
        }
        check(all(n == 4 for n in placed.values()),
              f"gossip state is not spread over 4 devices: {placed}")
        errs = {
            name: float(np.abs(np.asarray(a) - np.asarray(b)).max())
            / float(np.abs(np.asarray(b)).max())
            for name, a, b in (("U", res.state.U, ref.state.U),
                               ("W", res.state.W, ref.state.W))
        }
        cost_err = abs(res.final_cost - ref.final_cost) / abs(ref.final_cost)
        check(max(errs.values()) <= GOSSIP_RTOL and cost_err <= GOSSIP_RTOL,
              f"gossip vs FullGD: factor errors {errs}, cost error "
              f"{cost_err} (limit {GOSSIP_RTOL})")
        ph.fields.update(
            grid=[g, g], mesh=[2, 2], rounds=size.gossip_rounds,
            devices_holding=placed, fullgd_seconds=ref.wall_time,
            gossip_seconds=res.wall_time, factor_rel_error=errs,
            cost_rel_error=cost_err, tolerance=GOSSIP_RTOL)

    with Phase("sharded_serving", clock) as ph:
        index = jax.device_put(res.to_recommend_index(), jax.devices()[0])
        requests = make_requests(size, np.random.default_rng(seed + 1))
        with ServingEngine(index, buckets=size.buckets, k=size.k) as single, \
                ServingEngine(index, buckets=size.buckets, k=size.k,
                              plan=plan) as sharded:
            a = single.recommend_many(requests)
            b = sharded.recommend_many(requests)
        items_1, scores_1 = (np.concatenate([x[i] for x in a])
                             for i in (0, 1))
        items_4, scores_4 = (np.concatenate([x[i] for x in b])
                             for i in (0, 1))
        check(same_topk(items_4, scores_4, items_1, scores_1, F32_RTOL),
              "sharded top-k differs from the one-device top-k")
        ph.fields.update(item_shards=plan.num_item_shards,
                         requests=len(requests), users=int(len(items_1)),
                         k=size.k, equal_items=float(np.mean(items_4
                                                             == items_1)))


def run(size: Size, chips: int, seed: int) -> None:
    with CompileClock() as clock:
        if chips == 4:
            run_four_chips(size, seed, clock)
        else:
            run_one_chip(size, seed, clock)
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the cross-chip phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    try:
        require_tpu(args.chips)
    except NoChip as e:
        print(f"chip_smoke: {e}; nothing was run", file=sys.stderr)
        return 1
    run(ML1M, args.chips, args.seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
