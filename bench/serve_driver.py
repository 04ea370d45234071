"""Traffic of kind ``serve_openloop``: independent users calling the
recommender at a fixed Poisson rate.

Set-up generates the ratings (for the seen-item table only: no fit runs),
makes user and item factors on the device from the seed, starts a
``ServingEngine`` over them (which compiles one executable per bucket)
and warms every bucket.  The window replays a tape of ``rate x seconds``
requests (``openloop``) and times each from its due time.  Once it has
closed, every answer is compared with the plain top-k reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import openloop
import ratings as ratings_mod
import reference
from common import GcClock, key_seed


@functools.partial(jax.jit, static_argnames=("m", "n", "r"))
def _factors(key, m: int, n: int, r: int):
    ku, kw = jax.random.split(key)
    u = jax.random.normal(ku, (m, r), jnp.float32) / np.sqrt(r)
    w = jax.random.normal(kw, (n, r), jnp.float32)
    return u, w


class ServeRun:
    def __init__(self, cell, seed: int, phases, devices, seconds: float):
        from repro import obs
        from repro.serve.recommend import RecommendIndex, build_seen_table_coo
        from repro.serving import ServingEngine

        self.cell, self.seed, self.devices = cell, seed, devices
        cfg, tr = cell.config, cell.traffic
        d, sv = cfg["data"], cfg["serve"]
        m, n, r = d["users"], d["items"], cfg["fit"]["rank"]
        self.k = int(sv["k"])
        with phases("generate"):
            self.data = ratings_mod.from_config(cfg, seed)
            self.tape = openloop.make_tape(
                max(1, round(tr["rate_per_s"] * seconds)), tr["rate_per_s"],
                tr["size_min"], tr["size_max"], m, seed)
        with phases("ingest"):
            seen = build_seen_table_coo(self.data.train_rows,
                                        self.data.train_cols, m, n)
            headroom = int(sv["seen_capacity"]) - seen.shape[1]
            if headroom < 0:
                raise ValueError(
                    f"a user rated {seen.shape[1]} items (rounded), over "
                    f"the configuration's seen_capacity "
                    f"{sv['seen_capacity']}")
            key = jax.random.fold_in(jax.random.PRNGKey(key_seed(seed)), 2)
            self.u, self.w = _factors(key, m, n, r)
            index = RecommendIndex(self.u, self.w, jnp.asarray(seen))
            jax.block_until_ready(index)
        with phases("compile"):
            self.engine = ServingEngine(
                index, buckets=tuple(sv["buckets"]), k=self.k,
                exclude_seen=bool(sv["exclude_seen"]),
                seen_headroom=headroom, quant=sv.get("quant"))
        with phases("warmup"):
            rng = np.random.default_rng(seed)
            for size in (*sv["buckets"], tr["size_max"]):
                for _ in range(3):
                    self.engine.recommend(
                        rng.integers(0, m, size).astype(np.int32))
        self.compiles = obs.counter("serve_compiles_total").value
        obs.reset()          # the queue-wait histogram holds the window only

    def window(self, seconds: float, span) -> dict:
        with GcClock() as gc_clock:
            self.replay = openloop.replay(self.engine.submit, self.tape,
                                          span=span)
        rp = self.replay
        ok = np.isfinite(rp.latency)
        return {
            "attempted": len(rp.latency), "completed": int(ok.sum()),
            "failed": rp.failed, "window_s": rp.end - rp.start,
            "serve_p95_ms": openloop.percentile_ms(rp.latency, 95),
            "serve_p50_ms": openloop.percentile_ms(rp.latency, 50),
            "generator_lag_ms": {
                "median": float(np.median(rp.lag)) * 1e3,
                "max": float(np.max(rp.lag)) * 1e3,
                "behind_1ms": int(np.sum(rp.lag > 1e-3))},
            "gc_pauses": gc_clock.summary(),
            "offered_per_s": self.cell.traffic["rate_per_s"],
        }

    def release(self) -> None:
        self.engine.shutdown()
        self.engine = None

    def check(self) -> dict:
        return self.numbers("program")

    def numbers(self, mode: str = "program") -> dict:
        """Every answer due in the window against the reference top-k:
        ``score_err`` (worst |served score - reference score of the served
        item|) and ``shortfall`` (worst amount by which the item served at
        rank j scores below the reference's j-th best), both over the
        largest |score|, and ``unanswered`` requests.  ``mode="control"``
        puts the three-pass bf16 reference in the program's place."""

        d = self.cell.config["data"]
        seen = reference.seen_mask(self.data.train_rows, self.data.train_cols,
                                   d["users"], d["items"])
        ids = np.concatenate(self.tape.users)
        if mode == "control":
            scores, items, _, _ = reference.topk(self.u, self.w, seen, ids,
                                                 None, self.k, mode="high")
            unanswered = 0
        else:
            got = self.replay.answers
            unanswered = sum(a is None for a in got)
            keep = [i for i, a in enumerate(got) if a is not None]
            ids = (np.concatenate([self.tape.users[i] for i in keep])
                   if keep else np.zeros(0, np.int32))
            items = (np.concatenate([np.asarray(got[i][0]) for i in keep])
                     if keep else np.zeros((0, self.k), np.int32))
            scores = (np.concatenate([np.asarray(got[i][1]) for i in keep])
                      if keep else np.zeros((0, self.k), np.float32))
        if len(ids) == 0:
            return {"score_err": float("inf"), "shortfall": float("inf"),
                    "unanswered": float(unanswered)}
        top, _, at, scale = reference.topk(self.u, self.w, seen, ids,
                                           np.asarray(items, np.int32),
                                           self.k)
        with np.errstate(invalid="ignore"):
            err = np.where(np.isfinite(at), np.abs(scores - at), np.inf)
            short = np.where(np.isfinite(at), top - at, np.inf)
        return {"score_err": float(np.max(err)) / scale,
                "shortfall": float(np.max(short)) / scale,
                "unanswered": float(unanswered)}
