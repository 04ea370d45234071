"""Traffic of kind ``fit``: an operator fitting in chunks of C rounds.

Set-up generates the ratings, ingests them, makes the initial factors on
the device from the seed, and runs the first three chunks through the
same call the window makes:
``Trainer.fit(problem, Wave(num_rounds=C), state=..., key=...)``,
each chunk resuming from the last one's state with a key folded from the
seed.  Those three chunks compile and warm every program, and are what
the plain reference follows once the window has closed.

The window then runs further chunks until ``--seconds`` are spent; it
ends on a chunk boundary, so no partial round is lost, and
``rounds_per_s`` is every round it completed over the time to the end of
its last chunk.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

import ratings as ratings_mod
import reference
import workcount
from common import key_seed

STEPS = 3          # chunks set-up runs and the reference follows


@functools.partial(jax.jit, static_argnames=("shape_u", "shape_w"))
def _normal_pair(key, shape_u, shape_w):
    ku, kw = jax.random.split(key)
    sd = 1.0 / np.sqrt(shape_u[-1])
    return (sd * jax.random.normal(ku, shape_u, jnp.float32),
            sd * jax.random.normal(kw, shape_w, jnp.float32))


def init_factors(seed: int, p: int, q: int, mb: int, nb: int, r: int):
    """U, W ~ N(0, 1/r) on the device, in one jitted call."""

    key = jax.random.fold_in(jax.random.PRNGKey(key_seed(seed)), 1)
    return _normal_pair(key, (p, q, mb, r), (p, q, nb, r))


def chunk_key(seed: int, c: int):
    return jax.random.fold_in(jax.random.PRNGKey(key_seed(seed)), 1000 + c)


class FitRun:
    """One cell's fit: set-up, window, reference check."""

    def __init__(self, cell, seed: int, phases, devices, seconds: float):
        from repro.config import GossipMCConfig
        from repro.core.state import State
        from repro.mc import CompletionProblem, Trainer, Wave
        from repro.mc.problem import EngineOptions

        self.cell, self.seed, self.devices = cell, seed, devices
        cfg, tr = cell.config, cell.traffic
        fit = cfg["fit"]
        if tr["schedule"] != "wave":
            raise ValueError(f"fit schedule {tr['schedule']!r}: only "
                             f"'wave' has a driver")
        self.C = int(tr["chunk_rounds"])
        p, q = fit["grid"]
        r = fit["rank"]
        d = cfg["data"]
        with phases("generate"):
            self.data = ratings_mod.from_config(cfg, seed)
        rows, cols, vals = (self.data.train_rows, self.data.train_cols,
                            self.data.train_vals)
        self.nnz = ratings_mod.block_nnz(rows, cols, d["users"], d["items"],
                                         p, q)
        headroom = int(fit["block_capacity"]) - int(self.nnz.max())
        if headroom < 0:
            raise ValueError(
                f"a block holds {int(self.nnz.max())} ratings, over the "
                f"configuration's block_capacity {fit['block_capacity']}")
        with phases("ingest"):
            engine = EngineOptions(**fit.get("engine", {}))
            self.problem = CompletionProblem.from_entries(
                rows, cols, vals, (d["users"], d["items"]), p, q, r,
                layout=fit["layout"], engine=engine,
                mean_center=bool(fit["mean_center"]), headroom=headroom)
            jax.block_until_ready(self.problem.data)
        spec = self.problem.spec
        self.spec = spec
        self.hp = dict(fit["hparams"])
        self.trainer = Trainer(GossipMCConfig(
            m=spec.m, n=spec.n, p=p, q=q, rank=r, **self.hp))
        self.sched = Wave(num_rounds=self.C)
        with phases("init"):
            U0, W0 = init_factors(seed, p, q, spec.mb, spec.nb, r)
            self.state = State(U0, W0, jnp.zeros((), jnp.int32))
            jax.block_until_ready(self.state)
        self.theta0 = (np.asarray(U0), np.asarray(W0))
        self.costs = []
        self.thetas = {}
        self.chunks = 0
        with phases("first_steps"):
            for c in range(STEPS):
                res = self.step()
                self.costs.append(res.final_cost)
                if c + 1 in (1, STEPS):
                    self.thetas[c + 1] = (np.asarray(res.state.U),
                                          np.asarray(res.state.W))
        self.work = workcount.round_work("wave", self.nnz, r)

    def step(self):
        """One chunk: the call the window makes."""

        res = self.trainer.fit(self.problem, self.sched, state=self.state,
                               key=chunk_key(self.seed, self.chunks))
        self.state = res.state
        self.chunks += 1
        return res

    def window(self, seconds: float, span) -> dict:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        rounds = 0
        while time.perf_counter() < deadline:
            with span("bench.fit_chunk"):
                self.step()
            rounds += self.C
        t1 = time.perf_counter()
        return {"rounds": rounds, "attempted": rounds // self.C,
                "failed": 0, "window_s": t1 - t0,
                "rounds_per_s": rounds / (t1 - t0)}

    def release(self) -> None:
        """Free the program's device state before the reference runs."""

        self.state = self.problem = self.trainer = self.sched = None

    # ----------------------------------------------------------------- #
    # the check
    # ----------------------------------------------------------------- #

    def reference(self, dtype=jnp.float32) -> dict:
        """The reference's costs and states over the first three chunks,
        from the same initial factors and keys, ``dtype`` throughout."""

        d = self.cell.config["data"]
        p, q = self.cell.config["fit"]["grid"]
        vals = self.data.train_vals.astype(np.float64)
        if self.cell.config["fit"]["mean_center"]:
            vals = vals - vals.mean()
        centred = vals.astype(np.float32)
        X = reference.dense_blocks(self.data.train_rows,
                                   self.data.train_cols, centred,
                                   d["users"], d["items"], p, q, dtype)
        U, W = (jnp.asarray(a, dtype) for a in self.theta0)
        t, costs, thetas = 0, [], {}
        for c in range(STEPS):
            U, W, t = reference.wave_rounds(
                X, U, W, t, chunk_key(self.seed, c), self.C, self.hp, dtype)
            costs.append(reference.cost(X, U, W, self.hp["lam"]))
            if c + 1 in (1, STEPS):
                thetas[c + 1] = (np.asarray(U.astype(jnp.float32)),
                                 np.asarray(W.astype(jnp.float32)))
        del X
        return {"costs": costs, "thetas": thetas}

    def check(self) -> dict:
        """The compared numbers of this run: the program's first three
        chunks against the float32 reference's."""

        return fit_numbers(self.theta0, self.readings(), self.reference())

    def readings(self) -> dict:
        return {"costs": self.costs, "thetas": self.thetas}


def _leaf_change_norms(theta0, theta) -> np.ndarray:
    """Norm of the change of every block of U and of W (the parameters
    of one agent of the grid each), as one flat array."""

    out = []
    for a0, a in zip(theta0, theta):
        diff = a.astype(np.float64) - a0.astype(np.float64)
        out.append(np.sqrt(np.sum(diff * diff, axis=(2, 3))).ravel())
    return np.concatenate(out)


def change_gap(theta0, got, ref) -> float:
    """Worst leaf's |norm of the program's change - norm of the
    reference's| over the larger of the reference's norm of that leaf and
    the median leaf's.  Leaves the reference moves by under a thousandth
    of the median leaf are left out."""

    g = _leaf_change_norms(theta0, got)
    r = _leaf_change_norms(theta0, ref)
    med = float(np.median(r))
    keep = r >= 1e-3 * med
    if not keep.any() or not np.all(np.isfinite(g)):
        return float("inf")
    return float(np.max(np.abs(g - r)[keep] / np.maximum(r[keep], med)))


def fit_numbers(theta0, got: dict, ref: dict) -> dict:
    """The compared numbers of a fit cell (lower is better)."""

    loss = max(abs(a - b) / abs(b) if np.isfinite(a) else float("inf")
               for a, b in zip(got["costs"], ref["costs"]))
    return {
        "loss": float(loss),
        "change_step1": change_gap(theta0, got["thetas"][1],
                                   ref["thetas"][1]),
        "change_step3": change_gap(theta0, got["thetas"][STEPS],
                                   ref["thetas"][STEPS]),
    }
