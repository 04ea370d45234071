"""MeshPlan / sharded-data-plane semantics.

Two layers of coverage:

* inline tests — plan geometry, ownership math, spec delegation, and the
  segment-chunk autotune, all runnable on the 1-device test process;
* subprocess tests under ``--xla_force_host_platform_device_count=4``
  (jax fixes the device count at first init, so multi-device runs can't
  share the main process): sharded-vs-global parity for ingest, appends
  and gradients; minibatch restart-exactness and mesh-shape invariance;
  two-stage sharded top-k against the numpy oracle; and 1×1-plan
  bit-identity with the planless facade path.
"""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_prog(prog: str, devices: int = 4, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------- #
# inline: plan geometry + spec delegation (1 device is enough)
# ---------------------------------------------------------------------- #


def test_single_device_plan_geometry():
    from repro.mesh import MeshPlan

    plan = MeshPlan.build(3, 2)
    assert plan.is_single_device
    assert (plan.row_size, plan.col_size) == (1, 1)
    assert plan.blocks_per_row_shard == 3
    assert plan.blocks_per_col_shard == 2
    assert plan.num_item_shards == 1
    assert (plan.block_owners() == 0).all()
    assert plan.owner(2, 1) == plan.mesh.devices.reshape(-1)[0]
    assert "3x2 blocks" in plan.describe()


def test_plan_validation_errors():
    from repro.mesh import MeshPlan, build_mesh

    mesh = build_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="axis 'nope'"):
        MeshPlan.build(2, 2, mesh=mesh, row_axes="nope")
    plan = MeshPlan.build(4, 4, mesh=mesh)
    with pytest.raises(ValueError, match="4x4 grid"):
        # mismatched passthrough: plan for another grid
        MeshPlan.build(2, 2, mesh=plan)
    with pytest.raises(IndexError):
        plan.owner(4, 0)


def test_pspec_delegates_to_mesh_plan():
    """SparseProblem.pspec and plan.entries_spec build the same pytree."""

    import jax
    from jax.sharding import PartitionSpec as P

    from repro.mesh import MeshPlan
    from repro.sparse.store import SparseProblem

    plan = MeshPlan.build(2, 2)
    a = SparseProblem.pspec(plan.grid_spec)
    b = plan.entries_spec()
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert all(x == y for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert SparseProblem.pspec(P("data", "model")).nnz == P("data", "model")


def test_block_owner_map_2x2():
    """Ownership math without needing real devices: fake the mesh axes."""

    import numpy as np

    from repro.mesh import MeshPlan

    plan = MeshPlan.build(4, 4)   # 1 device; owners all 0
    own = plan.block_owners()
    assert own.shape == (4, 4) and (own == 0).all()
    # geometry helpers are pure functions of the sizes: check the
    # contiguous tiling contract via local_blocks on the 1x1 plan
    assert plan.local_blocks(0, 0) == [(i, j) for i in range(4)
                                      for j in range(4)]
    assert isinstance(plan.describe(), str)
    np.testing.assert_array_equal(own, np.zeros((4, 4), np.int32))


def test_launch_mesh_delegates():
    from repro.launch import mesh as LM

    cfg = LM.mesh_config_for(
        __import__("repro.mesh", fromlist=["build_mesh"]).build_mesh(
            (1, 1), ("data", "model")), multi_pod=False)
    plan = LM.production_plan(cfg)
    assert plan.mesh.axis_names == ("data", "model")
    assert LM.make_mesh_from_config(cfg).axis_names == ("data", "model")


# ---------------------------------------------------------------------- #
# inline: segment-chunk autotune (EngineOptions.chunk=None)
# ---------------------------------------------------------------------- #


def test_resolve_chunk_explicit_wins():
    from repro.kernels.sddmm.autotune import resolve_chunk

    assert resolve_chunk(48) == 48
    assert resolve_chunk(48, backend="tpu") == 48


def test_resolve_chunk_fallback_for_unknown_backend():
    from repro.kernels.sddmm import autotune
    from repro.kernels.sddmm.segment import SEG_CHUNK

    assert autotune.resolve_chunk(None, backend="notareal") == SEG_CHUNK
    # the committed sweep is cpu-only; other backends take the fallback
    expected = autotune._committed_sweep().get(
        "tpu", autotune.FALLBACK_CHUNK["tpu"])
    assert autotune.resolve_chunk(None, backend="tpu") == expected


def test_resolve_chunk_reads_committed_sweep(tmp_path):
    from repro.kernels.sddmm import autotune

    sweep = {
        "bench": "sparse_vs_dense", "backend": "cpu",
        "rows": [
            {"density": 0.01, "chunk_sweep_ms": {"16": 5.0, "32": 9.0}},
            {"density": 0.05, "chunk_sweep_ms": {"16": 12.0, "32": 11.0}},
        ],
    }
    path = tmp_path / "BENCH_sparse.json"
    path.write_text(json.dumps(sweep))
    # 16 wins on total (17ms vs 20ms) even though 32 wins one row
    assert autotune._sweep_table(str(path)) == {"cpu": 16}


def test_committed_sweep_is_consulted():
    """The repo's committed BENCH_sparse.json carries a chunk sweep and
    the resolver picks its winner for the cpu backend."""

    from repro.kernels.sddmm import autotune

    table = autotune._sweep_table(autotune._SWEEP_PATH)
    assert "cpu" in table
    assert autotune.resolve_chunk(None, backend="cpu") == table["cpu"]


# ---------------------------------------------------------------------- #
# subprocess: multi-device semantics on 4 forced CPU devices
# ---------------------------------------------------------------------- #

pytestmark_sub = [pytest.mark.distributed, pytest.mark.slow]


@pytest.mark.distributed
@pytest.mark.slow
def test_sharded_ingest_append_and_grads_match_global():
    run_prog("""
import jax, jax.numpy as jnp, numpy as np
from repro.mesh import MeshPlan, build_mesh
from repro import sparse
from repro.sparse.sharded import ShardedEntries, f_grads_sharded
from repro.sparse.objective import f_grads_sparse

rng = np.random.default_rng(0)
m, n, p, q, r = 64, 48, 4, 4, 4
nnz = 500
rows = rng.integers(0, m, nnz); cols = rng.integers(0, n, nnz)
lin = rows * n + cols
_, ui = np.unique(lin, return_index=True)
rows, cols = rows[ui], cols[ui]
vals = rng.normal(size=len(rows)).astype(np.float32)

mesh = build_mesh((2, 2), ("data", "model"))
plan = MeshPlan.build(p, q, mesh=mesh)
assert plan.num_devices == 4
own = plan.block_owners()
assert own[0, 0] == 0 and own[0, 3] == 1 and own[3, 0] == 2 and own[3, 3] == 3
try:
    MeshPlan.build(3, 4, mesh=mesh)        # 3 block rows over 2 device rows
    raise AssertionError("expected ValueError")
except ValueError as e:
    assert "does not tile" in str(e)

# owner-routed ingest == global from_entries, leaf for leaf (the sorted
# fields: a placed store carries no dense tile)
from repro.sparse.entries import SORTED_FIELDS
sp_ref, (M, N) = sparse.from_entries(rows, cols, vals, m, n, p, q, headroom=64)
sh, (M2, N2) = ShardedEntries.from_coo(rows, cols, vals, m, n, plan, headroom=64)
assert (M, N) == (M2, N2)
assert not sh.sp.has_tile
for f in SORTED_FIELDS:
    np.testing.assert_array_equal(np.asarray(getattr(sh.sp.entries, f)),
                                  np.asarray(getattr(sp_ref.entries, f)))
np.testing.assert_array_equal(np.asarray(sh.sp.nnz), np.asarray(sp_ref.nnz))
# placement: every device holds exactly its 2x2 block tile
loc = sh.local(1, 0)
np.testing.assert_array_equal(np.asarray(loc.nnz),
                              np.asarray(sp_ref.nnz)[2:4, 0:2])

# owner-routed append == global append (mixed inserts + duplicate edits)
arows = rng.integers(0, m, 60); acols = rng.integers(0, n, 60)
avals = rng.normal(size=60).astype(np.float32)
ref2 = sparse.append_entries(sp_ref, arows, acols, avals)
sh2 = sh.append(arows, acols, avals)
assert not sh2.sp.has_tile
for f in SORTED_FIELDS:
    np.testing.assert_array_equal(np.asarray(getattr(sh2.sp.entries, f)),
                                  np.asarray(getattr(ref2.entries, f)))
np.testing.assert_array_equal(np.asarray(sh2.sp.nnz), np.asarray(ref2.nnz))

# shard-local f-gradients == global vmap of the same segment engine.  The
# math is block-local, but XLA vectorizes the segment sums differently for
# a device's 2x2 blocks than for the global 4x4 batch, so the f32 sums
# round in another order: allow 8 ulps of the largest gradient (about 4
# ulps are observed)
U = jnp.asarray(rng.normal(size=(p, q, M // p, r)), jnp.float32)
W = jnp.asarray(rng.normal(size=(p, q, N // q, r)), jnp.float32)
gu, gw = f_grads_sharded(sh2, U, W)
_, gu0, gw0 = jax.vmap(jax.vmap(lambda e, u, w: f_grads_sparse(e, u, w)))(
    ref2.entries.without_tile(), U, W)
eps = float(np.finfo(np.float32).eps)
for g, g0 in ((gu, gu0), (gw, gw0)):
    tol = 8 * eps * float(jnp.max(jnp.abs(g0)))
    assert float(jnp.max(jnp.abs(g - g0))) <= tol, (float(jnp.max(jnp.abs(g - g0))), tol)
print("OK")
""")


@pytest.mark.distributed
@pytest.mark.slow
def test_minibatch_stream_restart_exact_and_mesh_invariant():
    run_prog("""
import jax, numpy as np
from repro.mesh import MeshPlan, build_mesh
from repro import sparse

rng = np.random.default_rng(1)
m, n, p, q = 64, 64, 4, 4
mask = (rng.random((m, n)) < 0.2).astype(np.float32)
x = rng.normal(size=(m, n)).astype(np.float32) * mask
from repro.core import grid as G
from repro.core.state import make_problem
spec = G.GridSpec(m, n, p, q, 4)
prob = make_problem(x, mask, spec)
sp = sparse.from_blocks(prob.xb, prob.maskb)

plan4 = MeshPlan.build(p, q, mesh=build_mesh((2, 2), ("data", "model")))
plan1 = MeshPlan.build(p, q)

def leaves(b):
    return [np.asarray(l) for l in jax.tree.leaves(b)]

s4 = sparse.MinibatchStream(sp, batch=32, seed=7, plan=plan4)
s4b = sparse.MinibatchStream(sp, batch=32, seed=7, plan=plan4)
s1 = sparse.MinibatchStream(sp, batch=32, seed=7, plan=plan1)
for step in (0, 3, 11):
    a, b, c = s4.batch_at(step), s4b.batch_at(step), s1.batch_at(step)
    for x_, y_ in zip(leaves(a), leaves(b)):
        np.testing.assert_array_equal(x_, y_)      # restart-exact
    for x_, y_ in zip(leaves(a), leaves(c)):
        np.testing.assert_array_equal(x_, y_)      # mesh-shape invariant
# different steps/seeds differ
d0 = leaves(s4.batch_at(0)); d1 = leaves(s4.batch_at(1))
assert any((x_ != y_).any() for x_, y_ in zip(d0, d1))
other = sparse.MinibatchStream(sp, batch=32, seed=8, plan=plan4)
do = leaves(other.batch_at(0))
assert any((x_ != y_).any() for x_, y_ in zip(d0, do))
# the sampled batches stay valid sorted stores (fast-path invariants)
b = s4.batch_at(5)
rows_ = np.asarray(b.rows)
assert (np.diff(rows_, axis=-1) >= 0).all()
print("OK")
""")


@pytest.mark.distributed
@pytest.mark.slow
def test_two_stage_topk_matches_numpy_oracle():
    run_prog("""
import jax, jax.numpy as jnp, numpy as np
from repro.mesh import MeshPlan
from repro.serve.recommend import (RecommendIndex, build_seen_table,
                                   recommend_topk, recommend_topk_sharded,
                                   shard_index)

rng = np.random.default_rng(3)
m, n, r, k, B = 128, 203, 8, 7, 32    # n % 4 != 0: exercises shard padding
u = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
w = jnp.asarray(rng.normal(size=(n, r)), jnp.float32)
mask = (rng.random((m, n)) < 0.1).astype(np.float32)
seen = jnp.asarray(build_seen_table(mask, n))
index = RecommendIndex(u, w, seen)

plan = MeshPlan.for_devices()
assert plan.num_item_shards == 4
sidx = shard_index(index, plan)
assert sidx.index.w.shape[0] % 4 == 0 and sidx.num_items == n

users = jnp.asarray(rng.integers(0, m, B), jnp.int32)
for exclude in (True, False):
    items, scores = recommend_topk_sharded(sidx, users, k=k,
                                           exclude_seen=exclude)
    # numpy oracle
    sc = np.asarray(u)[np.asarray(users)] @ np.asarray(w).T
    if exclude:
        sc[mask[np.asarray(users)].astype(bool)] = -np.inf
    oid = np.argsort(-sc, axis=1)[:, :k]
    osc = -np.sort(-sc, axis=1)[:, :k]
    np.testing.assert_allclose(np.asarray(scores), osc, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(items), oid)
    # and identical to the unsharded jitted path
    i0, s0 = recommend_topk(index, users, k=k, exclude_seen=exclude)
    np.testing.assert_array_equal(np.asarray(items), np.asarray(i0))

# k > shard slice raises with the geometry spelled out
try:
    recommend_topk_sharded(sidx, users, k=sidx.shard_items + 1)
    raise AssertionError("expected ValueError")
except ValueError as e:
    assert "per-shard" in str(e)
print("OK")
""")


@pytest.mark.distributed
@pytest.mark.slow
def test_gossip_via_plan_matches_full_gd_and_1x1_bit_identical():
    run_prog("""
import jax, jax.numpy as jnp, numpy as np
from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.mesh import MeshPlan, build_mesh
from repro.mc import CompletionProblem, FullGD, Gossip, Trainer

m = n = 128; p = q = 4; r = 4
ds = lowrank_problem(m, n, r, density=0.3, seed=0)
cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)

mesh = build_mesh((2, 2), ("data", "model"))
plan = MeshPlan.build(p, q, mesh=mesh)

# sparse problem placed by the plan at ingest; gossip consumes the shards
prob4 = CompletionProblem.from_dataset(ds, p, q, r, layout="sparse", mesh=plan)
res4 = Trainer(cfg).fit(prob4, Gossip(num_rounds=60), seed=0)

# single-device reference: FullGD is the deterministic limit of gossip
prob1 = CompletionProblem.from_dataset(ds, p, q, r, layout="sparse")
ref = Trainer(cfg).fit(prob1, FullGD(num_rounds=60), seed=0)
diff = float(jnp.max(jnp.abs(res4.state.U - ref.state.U)))
assert diff < 1e-5, diff

# 1x1 MeshPlan == planless gossip, bit for bit (equal seed)
plan1 = MeshPlan.build(p, q)
a = Trainer(cfg).fit(prob1.with_mesh(plan1), Gossip(num_rounds=40), seed=0)
b = Trainer(cfg).fit(prob1, Gossip(num_rounds=40), seed=0)
assert (np.asarray(a.state.U) == np.asarray(b.state.U)).all()
assert (np.asarray(a.state.W) == np.asarray(b.state.W)).all()
print("OK", diff)
""")


@pytest.mark.distributed
@pytest.mark.slow
def test_sharded_service_refresh_guards():
    run_prog("""
import numpy as np
from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.mesh import MeshPlan, build_mesh
from repro.mc import CompletionProblem, Incremental, Trainer

m = n = 96; p = q = 2; r = 4
ds = lowrank_problem(m, n, r, density=0.3, seed=0)
cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)
problem = CompletionProblem.from_dataset(ds, p, q, r, layout="sparse",
                                         headroom=256)
res = Trainer(cfg).fit(problem, "wave", num_rounds=30, seed=0)

plan4 = MeshPlan.for_devices()
svc = res.to_service(k=5, plan=plan4)
assert svc.num_item_shards == 4
items0, _ = svc.recommend(np.arange(8))

# same-geometry refresh hot-swaps cleanly
fresh = problem.append(np.array([1, 2]), np.array([3, 4]),
                       np.array([5.0, 4.0], np.float32))
res2 = Trainer(cfg).refit(res, fresh, Incremental(num_rounds=5))
svc.refresh(res2)
items1, _ = svc.recommend(np.arange(8))
assert items1.shape == items0.shape

# a refit whose problem carries a different item-shard geometry raises
# with the expected-vs-got counts (not a deep shape error mid-serve)
plan1 = MeshPlan.build(p, q)
res3 = Trainer(cfg).refit(res, fresh.with_mesh(plan1),
                          Incremental(num_rounds=2))
try:
    svc.refresh(res3)
    raise AssertionError("expected ValueError")
except ValueError as e:
    msg = str(e)
    assert "4 shards" in msg and "1 shards" in msg, msg
print("OK")
""")


@pytest.mark.distributed
@pytest.mark.slow
def test_sharded_serving_engine_parity_and_hot_refresh():
    """ServingEngine with plan=4-device MeshPlan: every bucket compiles
    once at startup against the sharded two-stage query, answers match
    the unsharded jitted oracle exactly, and a refit refresh hot-swaps
    the device shards without a single new compile."""

    run_prog("""
import jax.numpy as jnp, numpy as np
from repro import obs
from repro.mesh import MeshPlan
from repro.serve.recommend import (RecommendIndex, build_seen_table,
                                   recommend_topk)
from repro.serving import ServingEngine

rng = np.random.default_rng(5)
m, n, r, k = 128, 203, 8, 7            # n % 4 != 0: exercises shard padding
u = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
w = jnp.asarray(rng.normal(size=(n, r)), jnp.float32)
mask = (rng.random((m, n)) < 0.1).astype(np.float32)
index = RecommendIndex(u, w, jnp.asarray(build_seen_table(mask, n)))

plan = MeshPlan.for_devices()
assert plan.num_item_shards == 4
obs.reset()
buckets = (8, 32)
eng = ServingEngine(index, buckets=buckets, k=k, plan=plan)
assert obs.counter("serve_compiles_total").value == len(buckets)

for sz in (1, 8, 9, 32, 33, 70):       # padded, exact, and multi-chunk
    users = rng.integers(0, m, size=sz).astype(np.int32)
    items, scores = eng.recommend(users)
    ri, rs = recommend_topk(index, jnp.asarray(users), k=k,
                            exclude_seen=True)
    np.testing.assert_array_equal(items, np.asarray(ri))
    np.testing.assert_allclose(scores, np.asarray(rs), rtol=1e-5, atol=1e-5)
assert obs.counter("serve_compiles_total").value == len(buckets)

# hot refresh re-shards the new factors; still zero new compiles
u2 = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
index2 = RecommendIndex(u2, w, index.seen)
eng.refresh(index2)
users = rng.integers(0, m, size=20).astype(np.int32)
items, scores = eng.recommend(users)
ri, rs = recommend_topk(index2, jnp.asarray(users), k=k, exclude_seen=True)
np.testing.assert_array_equal(items, np.asarray(ri))
assert obs.counter("serve_compiles_total").value == len(buckets)
assert obs.counter("engine_refreshes_total").value == 1.0

eng.shutdown()
try:
    eng.submit([1])
    raise AssertionError("expected RuntimeError")
except RuntimeError:
    pass
print("OK")
""")


@pytest.mark.distributed
@pytest.mark.slow
def test_sharded_engine_int8_parity_overlap_and_hot_refresh():
    """ServingEngine(quant="int8") on a 4-device item-sharded plan:
    every bucket compiles once against the int8 layout, answers are
    bit-identical to the unsharded jitted quantized path (per-row scales
    commute with item sharding — DESIGN.md §16), top-k overlap vs the
    f32 index clears the 0.99 retrieval-stage gate at k=100, and an f32
    hot refresh is re-quantized + re-sharded with zero new compiles."""

    run_prog("""
import jax.numpy as jnp, numpy as np
from repro import obs
from repro.mesh import MeshPlan
from repro.serve.quant import quantize_index
from repro.serve.recommend import (RecommendIndex, build_seen_table,
                                   recommend_topk)
from repro.serving import ServingEngine

rng = np.random.default_rng(9)
m, n, r, k = 128, 502, 32, 100         # n % 4 != 0: exercises shard padding
u = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
w = jnp.asarray(rng.normal(size=(n, r)), jnp.float32)
mask = (rng.random((m, n)) < 0.05).astype(np.float32)
index = RecommendIndex(u, w, jnp.asarray(build_seen_table(mask, n)))

plan = MeshPlan.for_devices()
assert plan.num_item_shards == 4
obs.reset()
buckets = (8, 32)
eng = ServingEngine(index, buckets=buckets, k=k, plan=plan, quant="int8")
assert eng.quant == "int8"
assert obs.counter("serve_compiles_total").value == len(buckets)
assert obs.snapshot()["gauges"]["serve_index_bytes{dtype=int8}"] > 0

q = quantize_index(index)
overlaps = []
for sz in (1, 8, 9, 32, 33, 70):       # padded, exact, and multi-chunk
    users = rng.integers(0, m, size=sz).astype(np.int32)
    items, scores = eng.recommend(users)
    ri, rs = recommend_topk(q, jnp.asarray(users), k=k,
                            method=eng.quant_method)
    np.testing.assert_array_equal(items, np.asarray(ri))
    assert np.array_equal(scores, np.asarray(rs))          # bitwise
    fi, _ = recommend_topk(index, jnp.asarray(users), k=k)
    fi = np.asarray(fi)
    overlaps.append(np.mean([len(set(items[i]) & set(fi[i])) / k
                             for i in range(sz)]))
assert np.mean(overlaps) >= 0.99, overlaps
assert obs.counter("serve_compiles_total").value == len(buckets)

# f32 hot refresh: re-quantized + re-sharded, still zero new compiles
u2 = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
index2 = RecommendIndex(u2, w, index.seen)
eng.refresh(index2)
users = rng.integers(0, m, size=20).astype(np.int32)
items, scores = eng.recommend(users)
ri, rs = recommend_topk(quantize_index(index2), jnp.asarray(users), k=k,
                        method=eng.quant_method)
np.testing.assert_array_equal(items, np.asarray(ri))
assert np.array_equal(scores, np.asarray(rs))
assert obs.counter("serve_compiles_total").value == len(buckets)
assert obs.counter("engine_refreshes_total").value == 1.0
eng.shutdown()
print("OK")
""")


# ---------------------------------------------------------------------- #
# chaos: fault injection + recovery on the real 4-device grid
# ---------------------------------------------------------------------- #


@pytest.mark.distributed
@pytest.mark.slow
@pytest.mark.chaos
def test_gossip_fault_path_p0_bit_identical_and_p02_converges():
    """Acceptance pins for the fault model (DESIGN.md §13) on a 2x2
    device grid: a FaultPlan with p_drop=0 is bit-identical to the
    fault-free step, and p_drop=0.2 still converges — held-out RMSE
    within 2x of the fault-free fit at equal rounds, with the drop /
    staleness counters streaming into the obs registry."""

    run_prog("""
import numpy as np
from repro import obs
from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.faults import FaultPlan
from repro.mc import CompletionProblem, Gossip, Trainer
from repro.mesh import MeshPlan, build_mesh

m = n = 64; p = q = 2; r = 4; rounds = 40
mesh = build_mesh((2, 2), ("data", "model"))
plan = MeshPlan.build(p, q, mesh=mesh)
ds = lowrank_problem(m, n, r, density=0.3, seed=0)
problem = CompletionProblem.from_dataset(ds, p, q, r, layout="sparse",
                                         mesh=plan)
cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)

def fit(faults):
    return Trainer(cfg).fit(
        problem, Gossip(num_rounds=rounds, plan=plan, faults=faults),
        seed=0)

clean = fit(None)

# p_drop=0: the fault machinery costs nothing when nothing fails
p0 = fit(FaultPlan(key=0, p_drop_edge=0.0))
assert (np.asarray(p0.state.U) == np.asarray(clean.state.U)).all()
assert (np.asarray(p0.state.W) == np.asarray(clean.state.W)).all()

# p_drop=0.2: graceful degradation, not a cliff
obs.reset()
faulty = fit(FaultPlan(key=0, p_drop_edge=0.2))
ratio = float(faulty.rmse() / clean.rmse())
assert ratio < 2.0, ratio
counters = obs.snapshot()["counters"]
assert counters["gossip_edges_dropped_total"] > 0, counters
assert counters["gossip_stale_rounds_total"] > 0, counters
print("OK rmse_vs_clean=", ratio)
""")


@pytest.mark.distributed
@pytest.mark.slow
@pytest.mark.chaos
def test_gossip_crash_mid_fit_restart_bit_exact():
    """examples/failure_recovery.py's assertion, lifted to the Gossip
    schedule on the 4-device grid: crash mid-fit, restore from the last
    checkpoint, and the resumed fit matches the uninterrupted run
    bit-for-bit (staleness=1 halos are rebuilt on the first resumed
    round, so resume is exact)."""

    run_prog("""
import tempfile
import numpy as np
from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.mc import (Callback, Checkpoint, CompletionProblem, Gossip,
                      Trainer)
from repro.mesh import MeshPlan, build_mesh

m = n = 64; p = q = 2; r = 4
mesh = build_mesh((2, 2), ("data", "model"))
plan = MeshPlan.build(p, q, mesh=mesh)
ds = lowrank_problem(m, n, r, density=0.3, seed=0)
problem = CompletionProblem.from_dataset(ds, p, q, r, layout="sparse",
                                         mesh=plan)
cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)
sched = Gossip(num_rounds=12, eval_every=2, plan=plan)

ref = Trainer(cfg).fit(problem, sched, seed=0)

class Crash(RuntimeError):
    pass

class CrashAt(Callback):
    def __init__(self, unit):
        self.unit = unit
    def on_eval(self, unit, cost, state, key):
        if unit >= self.unit:
            raise Crash()

ck = Checkpoint(tempfile.mkdtemp(prefix="chaos_ck_"))
try:
    Trainer(cfg, callbacks=[CrashAt(7), ck]).fit(problem, sched, seed=0)
    raise AssertionError("crash did not fire")
except Crash:
    pass
unit, _, _ = ck.restore(problem)
assert 0 < unit < 12, unit
rec = Trainer(cfg, callbacks=[ck]).fit(problem, sched, seed=0,
                                       resume_from=ck)
assert (np.asarray(rec.state.U) == np.asarray(ref.state.U)).all()
assert (np.asarray(rec.state.W) == np.asarray(ref.state.W)).all()
print("OK resumed from", unit)
""")


@pytest.mark.distributed
@pytest.mark.slow
@pytest.mark.chaos
def test_gossip_nan_inject_auto_restores():
    """A fit that hits an injected NaN round self-heals: the guard fires
    at the next eval, the trainer restores the last valid checkpoint,
    refolds the fault stream (nan_at cleared — transient faults don't
    replay), and the resumed fit completes finite, with the restart in
    FitResult.recovery_log and fit_recoveries_total."""

    run_prog("""
import tempfile
import numpy as np
from repro import obs
from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.faults import FaultPlan, RecoveryPolicy
from repro.mc import Checkpoint, CompletionProblem, Gossip, Trainer
from repro.mesh import MeshPlan, build_mesh

m = n = 64; p = q = 2; r = 4
mesh = build_mesh((2, 2), ("data", "model"))
plan = MeshPlan.build(p, q, mesh=mesh)
ds = lowrank_problem(m, n, r, density=0.3, seed=0)
problem = CompletionProblem.from_dataset(ds, p, q, r, layout="sparse",
                                         mesh=plan)
cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)

# NaN lands at round 5: checkpoints at rounds 2 and 4 are finite, the
# eval at round 6 sees the poison and the guard fires before Checkpoint
sched = Gossip(num_rounds=12, eval_every=2, plan=plan,
               faults=FaultPlan(key=0, nan_at=5))
ck = Checkpoint(tempfile.mkdtemp(prefix="chaos_nan_"))
obs.reset()
res = Trainer(cfg, callbacks=[ck]).fit(
    problem, sched, seed=0,
    recovery=RecoveryPolicy(max_restarts=2, backoff=0.5))

assert np.isfinite(res.final_cost), res.final_cost
assert np.isfinite(np.asarray(res.state.U)).all()
assert len(res.recovery_log) == 1, res.recovery_log
entry = res.recovery_log[0]
assert entry["restart"] == 1
assert entry["reason"] == "non-finite cost"
assert entry["resumed_from"] == 4, entry
assert obs.snapshot()["counters"]["fit_recoveries_total"] == 1.0
print("OK recovered:", entry)
""")


# ---------------------------------------------------------------------- #
# subprocess: asynchronous stochastic gossip (DESIGN.md §15)
# ---------------------------------------------------------------------- #


@pytest.mark.distributed
@pytest.mark.slow
def test_async_e1_s0_bit_identical_to_sync():
    """The degenerate async regime (exchange_every=1, max_staleness=0,
    batch=None) is the synchronous step: on the 2x2 device grid the two
    fits are bit-identical — the acceptance pin that async is a strict
    generalization, not a fork."""

    run_prog("""
import numpy as np
from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.mc import CompletionProblem, Gossip, Trainer
from repro.mesh import MeshPlan, build_mesh

m = n = 64; p = q = 2; r = 4; rounds = 40
mesh = build_mesh((2, 2), ("data", "model"))
plan = MeshPlan.build(p, q, mesh=mesh)
ds = lowrank_problem(m, n, r, density=0.3, seed=0)
problem = CompletionProblem.from_dataset(ds, p, q, r, layout="sparse",
                                         mesh=plan)
cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)

sync = Trainer(cfg).fit(problem, Gossip(num_rounds=rounds, plan=plan), seed=0)
asyn = Trainer(cfg).fit(
    problem,
    Gossip(num_rounds=rounds, plan=plan, async_rounds=True,
           exchange_every=1, max_staleness=0),
    seed=0)
assert (np.asarray(sync.state.U) == np.asarray(asyn.state.U)).all()
assert (np.asarray(sync.state.W) == np.asarray(asyn.state.W)).all()
print("OK async e=1 s=0 bit-identical")
""")


@pytest.mark.distributed
@pytest.mark.slow
def test_async_age_bounded_by_planned_skipping():
    """Under async_rounds with exchange_every=e and no faults, the halo
    age is exactly rnd % e on every direction — it touches but never
    exceeds max_staleness = e-1, so no seam ever gates out under planned
    skipping alone."""

    run_prog("""
import numpy as np, jax
from repro.config import GossipMCConfig
from repro.core import gossip
from repro.core import grid as G
from repro.core.state import init_state, make_problem
from repro.data import lowrank_problem
from repro.mesh import MeshPlan, build_mesh

m = n = 64; p = q = 2; r = 4; e = 3
spec = G.GridSpec(m, n, p, q, r)
ds = lowrank_problem(m, n, r, density=0.3, seed=0)
prob = make_problem(ds.x, ds.train_mask, spec)
mesh = build_mesh((2, 2), ("data", "model"))
plan = MeshPlan.build(p, q, mesh=mesh)
cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)

step, _ = gossip.make_gossip_step(
    None, (p, q), cfg, plan=plan, async_rounds=True, exchange_every=e,
    max_staleness=e - 1)
carry = gossip.init_carry(init_state(jax.random.PRNGKey(0), spec))
seen = []
for t in range(12):
    carry = step(prob, carry)
    age = np.asarray(carry.halos.age)
    assert (age == t % e).all(), (t, age)
    seen.append(int(age.max()))
assert max(seen) == e - 1, seen
print("OK age = rnd % e, max", max(seen))
""")


@pytest.mark.distributed
@pytest.mark.slow
@pytest.mark.chaos
def test_async_composes_with_fault_plan():
    """async + FaultPlan compose: drop events burn only on exchange
    rounds, so the observed drop counter equals the host-side
    FaultPlan.replay masked to rounds with rnd % e == 0 (and to edges
    that exist on the device grid), while the skipped-exchange counter
    accounts every planned skip exactly."""

    run_prog("""
import numpy as np
from repro import obs
from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.faults import FaultPlan
from repro.mc import CompletionProblem, Gossip, Trainer
from repro.mesh import MeshPlan, build_mesh

m = n = 64; p = q = 2; r = 4; rounds = 24; e = 2
mesh = build_mesh((2, 2), ("data", "model"))
plan = MeshPlan.build(p, q, mesh=mesh)
ds = lowrank_problem(m, n, r, density=0.3, seed=0)
problem = CompletionProblem.from_dataset(ds, p, q, r, layout="sparse",
                                         mesh=plan)
cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)

fp = FaultPlan(key=7, p_drop_edge=0.3)
obs.reset()
res = Trainer(cfg).fit(
    problem,
    Gossip(num_rounds=rounds, plan=plan, async_rounds=True,
           exchange_every=e, max_staleness=3, faults=fp),
    seed=0)
counters = obs.snapshot()["counters"]

rp = fp.replay(rounds, plan.num_devices)
R, C = plan.row_size, plan.col_size
exists = np.zeros((plan.num_devices, 4), bool)
for di in range(R):
    for dj in range(C):
        exists[di * C + dj] = (dj > 0, dj < C - 1, di > 0, di < R - 1)
on_exchange = np.array([t % e == 0 for t in range(rounds)])
expected = int((rp["drops"] & exists[None] & on_exchange[:, None, None]).sum())
assert expected > 0, "degenerate replay: no drops injected"
assert counters["gossip_edges_dropped_total"] == expected, (
    counters["gossip_edges_dropped_total"], expected)
assert counters["gossip_skipped_exchanges_total"] == rounds - rounds // e
assert counters["gossip_stale_rounds_total"] > 0
assert np.isfinite(res.final_cost)
print("OK drops", expected, "skipped",
      counters["gossip_skipped_exchanges_total"])
""")


@pytest.mark.distributed
@pytest.mark.slow
@pytest.mark.chaos
def test_async_stochastic_beats_sync_at_equal_wall_clock():
    """Convergence gate (DESIGN.md §15): at a scale where the full
    gradient is compute-bound (nnz/block >> batch), async stochastic
    gossip reaches RMSE <= 1.05x the sync full-gradient fit inside the
    same wall-clock budget on the 2x2 device grid.  Rounds are allocated
    from per-round times measured in-process, so the gate is about the
    sync/async round-cost *ratio* (the physics), not absolute machine
    speed; at the measured ~4x ratio the async arm lands far below the
    gate, leaving a wide flake margin."""

    run_prog("""
import time
import numpy as np
from repro.config import GossipMCConfig
from repro.data import lowrank_problem
from repro.mc import CompletionProblem, Gossip, Trainer
from repro.mesh import MeshPlan, build_mesh

m = n = 2048; p = q = 2; r = 16; density = 0.3
mesh = build_mesh((2, 2), ("data", "model"))
plan = MeshPlan.build(p, q, mesh=mesh)
ds = lowrank_problem(m, n, r, density=density, seed=0)
problem = CompletionProblem.from_dataset(ds, p, q, r, layout="sparse",
                                         mesh=plan)
cfg = GossipMCConfig(m=m, n=n, p=p, q=q, rank=r)

def fit(R, **kw):
    t0 = time.perf_counter()
    res = Trainer(cfg).fit(problem, Gossip(num_rounds=R, plan=plan, **kw),
                           seed=0)
    return res, time.perf_counter() - t0

akw = dict(batch=8192, async_rounds=True, exchange_every=2, max_staleness=2)
fit(2); fit(2, **akw)                          # compile both paths

R_sync = 16
sync, t_sync = fit(R_sync)
# two-point calibration: per-fit fixed cost (ingest sync, final eval) is
# ~1s and would otherwise be billed as round time, starving the async arm
_, t8 = fit(8, **akw)
_, t24 = fit(24, **akw)
slope = max((t24 - t8) / 16.0, 1e-4)
fixed = max(t8 - 8.0 * slope, 0.0)
R_async = max(1, min(96, int((t_sync - fixed) / slope)))
asyn, t_async = fit(R_async, **akw)

rs, ra = float(sync.rmse()), float(asyn.rmse())
print(f"sync {R_sync}rd {t_sync:.2f}s rmse={rs:.4f} | "
      f"async {R_async}rd {t_async:.2f}s rmse={ra:.4f}")
assert ra <= 1.05 * rs, (ra, rs, R_async)
""")
