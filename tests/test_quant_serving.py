"""int8 factor serving cache (DESIGN.md §16): quantization error bounds,
fused-kernel arithmetic identities, the overlap@k accuracy gate, engine
AOT bit-identity on the int8 layout, and refresh layout discipline.

The contracts pinned here:

* ``quantize_rows`` round-trip error is ≤ scale/2 = max|row|/254
  elementwise (zero rows exact), and per-row scales make quantization a
  pure per-row map — quantize-then-slice == slice-then-quantize, which is
  why the sharded path serves int8 with zero extra machinery;
* ``method="dequant"`` equals the numpy dequantize-then-matmul oracle
  within the f32 dot's rounding bound; ``method="fused"`` (XLA emulation) equals the Pallas kernel in
  interpret mode **bit for bit** (both accumulate the int8 products in
  int32, then apply the same f32 epilogue);
* top-k overlap@k against the f32 index stays ≥ 0.99 on randomized grids
  at the retrieval-stage contract (k=100) — the inline accuracy gate;
* ``ServingEngine(quant="int8")`` serves every bucket bit-identical to
  the jitted quantized path with zero serve-time compiles, re-quantizes
  f32 refreshes on the hot swap, never mixes factor versions under a
  refresh storm, and rejects cross-layout swaps with the full
  expected-vs-got shapes in the message.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels.quant import (FALLBACK_METHOD, dequant_score,
                                 dequant_score_ref, fused_score_xla,
                                 resolve_method)
from repro.serve.quant import (QuantizedRecommendIndex, index_nbytes,
                               quantize_index, quantize_rows)
from repro.serve.recommend import (RecommendIndex, RecommendService,
                                   recommend_topk, score_pairs, shard_index)
from repro.serving import ServingEngine

K = 100


def _index(m=300, n=2000, r=32, seed=0, seen_per_user=4):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n, r)), jnp.float32)
    seen = np.full((m, 16), n, np.int32)
    seen[:, :seen_per_user] = rng.integers(0, n, size=(m, seen_per_user))
    return RecommendIndex(u, w, jnp.asarray(seen))


def _overlap(a, b, k):
    a, b = np.asarray(a), np.asarray(b)
    return np.mean([len(set(a[i]) & set(b[i])) / k for i in range(len(a))])


# --------------------------------------------------------------------------
# quantization: round-trip bound, zero rows, per-row locality
# --------------------------------------------------------------------------


@pytest.mark.parametrize("seed,shape", [(0, (50, 8)), (1, (200, 32)),
                                        (2, (17, 48)), (3, (1, 128))])
def test_roundtrip_error_bound(seed, shape):
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=shape) * rng.lognormal(size=(shape[0], 1))
         ).astype(np.float32)
    q, s = quantize_rows(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    back = np.asarray(q, np.float32) * np.asarray(s)[:, None]
    amax = np.abs(x).max(axis=1)
    # elementwise: |x - s·round(x/s)| <= s/2 = amax/254
    bound = amax / 254.0 + 1e-6
    assert (np.abs(x - back) <= bound[:, None]).all()


def test_zero_rows_get_unit_scale_and_zero_codes():
    x = np.zeros((4, 16), np.float32)
    x[2] = np.linspace(-1, 1, 16)
    q, s = quantize_rows(x)
    q, s = np.asarray(q), np.asarray(s)
    assert (q[[0, 1, 3]] == 0).all()
    assert (s[[0, 1, 3]] == 1.0).all()       # never 0: scales multiply
    assert np.abs(q[2]).max() == 127


def test_per_row_scales_commute_with_slicing():
    # the property the sharded path leans on: a row's quantization
    # depends on nothing outside the row
    x = np.random.default_rng(7).normal(size=(64, 16)).astype(np.float32)
    q_all, s_all = quantize_rows(x)
    q_cut, s_cut = quantize_rows(x[20:50])
    np.testing.assert_array_equal(np.asarray(q_all)[20:50],
                                  np.asarray(q_cut))
    np.testing.assert_array_equal(np.asarray(s_all)[20:50],
                                  np.asarray(s_cut))


def test_quantize_index_idempotent_and_gauges():
    obs.reset()
    idx = _index(m=100, n=500, r=32)
    q = quantize_index(idx)
    assert isinstance(q, QuantizedRecommendIndex)
    assert quantize_index(q) is q
    assert (q.num_users, q.num_items, q.rank) == (100, 500, 32)
    # memory story: (r+4)/(4r) at r=32 -> 0.28125, and the gauges carry it
    assert index_nbytes(q) / index_nbytes(idx) <= 0.3
    g = obs.snapshot()["gauges"]
    assert g["serve_index_bytes{dtype=f32}"] == index_nbytes(idx)
    assert g["serve_index_bytes{dtype=int8}"] == index_nbytes(q)


# --------------------------------------------------------------------------
# scoring methods: oracle parity, kernel/emulation bit-identity
# --------------------------------------------------------------------------


def test_dequant_method_equals_numpy_oracle():
    idx = _index(m=60, n=300, r=24, seed=1)
    q = quantize_index(idx)
    got = dequant_score(q.u_q[:32], q.u_scale[:32], q.w_q, q.w_scale,
                        method="dequant")
    u = np.asarray(q.u_q[:32], np.float32) * np.asarray(q.u_scale[:32])[:, None]
    w = np.asarray(q.w_q, np.float32) * np.asarray(q.w_scale)[:, None]
    # same dequantized rows, but XLA's and numpy's f32 dots sum the r
    # products in different orders: each is within r·eps·Σ|u_i·w_i| of the
    # exact dot (the standard bound), so they differ by at most twice that
    bound = 2 * u.shape[1] * np.finfo(np.float32).eps * (np.abs(u) @ np.abs(w).T)
    assert (np.abs(np.asarray(got) - u @ w.T) <= bound).all()


def test_fused_xla_equals_pallas_kernel_bitwise():
    # the XLA emulation and the Pallas kernel share the exact arithmetic:
    # int32 accumulation of int8 products, then the f32 scale epilogue —
    # interpret mode runs the real kernel body off-TPU
    for seed, (b, n, r) in [(0, (8, 100, 16)), (1, (32, 700, 32)),
                            (2, (5, 129, 50))]:
        idx = _index(m=max(b, 8), n=n, r=r, seed=seed)
        q = quantize_index(idx)
        a = fused_score_xla(q.u_q[:b], q.u_scale[:b], q.w_q, q.w_scale)
        k = dequant_score(q.u_q[:b], q.u_scale[:b], q.w_q, q.w_scale,
                          method="fused", force_kernel=True, interpret=True)
        assert a.shape == k.shape == (b, n)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(k))


def test_fused_close_to_dequant_reference():
    # same quantized inputs, different float rounding order only
    idx = _index(m=50, n=400, r=32, seed=3)
    q = quantize_index(idx)
    f = dequant_score(q.u_q, q.u_scale, q.w_q, q.w_scale, method="fused")
    d = dequant_score(q.u_q, q.u_scale, q.w_q, q.w_scale, method="dequant")
    np.testing.assert_allclose(np.asarray(f), np.asarray(d),
                               rtol=1e-4, atol=1e-5)


def test_score_pairs_quantized_matches_dequant():
    idx = _index(m=50, n=200, r=16, seed=4)
    q = quantize_index(idx)
    uids = jnp.arange(30)
    iids = jnp.asarray(np.random.default_rng(0).integers(0, 200, 30))
    got = score_pairs(q, uids, iids)
    want = score_pairs(q.dequantize(), uids, iids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_resolve_method_validation_and_fallback():
    assert resolve_method("fused") == "fused"
    assert resolve_method("dequant") == "dequant"
    with pytest.raises(ValueError, match="unknown dequant-score method"):
        resolve_method("int4")
    # unknown backend falls back to the always-correct reference
    assert resolve_method(None, backend="weird-accelerator") == "dequant"
    for backend, m in FALLBACK_METHOD.items():
        assert resolve_method(None, backend=backend) in ("fused", "dequant")


# --------------------------------------------------------------------------
# accuracy gate: overlap@k vs the f32 index
# --------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_overlap_gate_randomized_grids(seed):
    idx = _index(seed=seed)                     # m=300, n=2000, r=32
    q = quantize_index(idx)
    uids = jnp.asarray(np.random.default_rng(seed + 10)
                       .integers(0, 300, 256).astype(np.int32))
    i_f, _ = recommend_topk(idx, uids, k=K)
    for method in ("fused", "dequant"):
        i_q, _ = recommend_topk(q, uids, k=K, method=method)
        assert _overlap(i_f, i_q, K) >= 0.99


def test_recommend_topk_quantized_respects_seen_and_k_guard():
    idx = _index(m=40, n=120, r=8, seed=5, seen_per_user=6)
    q = quantize_index(idx)
    uids = jnp.arange(40)
    items, _ = recommend_topk(q, uids, k=20, exclude_seen=True)
    items = np.asarray(items)
    seen = np.asarray(idx.seen)
    for i in range(40):
        assert not (set(items[i]) & set(seen[i][seen[i] < 120]))
    with pytest.raises(ValueError, match="exceeds catalog size"):
        recommend_topk(q, uids, k=121)


# --------------------------------------------------------------------------
# engine: AOT int8 path, zero serve-time compiles, refresh discipline
# --------------------------------------------------------------------------


def test_engine_int8_bit_identical_to_jitted_quantized_path():
    idx = _index(m=200, n=500, r=32, seed=6)
    obs.reset()
    buckets = (8, 32)
    eng = ServingEngine(idx, buckets=buckets, k=K, quant="int8")
    try:
        assert eng.quant == "int8"
        assert obs.counter("serve_compiles_total").value == len(buckets)
        g = obs.snapshot()["gauges"]
        assert g["serve_index_bytes{dtype=int8}"] > 0
        qref = quantize_index(idx)._replace(seen=eng._bufs.seen)
        for sz in (1, 8, 9, 32, 33, 70):
            users = np.random.default_rng(sz).integers(0, 200, sz)
            items, scores = eng.recommend(users.astype(np.int32))
            # pad exactly like the ladder does, compare chunk by chunk
            ji = np.empty((sz, K), np.int32)
            js = np.empty((sz, K), np.float32)
            for start, length, bucket in eng.ladder.plan(sz):
                chunk = users[start:start + length].astype(np.int32)
                chunk = np.pad(chunk, (0, bucket - length))
                a, b = recommend_topk(qref, jnp.asarray(chunk), k=K,
                                      method=eng.quant_method)
                ji[start:start + length] = np.asarray(a)[:length]
                js[start:start + length] = np.asarray(b)[:length]
            np.testing.assert_array_equal(items, ji)
            assert np.array_equal(scores, js)
        assert obs.counter("serve_compiles_total").value == len(buckets)
    finally:
        eng.shutdown()


def test_engine_refresh_requantizes_f32_swap_in():
    idx_a = _index(m=80, n=200, r=16, seed=7)
    idx_b = _index(m=80, n=200, r=16, seed=8)
    obs.reset()
    eng = ServingEngine(idx_a, buckets=(16,), k=10, quant="int8")
    try:
        users = np.arange(16, dtype=np.int32)
        items_a, _ = eng.recommend(users)
        eng.refresh(idx_b)                      # f32 in -> re-quantized
        items_b, scores_b = eng.recommend(users)
        qb = quantize_index(idx_b)._replace(seen=eng._bufs.seen)
        ri, rs = recommend_topk(qb, jnp.asarray(users), k=10,
                                method=eng.quant_method)
        np.testing.assert_array_equal(items_b, np.asarray(ri))
        assert np.array_equal(scores_b, np.asarray(rs))
        assert not np.array_equal(items_a, items_b)
        assert obs.counter("serve_compiles_total").value == 1.0
        # the gauge tracks the refreshed int8 payload
        g = obs.snapshot()["gauges"]
        assert g["serve_index_bytes{dtype=int8}"] == index_nbytes(
            qb._replace(seen=eng._bufs.seen))
    finally:
        eng.shutdown()


def test_engine_rejects_mixed_layout_swaps():
    idx = _index(m=40, n=100, r=8, seed=9)
    q = quantize_index(idx)
    f32_eng = ServingEngine(idx, buckets=(8,), k=5)
    try:
        with pytest.raises(ValueError, match="mix factor layouts"):
            f32_eng.refresh(q)
    finally:
        f32_eng.shutdown()
    # shape guard on the int8 engine reports the full expected-vs-got
    # shapes, symmetric with the f32 message
    eng = ServingEngine(idx, buckets=(8,), k=5, quant="int8")
    try:
        bad = RecommendIndex(idx.u, jnp.ones((101, 8), jnp.float32),
                             idx.seen)
        with pytest.raises(ValueError) as ei:
            eng.refresh(bad)
        msg = str(ei.value)
        assert "expected u(40, 8) x w(100, 8) (int8 layout)" in msg
        assert "got u(40, 8) x w(101, 8)" in msg
    finally:
        eng.shutdown()


def test_quantized_index_refresh_message_shapes():
    idx = _index(m=30, n=50, r=8, seed=10)
    q = quantize_index(idx)

    class FakeFit:
        def __init__(self, index):
            self._i = index

        def to_recommend_index(self):
            return self._i

    bad = RecommendIndex(idx.u, jnp.ones((51, 8), jnp.float32), idx.seen)
    with pytest.raises(ValueError) as ei:
        q.refresh(FakeFit(bad))
    msg = str(ei.value)
    assert "expected u(30, 8) x w(50, 8) (int8 layout)" in msg
    assert "got u(30, 8) x w(51, 8)" in msg
    # a same-shape refresh re-quantizes
    idx2 = _index(m=30, n=50, r=8, seed=11)
    q2 = q.refresh(FakeFit(idx2))
    np.testing.assert_array_equal(np.asarray(q2.u_q),
                                  np.asarray(quantize_index(idx2).u_q))


def test_sharded_index_refresh_message_shapes_single_device():
    # 1-device plan: exercises the sharded refresh guard without a mesh
    from repro.mesh import MeshPlan

    class FakeFit:
        def __init__(self, index):
            self._i = index

        def to_recommend_index(self):
            return self._i

    plan = MeshPlan.for_devices()
    idx = _index(m=20, n=40, r=8, seed=12)
    sq = shard_index(quantize_index(idx), plan)
    assert sq.quantized
    bad = RecommendIndex(idx.u, jnp.ones((41, 8), jnp.float32), idx.seen)
    with pytest.raises(ValueError) as ei:
        sq.refresh(FakeFit(bad))
    msg = str(ei.value)
    assert "expected u(20, 8) x w(40, 8) (int8 layout)" in msg
    assert "got u(20, 8) x w(41, 8)" in msg
    # good refresh keeps the quantized sharded layout
    idx2 = _index(m=20, n=40, r=8, seed=13)
    sq2 = sq.refresh(FakeFit(idx2))
    assert sq2.quantized
    np.testing.assert_array_equal(
        np.asarray(sq2.index.w_q)[:40],
        np.asarray(quantize_index(idx2).w_q))


def test_engine_refresh_under_load_never_mixes_quantized_versions():
    idx_a = _index(m=120, n=90, r=6, seed=3, seen_per_user=4)
    idx_b = _index(m=120, n=90, r=6, seed=4, seen_per_user=4)
    eng = ServingEngine(idx_a, buckets=(8, 32), k=5, quant="int8")
    try:
        # 40-user requests span two chunks on this ladder; a torn swap
        # would stitch version A's first chunk to B's second
        users = [np.random.default_rng(i).integers(0, 120, size=40)
                 .astype(np.int32) for i in range(20)]
        oracles = {}
        for key, idx in (("a", idx_a), ("b", idx_b)):
            q = quantize_index(idx)
            oracles[key] = [
                tuple(np.asarray(x) for x in recommend_topk(
                    q, jnp.asarray(u), k=5, method=eng.quant_method))
                for u in users]
        stop = threading.Event()

        def refresher():
            flip = True
            while not stop.is_set():
                eng.refresh(idx_b if flip else idx_a)  # re-quantizes
                flip = not flip

        t = threading.Thread(target=refresher)
        t.start()
        try:
            futures = [eng.submit(u) for u in users]
            results = [f.result(timeout=60) for f in futures]
        finally:
            stop.set()
            t.join()
        for i, (items, scores) in enumerate(results):
            is_a = (np.array_equal(items, oracles["a"][i][0])
                    and np.array_equal(scores, oracles["a"][i][1]))
            is_b = (np.array_equal(items, oracles["b"][i][0])
                    and np.array_equal(scores, oracles["b"][i][1]))
            assert is_a or is_b, f"request {i}: mixed quantized versions"
    finally:
        eng.shutdown()


# --------------------------------------------------------------------------
# front ends: RecommendService / FitResult bridges
# --------------------------------------------------------------------------


def test_service_quant_serves_and_validates():
    idx = _index(m=100, n=300, r=16, seed=14)
    svc = RecommendService(idx, batch=32, k=10, quant="int8")
    assert isinstance(svc.index, QuantizedRecommendIndex)
    items, scores = svc.recommend(np.arange(50))
    assert items.shape == (50, 10)
    ri, _ = recommend_topk(svc.index, jnp.arange(32), k=10,
                           method=svc.quant_method)
    np.testing.assert_array_equal(items[:32], np.asarray(ri))
    with pytest.raises(ValueError, match="unknown quant mode"):
        RecommendService(idx, quant="int4")
    with pytest.raises(ValueError, match="unknown quant mode"):
        ServingEngine(idx, quant="fp8")


def test_fit_result_to_service_and_engine_quant():
    from repro.config import GossipMCConfig
    from repro.data import lowrank_problem
    from repro.mc import CompletionProblem, Trainer, Wave

    M, N, P, Q, R = 48, 40, 2, 2, 3
    ds = lowrank_problem(M, N, R, density=0.3, seed=0)
    rr, cc = np.nonzero(ds.train_mask)
    vv = ds.x[rr, cc]
    prob = CompletionProblem.from_entries(
        rr, cc, vv, shape=(M, N), p=P, q=Q, rank=R)
    cfg = GossipMCConfig(m=prob.spec.m, n=prob.spec.n, p=P, q=Q, rank=R)
    trainer = Trainer(cfg)
    result = trainer.fit(prob, Wave(num_rounds=2), seed=0)

    svc = result.to_service(batch=16, k=5, quant="int8")
    assert isinstance(svc.index, QuantizedRecommendIndex)
    items, _ = svc.recommend(np.arange(10))
    assert items.shape == (10, 5)

    obs.reset()
    eng = result.to_engine(buckets=(8,), k=5, quant="int8")
    try:
        assert eng.quant == "int8"
        assert obs.counter("serve_compiles_total").value == 1.0
        items, _ = eng.recommend(np.arange(10))
        assert items.shape == (10, 5)
        # FitResult refresh flows through re-quantization
        eng.refresh(result)
        assert obs.counter("serve_compiles_total").value == 1.0
    finally:
        eng.shutdown()
