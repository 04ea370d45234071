"""ServingEngine: bucket routing parity, AOT bit-identity, hot refresh
under load, RefreshPolicy auto-refit, and shutdown semantics.

The contracts pinned here (DESIGN.md §14):

* every request size routes onto the ladder and comes back equal to the
  direct jitted ``recommend_topk`` — item ids exact wherever scores are not
  tied, scores up to the f32 rounding of a matmul at another batch shape;
  padding and chunking are otherwise invisible;
* ``serve_compiles_total`` equals the bucket count after startup and
  never moves under traffic or refresh (the always-hot property);
* a request runs against exactly one factor version even when a refresh
  lands mid-stream (atomic snapshot per request, multi-chunk included);
* ``shutdown(drain=True)`` resolves the backlog, then rejects new work.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.serve.recommend import RecommendIndex, recommend_topk
from repro.serving import (BucketLadder, DEFAULT_BUCKETS, RefreshPolicy,
                           ServingEngine, compile_buckets)

K = 5


def _index(m=120, n=90, r=6, seed=0, seen_per_user=4):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(m, r)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n, r)), jnp.float32)
    seen = np.full((m, 16), n, np.int32)
    seen[:, :seen_per_user] = rng.integers(0, n, size=(m, seen_per_user))
    return RecommendIndex(u, w, jnp.asarray(seen))


def _oracle(idx, user_ids, k=K):
    items, scores = recommend_topk(idx, jnp.asarray(user_ids, jnp.int32),
                                   k=k, exclude_seen=True)
    return np.asarray(items), np.asarray(scores)


def _assert_same_topk(items, scores, ref_i, ref_s, rtol=1e-5):
    """Top-k equal up to f32 rounding.  A request padded into a bucket
    runs the score matmul at another batch shape than the direct call, and
    XLA picks its reduction order per shape, so scores may differ in the
    last ulps.  Item ids must match exactly wherever a score is not tied
    (within ``rtol``) with a neighbour, where rounding may swap them."""

    atol = rtol * np.abs(ref_s).max()
    np.testing.assert_allclose(scores, ref_s, rtol=rtol, atol=atol)
    close = np.isclose(ref_s[:, 1:], ref_s[:, :-1], rtol=rtol, atol=atol)
    tied = np.zeros(ref_s.shape, bool)
    tied[:, 1:] |= close
    tied[:, :-1] |= close
    np.testing.assert_array_equal(items[~tied], ref_i[~tied])


# --------------------------------------------------------------------------
# BucketLadder geometry
# --------------------------------------------------------------------------


def test_ladder_bucket_for_and_plan():
    lad = BucketLadder((16, 64, 256))
    assert lad.max_size == 256
    assert [lad.bucket_for(n) for n in (1, 16, 17, 64, 65, 256)] == \
        [16, 16, 64, 64, 256, 256]
    # plan() chunk lengths always sum to n; chunk buckets are on the ladder
    for n in list(range(1, 70)) + [255, 256, 257, 512, 513, 1000]:
        chunks = lad.plan(n)
        assert sum(length for _, length, _ in chunks) == n
        assert all(b in lad.sizes and length <= b
                   for _, length, b in chunks)
        # contiguous coverage from 0
        pos = 0
        for start, length, _ in chunks:
            assert start == pos
            pos += length
    # oversize requests split into top-bucket chunks + one padded tail
    assert lad.plan(600) == [(0, 256, 256), (256, 256, 256), (512, 88, 256)]


def test_ladder_validation():
    with pytest.raises(ValueError, match="at least one"):
        BucketLadder(())
    with pytest.raises(ValueError, match="positive"):
        BucketLadder((0, 8))
    with pytest.raises(ValueError, match="strictly increasing"):
        BucketLadder((8, 8))
    with pytest.raises(ValueError, match="positive"):
        BucketLadder((16,)).bucket_for(0)
    with pytest.raises(ValueError, match="exceeds the top bucket"):
        BucketLadder((16,)).bucket_for(17)
    assert BucketLadder().sizes == DEFAULT_BUCKETS


# --------------------------------------------------------------------------
# AOT compile: bit-identity + eager compile accounting
# --------------------------------------------------------------------------


def test_compile_buckets_bit_identical_to_jit():
    """The executables ARE the compiled form of recommend_topk: same
    padded batch in, bitwise-equal items AND scores out."""

    idx = _index()
    lad = BucketLadder((8, 32))
    obs.reset()
    execs = compile_buckets(idx, lad, K, True)
    assert set(execs) == {8, 32}
    assert obs.counter("serve_compiles_total").value == 2.0
    rng = np.random.default_rng(1)
    for bucket in lad.sizes:
        users = rng.integers(0, 120, size=bucket).astype(np.int32)
        items, scores = execs[bucket](idx, users)
        ref_i, ref_s = _oracle(idx, users)
        np.testing.assert_array_equal(np.asarray(items), ref_i)
        assert np.array_equal(np.asarray(scores), ref_s)   # bitwise


def test_engine_routing_parity_every_size():
    """Every request size around the bucket edges — single-bucket, padded
    tail, and multi-chunk oversize — returns exactly what the direct
    jitted query returns, and serves zero post-startup compiles."""

    idx = _index()
    obs.reset()
    eng = ServingEngine(idx, buckets=(8, 32, 64), k=K)
    try:
        assert obs.counter("serve_compiles_total").value == 3.0
        rng = np.random.default_rng(2)
        sizes = [1, 7, 8, 9, 31, 32, 33, 63, 64, 65, 128, 129, 200]
        for sz in sizes:
            users = rng.integers(0, 120, size=sz).astype(np.int32)
            items, scores = eng.recommend(users)
            _assert_same_topk(items, scores, *_oracle(idx, users))
        assert obs.counter("serve_compiles_total").value == 3.0
        m = eng.metrics()
        assert m["compiles"] == 3.0
        assert m["requests"] == len(sizes)
        assert m["latency"]["count"] == len(sizes)
        assert m["queue_wait"]["count"] == len(sizes)
        assert sum(b["count"] for b in m["buckets"].values()) >= len(sizes)
        assert m["qps"] > 0.0
    finally:
        eng.shutdown()


def test_engine_recommend_many_and_futures():
    idx = _index()
    with ServingEngine(idx, buckets=(8, 32), k=K) as eng:
        reqs = [np.arange(5), np.arange(10, 40), np.array([7])]
        outs = eng.recommend_many(reqs)
        assert len(outs) == 3
        for users, (items, scores) in zip(reqs, outs):
            ref_i, _ = _oracle(idx, users)
            np.testing.assert_array_equal(items, ref_i)
        fut = eng.submit([1, 2, 3])
        items, scores = fut.result(timeout=30)
        assert items.shape == (3, K)
        with pytest.raises(ValueError, match="empty"):
            eng.submit([])


# --------------------------------------------------------------------------
# hot refresh
# --------------------------------------------------------------------------


def test_refresh_swaps_without_recompiling():
    idx_a = _index(seed=0)
    idx_b = _index(seed=1)          # same shapes, different factors
    obs.reset()
    eng = ServingEngine(idx_a, buckets=(8, 32), k=K)
    try:
        users = np.arange(40, dtype=np.int32)
        items_a, _ = eng.recommend(users)
        eng.refresh(idx_b)
        items_b, scores_b = eng.recommend(users)
        ref_i, ref_s = _oracle(idx_b, users)
        np.testing.assert_array_equal(items_b, ref_i)
        assert np.array_equal(scores_b, ref_s)
        assert not np.array_equal(items_a, items_b)
        assert obs.counter("serve_compiles_total").value == 2.0
        assert obs.counter("engine_refreshes_total").value == 1.0
        assert eng.metrics()["refreshes"] == 1.0
    finally:
        eng.shutdown()


def test_refresh_guards_shapes_and_seen_capacity():
    idx = _index(m=50, n=40, r=4)
    eng = ServingEngine(idx, buckets=(8,), k=3, seen_headroom=16)
    try:
        assert eng.seen_capacity == 16 + 16
        # wider seen table within headroom: fine (post-append refreshes)
        wider = idx._replace(seen=jnp.full((50, 30), 40, jnp.int32))
        eng.refresh(wider)
        # beyond capacity: the frozen executable shapes cannot absorb it
        too_wide = idx._replace(seen=jnp.full((50, 64), 40, jnp.int32))
        with pytest.raises(ValueError, match="seen_headroom"):
            eng.refresh(too_wide)
        # factor reshape is a new engine, not a refresh
        bad = RecommendIndex(idx.u, jnp.ones((41, 4), jnp.float32), idx.seen)
        with pytest.raises(ValueError, match="factor shapes"):
            eng.refresh(bad)
    finally:
        eng.shutdown()


def test_refresh_under_load_never_mixes_versions():
    """Requests in flight across a refresh each resolve against exactly
    one factor version — multi-chunk requests included (the snapshot is
    per-request, not per-chunk)."""

    idx_a = _index(seed=3)
    idx_b = _index(seed=4)
    # 40-user requests span two chunks on this ladder (32 + padded 8):
    # a torn swap would stitch version A's first chunk to B's second
    users = [np.random.default_rng(i).integers(0, 120, size=40)
             .astype(np.int32) for i in range(30)]
    oracle_a = [_oracle(idx_a, u) for u in users]
    oracle_b = [_oracle(idx_b, u) for u in users]
    eng = ServingEngine(idx_a, buckets=(8, 32), k=K)
    try:
        stop = threading.Event()

        def refresher():
            flip = True
            while not stop.is_set():
                eng.refresh(idx_b if flip else idx_a)
                flip = not flip
                time.sleep(0.001)

        t = threading.Thread(target=refresher)
        t.start()
        futures = [eng.submit(u) for u in users]
        results = [f.result(timeout=60) for f in futures]
        stop.set()
        t.join()
        for i, (items, scores) in enumerate(results):
            is_a = (np.array_equal(items, oracle_a[i][0])
                    and np.array_equal(scores, oracle_a[i][1]))
            is_b = (np.array_equal(items, oracle_b[i][0])
                    and np.array_equal(scores, oracle_b[i][1]))
            assert is_a or is_b, f"request {i}: mixed factor versions"
    finally:
        eng.shutdown()


# --------------------------------------------------------------------------
# RefreshPolicy auto-refit
# --------------------------------------------------------------------------


def test_refresh_policy_validation():
    with pytest.raises(ValueError, match="max_appends and/or"):
        RefreshPolicy()
    with pytest.raises(ValueError, match="positive"):
        RefreshPolicy(max_appends=0)
    with pytest.raises(ValueError, match="positive"):
        RefreshPolicy(max_age_seconds=-1.0)
    p = RefreshPolicy(max_appends=10, max_age_seconds=60.0)
    assert not p.due(9, 59.0)
    assert p.due(10, 0.0) and p.due(0, 60.0)


def test_refresh_policy_trips_refit_and_hot_swap():
    """The full auto-refit loop against a real (tiny) Trainer fit:
    note_append bookkeeping → policy trips → trainer.refit → hot swap,
    with the engine then serving the refreshed factors."""

    from repro.config import GossipMCConfig
    from repro.data import lowrank_problem
    from repro.mc import CompletionProblem, Trainer, Wave

    M, N, P, Q, R = 48, 40, 2, 2, 3
    ds = lowrank_problem(M, N, R, density=0.3, seed=0)
    rr, cc = np.nonzero(ds.train_mask)
    vv = ds.x[rr, cc]
    cut = int(0.8 * len(rr))
    prob = CompletionProblem.from_entries(
        rr[:cut], cc[:cut], vv[:cut], shape=(M, N), p=P, q=Q, rank=R,
        headroom=256,
    )
    cfg = GossipMCConfig(m=prob.spec.m, n=prob.spec.n, p=P, q=Q, rank=R)
    trainer = Trainer(cfg)
    result = trainer.fit(prob, Wave(num_rounds=3), seed=0)

    obs.reset()
    eng = result.to_engine(buckets=(8, 16), k=4, trainer=trainer,
                           refresh_policy=RefreshPolicy(max_appends=30))
    try:
        grown = prob.append(rr[cut:], cc[cut:], vv[cut:])
        before, _ = eng.recommend(np.arange(16))
        # below threshold: bookkeeping only
        assert eng.note_append(10, problem=grown) is False
        assert eng.appends_since_refresh == 10
        assert obs.counter("engine_refreshes_total").value == 0.0
        # crossing the threshold trips refit + swap
        assert eng.note_append(25) is True
        assert eng.appends_since_refresh == 0
        assert obs.counter("engine_refreshes_total").value == 1.0
        # the engine now serves the refitted factors, bit-identical to
        # the refit's own index padded into the frozen seen capacity
        after, after_s = eng.recommend(np.arange(16))
        ref = eng._fit_result.to_recommend_index()
        ref_i, ref_s = recommend_topk(ref, jnp.arange(16, dtype=jnp.int32),
                                      k=4, exclude_seen=True)
        ref_i, ref_s = np.asarray(ref_i), np.asarray(ref_s)
        np.testing.assert_array_equal(after, ref_i)
        assert np.array_equal(after_s, ref_s)
        # no serve-time compiles through any of it
        assert obs.counter("serve_compiles_total").value == 2.0
    finally:
        eng.shutdown()


def test_refresh_policy_age_trigger():
    idx = _index(m=30, n=20, r=3)
    eng = ServingEngine(idx, buckets=(8,), k=3,
                        refresh_policy=RefreshPolicy(max_age_seconds=1e-6))
    try:
        # due by age but nothing bound → bookkeeping only, no crash
        time.sleep(0.005)
        assert eng.note_append(0) is False
        assert eng.metrics()["last_refresh_age_seconds"] > 0.0
    finally:
        eng.shutdown()


# --------------------------------------------------------------------------
# lifecycle
# --------------------------------------------------------------------------


def test_shutdown_drains_then_rejects():
    idx = _index()
    eng = ServingEngine(idx, buckets=(8, 32), k=K)
    users = [np.arange(i + 1, dtype=np.int32) for i in range(20)]
    futures = [eng.submit(u) for u in users]
    eng.shutdown(drain=True)
    for u, f in zip(users, futures):
        items, scores = f.result(timeout=0)   # already resolved
        assert items.shape == (len(u), K)
    with pytest.raises(RuntimeError, match="shut down"):
        eng.submit([1])
    eng.shutdown()                            # idempotent


def test_drain_blocks_until_empty():
    idx = _index()
    with ServingEngine(idx, buckets=(8,), k=K) as eng:
        futures = [eng.submit([i]) for i in range(50)]
        eng.drain()
        assert all(f.done() for f in futures)
        assert eng.metrics()["queue_depth"] == 0


# --------------------------------------------------------------------------
# spans on the worker path
# --------------------------------------------------------------------------


def _span_stats():
    return {k[len("span_seconds{name="):-1]: h
            for k, h in obs.snapshot()["histograms"].items()
            if k.startswith("span_seconds{name=serve.")}


def test_request_spans_cover_its_chunks():
    """A request that splits into two chunks is one ``serve.request``
    around two ``serve.dispatch`` and two ``serve.fetch`` spans, and each
    chunk's batch time is its two spans' time."""

    idx = _index()
    with ServingEngine(idx, buckets=(8, 32, 64), k=K) as eng:
        eng.recommend(np.arange(8))               # warm the 8 bucket
        obs.reset()
        eng.recommend(np.arange(100) % 120)       # 64 + 36 → two 64s
        eng.drain()                               # the request span closed
        spans = _span_stats()
        assert {k: h["count"] for k, h in spans.items()} == {
            "serve.request": 1, "serve.dispatch": 2, "serve.fetch": 2}
        inner = spans["serve.dispatch"]["sum"] + spans["serve.fetch"]["sum"]
        assert spans["serve.request"]["sum"] >= inner
        batch = eng.metrics()["buckets"][64]
        assert batch["count"] == 2
        assert batch["sum"] == pytest.approx(inner)


@pytest.mark.parametrize("size", [1, 9, 32, 64, 65, 129, 200])
def test_bucket_counts_follow_the_ladder_plan(size):
    """``metrics()["buckets"]`` counts one observation per planned chunk,
    and one request span per request whatever its size."""

    idx = _index()
    buckets = (8, 32, 64)
    with ServingEngine(idx, buckets=buckets, k=K) as eng:
        obs.reset()
        eng.recommend(np.arange(size) % 120)
        eng.drain()
        plan = eng.ladder.plan(size)
        counts = {b: h["count"] for b, h in eng.metrics()["buckets"].items()}
        assert counts == {b: sum(c[2] == b for c in plan) for b in buckets}
        spans = _span_stats()
        assert spans["serve.request"]["count"] == 1
        assert spans["serve.dispatch"]["count"] == len(plan)
        assert spans["serve.fetch"]["count"] == len(plan)


def test_request_ids_tie_chunks_to_their_request(tmp_path):
    """Under a profiler capture each request's spans share its ``rid``;
    chunk spans also carry their ``bucket``."""

    import glob

    from jax.profiler import ProfileData

    idx = _index()
    with ServingEngine(idx, buckets=(8, 32), k=K) as eng:
        eng.recommend(np.arange(40))              # compile outside
        with obs.trace(str(tmp_path)):
            eng.recommend_many([np.arange(5), np.arange(40)])
            eng.drain()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host")
              for line in plane.lines for e in line.events
              if e.name.startswith("serve.")]
    reqs = sorted(st["rid"] for name, st in events if name == "serve.request")
    assert len(reqs) == 2 and reqs[0] != reqs[1]
    chunks = sorted((st["rid"], st["bucket"], name) for name, st in events
                    if name != "serve.request")
    assert chunks == [                            # 5 → 8; 40 → 32 + 8
        (reqs[0], 8, "serve.dispatch"), (reqs[0], 8, "serve.fetch"),
        (reqs[1], 8, "serve.dispatch"), (reqs[1], 8, "serve.fetch"),
        (reqs[1], 32, "serve.dispatch"), (reqs[1], 32, "serve.fetch")]
