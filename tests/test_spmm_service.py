"""SpmmService: request batching, bucket padding, plan caching, results."""
import numpy as np
import pytest

from repro.core import spmm
from repro.data import graphs
from repro.dynamic import GraphDelta
from repro.errors import AdmissionError
from repro.distributed.sharding import make_spmm_mesh
from repro.serve import SpmmService
from conftest import make_sparse


def _register(svc, rng, name="g", m=90, k=70):
    a, rows, cols, vals = make_sparse(rng, m, k, 0.08, n_dense_rows=3)
    svc.register(name, rows, cols, vals, a.shape)
    return a


def test_flush_returns_correct_results(rng):
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    a = _register(svc, rng)
    panels = [rng.randn(70, 16).astype(np.float32) for _ in range(6)]
    tickets = [svc.submit("g", p) for p in panels]
    assert svc.pending("g") == 6
    assert svc.flush() == 6
    assert svc.pending() == 0
    for t, p in zip(tickets, panels):
        got = np.asarray(svc.fetch(t))
        np.testing.assert_allclose(got, a @ p, rtol=1e-4, atol=1e-4)
    with pytest.raises(KeyError):  # fetch pops
        svc.fetch(tickets[0])


def test_bucket_padding_amortizes_traces(rng):
    """Ragged batch sizes pad up to power-of-two buckets, so flushes with
    1..max_batch pending requests share at most log2(max_batch)+1 traces."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    _register(svc, rng)
    b = rng.randn(70, 8).astype(np.float32)
    svc.submit("g", b)
    svc.submit("g", b)
    svc.submit("g", b)
    svc.flush()  # 3 requests -> one bucket-4 dispatch, 1 padded slot
    assert svc.stats.dispatches == 1
    assert svc.stats.padded_slots == 1
    before = spmm.fused_trace_count()
    for _ in range(3):  # any count <= 4 reuses the bucket-4 program
        svc.submit("g", b)
    svc.flush()
    assert spmm.fused_trace_count() == before


def test_oversized_queue_splits_into_groups(rng):
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=2)
    a = _register(svc, rng)
    panels = [rng.randn(70, 8).astype(np.float32) for _ in range(5)]
    tickets = [svc.submit("g", p) for p in panels]
    svc.flush()
    assert svc.stats.dispatches == 3  # 2 + 2 + 1(padded to 2)
    for t, p in zip(tickets, panels):
        np.testing.assert_allclose(np.asarray(svc.fetch(t)), a @ p,
                                   rtol=1e-4, atol=1e-4)


def test_mixed_width_requests_flush_correctly(rng):
    """Panels of different N for one matrix batch per shape group — a mixed
    stack used to raise mid-drain after dequeue, losing both requests."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    a = _register(svc, rng)
    p8 = rng.randn(70, 8).astype(np.float32)
    p16 = rng.randn(70, 16).astype(np.float32)
    t8, t16 = svc.submit("g", p8), svc.submit("g", p16)
    assert svc.flush() == 2
    np.testing.assert_allclose(np.asarray(svc.fetch(t8)), a @ p8,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(svc.fetch(t16)), a @ p16,
                               rtol=1e-4, atol=1e-4)
    assert svc.stats.dispatches == 2  # one per shape group


def test_submit_validates_operand(rng):
    svc = SpmmService(spmm.SpmmConfig(impl="xla"))
    _register(svc, rng)
    with pytest.raises(KeyError):
        svc.submit("unknown", np.zeros((70, 4), np.float32))
    with pytest.raises(ValueError, match="must be"):
        svc.submit("g", np.zeros((71, 4), np.float32))


def test_failed_dispatch_keeps_queue_intact(rng):
    """Requests leave the queue only after a successful dispatch: an
    execute-time failure must not strand tickets result-less."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    a = _register(svc, rng)
    p = rng.randn(70, 8).astype(np.float32)
    t = svc.submit("g", p)
    boom = RuntimeError("injected dispatch failure")
    orig = svc._execute
    svc._execute = lambda *args: (_ for _ in ()).throw(boom)
    with pytest.raises(RuntimeError, match="injected"):
        svc.flush()
    assert svc.pending("g") == 1  # still queued, not stranded
    svc._execute = orig
    svc.flush()
    np.testing.assert_allclose(np.asarray(svc.fetch(t)), a @ p,
                               rtol=1e-4, atol=1e-4)


def test_submit_rejects_indivisible_n_for_rhs_plan(rng):
    """rhs-sharded divisibility is enforced at submit, while the request is
    still the caller's problem (a flush-time raise would strand batches)."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    real = spmm.prepare_sharded(
        np.array([0], np.int64), np.array([0], np.int64),
        np.array([1.0], np.float32), (8, 8), make_spmm_mesh(1),
        spmm.SpmmConfig(impl="xla"), shard_axis="rhs")
    import dataclasses
    svc.register_sharded("g", dataclasses.replace(real, n_shards=4))
    with pytest.raises(ValueError, match="divisible"):
        svc.submit("g", np.zeros((8, 30), np.float32))
    svc.submit("g", np.zeros((8, 32), np.float32))  # divisible: accepted


def test_reregister_with_pending_requests_rejected(rng):
    svc = SpmmService(spmm.SpmmConfig(impl="xla"))
    a = _register(svc, rng)
    svc.submit("g", rng.randn(70, 8).astype(np.float32))
    with pytest.raises(AdmissionError, match="pending"):
        _register(svc, rng, m=50, k=40)


def test_non_pow2_max_batch_rounds_up(rng):
    """The log2(max_batch)+1 trace bound requires pow2 buckets; a non-pow2
    cap would add itself as an extra compiled batch size."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=6)
    assert svc.max_batch == 8
    a = _register(svc, rng)
    b = rng.randn(70, 8).astype(np.float32)
    ts = [svc.submit("g", b) for _ in range(6)]
    svc.flush()  # 6 requests pad to one bucket-8 dispatch
    assert svc.stats.dispatches == 1
    assert svc.stats.padded_slots == 2
    for t in ts:
        np.testing.assert_allclose(np.asarray(svc.fetch(t)), a @ b,
                                   rtol=1e-4, atol=1e-4)


def test_sharded_plan_backend(rng):
    """The same service front drains through a multi-device plan."""
    a, rows, cols, vals = make_sparse(rng, 90, 70, 0.08, n_dense_rows=3)
    cfg = spmm.SpmmConfig(impl="xla")
    splan = spmm.prepare_sharded(rows, cols, vals, a.shape, make_spmm_mesh(1),
                                 cfg, shard_axis="rows")
    svc = SpmmService(cfg, max_batch=2)
    svc.register_sharded("g", splan)
    p = rng.randn(70, 12).astype(np.float32)
    t = svc.submit("g", p)
    svc.flush()
    np.testing.assert_allclose(np.asarray(svc.fetch(t)), a @ p,
                               rtol=1e-4, atol=1e-4)


def test_per_matrix_flush_leaves_other_queues(rng):
    """flush(name=...) drains one queue; other matrices stay pending, so a
    dynamic update to one matrix never forces dispatching every queue."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    a = _register(svc, rng, name="g1")
    a2 = _register(svc, rng, name="g2", m=50, k=40)
    t1 = svc.submit("g1", rng.randn(70, 8).astype(np.float32))
    t2 = svc.submit("g2", rng.randn(40, 8).astype(np.float32))
    assert svc.flush(name="g1") == 1
    assert svc.pending("g1") == 0
    assert svc.pending("g2") == 1  # untouched
    svc.fetch(t1)
    with pytest.raises(KeyError, match="still queued"):
        svc.fetch(t2)
    with pytest.raises(KeyError, match="no matrix registered"):
        svc.flush(name="unknown")
    svc.flush(name="g2")
    svc.fetch(t2)


def test_fetch_raises_clear_keyerrors(rng):
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    _register(svc, rng)
    t = svc.submit("g", rng.randn(70, 8).astype(np.float32))
    with pytest.raises(KeyError, match="still queued"):
        svc.fetch(t)
    svc.flush()
    svc.fetch(t)
    with pytest.raises(KeyError, match="already fetched"):
        svc.fetch(t)
    with pytest.raises(KeyError, match="never issued"):
        svc.fetch(999)


def test_update_matrix_serves_mutated_results(rng):
    """update_matrix flushes that matrix's pre-update requests, applies the
    delta, and later submits see the mutated matrix."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    a = _register(svc, rng)
    dense = a.astype(np.float64).copy()
    p = rng.randn(70, 8).astype(np.float32)
    t_pre = svc.submit("g", p)

    rows, cols = np.nonzero(a)
    zr, zc = np.nonzero(a == 0)
    pick = rng.choice(zr.size, 6, replace=False)
    iv = rng.randn(6)
    delta = GraphDelta(
        ins_rows=zr[pick], ins_cols=zc[pick], ins_vals=iv,
        del_rows=rows[:4], del_cols=cols[:4],
    )
    stats = svc.update_matrix("g", delta)
    assert stats["delta_nnz"] >= 0
    # the pre-update request was drained against the OLD matrix
    np.testing.assert_allclose(np.asarray(svc.fetch(t_pre)), dense @ p,
                               rtol=1e-4, atol=1e-4)
    dense[zr[pick], zc[pick]] += iv
    dense[rows[:4], cols[:4]] = 0
    t_post = svc.submit("g", p)
    svc.flush()
    np.testing.assert_allclose(np.asarray(svc.fetch(t_post)), dense @ p,
                               rtol=1e-4, atol=1e-4)
    assert svc.stats.updates == 1
    with pytest.raises(KeyError):
        svc.update_matrix("nope", delta)


def test_reorder_cols_config_still_serves(rng):
    """reorder_cols plans can't carry a delta sidecar, but registering and
    serving them must keep working (update_matrix is what's unavailable)."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla", reorder_cols=True),
                      max_batch=2)
    a = _register(svc, rng)
    p = rng.randn(70, 8).astype(np.float32)
    t = svc.submit("g", p)
    svc.flush()
    np.testing.assert_allclose(np.asarray(svc.fetch(t)), a @ p,
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="update"):
        svc.update_matrix("g", GraphDelta.deletes([0], [0]))


def test_update_matrix_over_mutation_stream(rng):
    """Drive the service with data.graphs.mutate — the dynamic-serving
    workload end to end, checked against a dense mirror every step."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    a = _register(svc, rng)
    dense = a.astype(np.float64).copy()
    rows, cols = np.nonzero(a)
    vals = a[rows, cols]
    p = rng.randn(70, 8).astype(np.float32)
    for delta in graphs.mutate(rows, cols, vals, a.shape, steps=4,
                               insert_frac=0.04, delete_frac=0.03,
                               update_frac=0.08, seed=5):
        svc.update_matrix("g", delta)
        for r, c, v in zip(delta.ins_rows, delta.ins_cols, delta.ins_vals):
            dense[r, c] += v
        for r, c in zip(delta.del_rows, delta.del_cols):
            dense[r, c] = 0.0
        for r, c, v in zip(delta.upd_rows, delta.upd_cols, delta.upd_vals):
            dense[r, c] = v
        t = svc.submit("g", p)
        svc.flush(name="g")
        np.testing.assert_allclose(np.asarray(svc.fetch(t)), dense @ p,
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# background (async) compaction
# ---------------------------------------------------------------------------
def _structural_overload(rng, a, frac=0.4):
    """A GraphDelta of zero-position inserts big enough to force a fold."""
    dense = a.astype(np.float64)
    zr, zc = np.nonzero(dense == 0)
    n = max(1, int(np.count_nonzero(dense) * frac))
    pick = rng.choice(zr.size, n, replace=False)
    iv = rng.randn(n)
    return GraphDelta.inserts(zr[pick], zc[pick], iv), (zr[pick], zc[pick], iv)


def test_async_compaction_never_blocks_serving(rng, monkeypatch):
    """A should_compact fold runs on the worker thread; submit/flush/fetch
    keep succeeding against the old plan + sidecar until the atomic swap."""
    import threading

    import repro.serve.spmm_service as svc_mod

    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    assert svc.async_compaction
    a = _register(svc, rng)
    dense = a.astype(np.float64).copy()

    real_build = svc_mod._compact_build
    started, release = threading.Event(), threading.Event()

    def slow_build(name, dplan, rows, cols, vals):
        started.set()
        assert release.wait(30), "test never released the fold"
        return real_build(name, dplan, rows, cols, vals)

    monkeypatch.setattr(svc_mod, "_compact_build", slow_build)

    delta, (ir, ic, iv) = _structural_overload(rng, a)
    stats = svc.update_matrix("g", delta)
    dense[ir, ic] += iv
    assert stats["compacted"] == 0  # nothing folded inline
    assert svc.stats.compactions_scheduled == 1
    assert started.wait(10), "fold never started on the worker"

    dp = svc.plan("g")
    p = rng.randn(70, 8).astype(np.float32)
    for _ in range(3):  # serving proceeds while the fold is deliberately stuck
        t = svc.submit("g", p)
        svc.flush(name="g")
        np.testing.assert_allclose(np.asarray(svc.fetch(t)), dense @ p,
                                   rtol=1e-4, atol=1e-4)
    assert dp.compactions == 0 and dp.delta_nnz > 0  # still pre-swap

    release.set()
    svc.drain_compactions(timeout=60)
    assert dp.compactions == 1 and dp.delta_nnz == 0
    assert svc.stats.compactions_applied == 1

    t = svc.submit("g", p)  # post-swap answers are unchanged
    svc.flush(name="g")
    np.testing.assert_allclose(np.asarray(svc.fetch(t)), dense @ p,
                               rtol=1e-4, atol=1e-4)
    svc.close()


def test_async_compaction_stale_snapshot_reschedules(rng, monkeypatch):
    """Mutations landing mid-fold make the snapshot stale: the finished
    fold is discarded (never swapped over newer state) and a fresh fold
    runs from the current matrix."""
    import threading

    import repro.serve.spmm_service as svc_mod

    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    a = _register(svc, rng)
    dense = a.astype(np.float64).copy()

    real_build = svc_mod._compact_build
    started, release = threading.Event(), threading.Event()

    def gated_build(name, dplan, rows, cols, vals):
        started.set()
        assert release.wait(30)
        return real_build(name, dplan, rows, cols, vals)

    monkeypatch.setattr(svc_mod, "_compact_build", gated_build)

    delta, (ir, ic, iv) = _structural_overload(rng, a)
    svc.update_matrix("g", delta)
    dense[ir, ic] += iv
    assert started.wait(10)

    # a second mutation lands while the first fold is in flight
    r0, c0 = int(ir[0]), int(ic[0])
    svc.update_matrix("g", GraphDelta.updates([r0], [c0], [9.5]))
    dense[r0, c0] = 9.5

    release.set()
    svc.drain_compactions(timeout=60)
    assert svc.stats.compactions_stale >= 1   # first fold was discarded
    assert svc.stats.compactions_applied >= 1  # rescheduled fold landed
    dp = svc.plan("g")
    assert dp.delta_nnz == 0

    p = rng.randn(70, 8).astype(np.float32)
    t = svc.submit("g", p)
    svc.flush(name="g")
    np.testing.assert_allclose(np.asarray(svc.fetch(t)), dense @ p,
                               rtol=1e-4, atol=1e-4)
    svc.close()


def test_sync_compaction_opt_out_folds_inline(rng):
    """async_compaction=False restores the old synchronous behavior: the
    fold happens inside update_matrix and is visible in its stats."""
    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4,
                      async_compaction=False)
    a = _register(svc, rng)
    delta, _ = _structural_overload(rng, a)
    stats = svc.update_matrix("g", delta)
    assert stats["compacted"] == 1
    assert svc.plan("g").compactions == 1
    assert svc.stats.compactions_scheduled == 0


def test_failed_fold_does_not_discard_other_folds(rng, monkeypatch):
    """A failed background build surfaces its error but never swallows
    another matrix's completed fold from the same poll batch."""
    import repro.serve.spmm_service as svc_mod

    svc = SpmmService(spmm.SpmmConfig(impl="xla"), max_batch=4)
    a_good = _register(svc, rng, name="good")
    _register(svc, rng, name="bad", m=88)
    dense = a_good.astype(np.float64).copy()

    real_build = svc_mod._compact_build

    def flaky_build(name, dplan, rows, cols, vals):
        if name == "bad":
            raise RuntimeError("injected build failure")
        return real_build(name, dplan, rows, cols, vals)

    monkeypatch.setattr(svc_mod, "_compact_build", flaky_build)

    dg, (ir, ic, iv) = _structural_overload(rng, a_good)
    svc.update_matrix("good", dg)
    dense[ir, ic] += iv
    db, _ = _structural_overload(rng, _dense_of(svc, "bad"))
    svc.update_matrix("bad", db)
    assert svc.stats.compactions_scheduled == 2

    # an unrelated matrix's flush never raises the bad fold's error — the
    # poll records it, adopts the good fold, and the drain surfaces it
    import time as _time

    deadline = _time.time() + 60
    p = rng.randn(70, 8).astype(np.float32)
    while svc.plan("good").compactions == 0 and _time.time() < deadline:
        t = svc.submit("good", p)
        svc.flush(name="good")  # must not raise "injected build failure"
        np.testing.assert_allclose(np.asarray(svc.fetch(t)), dense @ p,
                                   rtol=1e-4, atol=1e-4)
        _time.sleep(0.01)
    assert svc.plan("good").compactions == 1
    assert svc.plan("good").delta_nnz == 0
    with pytest.raises(RuntimeError, match="injected build failure"):
        svc.drain_compactions(timeout=60)
    assert svc.stats.compactions_failed == 1
    svc.close()


def _dense_of(svc, name):
    dp = svc.plan(name)
    maps = dp.maps
    dense = np.zeros(dp.shape, np.float64)
    np.add.at(dense, (maps.rows, maps.cols), maps.vals)
    return dense
