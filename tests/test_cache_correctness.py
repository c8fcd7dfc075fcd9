"""Cache correctness: signature uniqueness across tier/batch/shard variants
and retrace-count guarantees for the batched executor.

A signature collision would silently hand a plan to another plan's compiled
executor (wrong static shapes/kernels); a retrace leak would recompile per
call.  Both are invisible to output-correctness tests, so they get their
own suite.
"""
import jax.numpy as jnp
import numpy as np

from repro.core import spmm
from repro.distributed.sharding import make_spmm_mesh
from conftest import make_sparse


def _fringe_problem(rng, m=60, k=96, nnz=400):
    rows = rng.randint(0, m, nnz)
    cols = rng.randint(0, k, nnz)
    vals = rng.randn(nnz).astype(np.float32)
    return rows.astype(np.int64), cols.astype(np.int64), vals, (m, k)


# ---------------------------------------------------------------------------
# signature uniqueness
# ---------------------------------------------------------------------------
def test_signatures_unique_across_tier_and_shard_variants(rng):
    """Tier variants, the sharded rows/rhs variants, and the plain plan all
    carry distinct cache keys — no fused-executor aliasing."""
    rows, cols, vals, shape = _fringe_problem(rng)
    mk = lambda budget: spmm.prepare(
        rows, cols, vals, shape,
        spmm.SpmmConfig(impl="pallas_interpret", bn=128, alpha=1.0,
                        fringe_vmem_budget=budget))
    resident, ksharded, xla = mk(None), mk(60_000), mk(4_096)
    assert {p.fringe_tier for p in (resident, ksharded, xla)} == {
        "resident", "ksharded", "xla"}

    mesh = make_spmm_mesh(1)
    cfg = spmm.SpmmConfig(impl="xla")
    plain = spmm.prepare(rows, cols, vals, shape, cfg)
    srows = spmm.prepare_sharded(rows, cols, vals, shape, mesh, cfg,
                                 shard_axis="rows")
    srhs = spmm.prepare_sharded(rows, cols, vals, shape, mesh, cfg,
                                shard_axis="rhs")
    sigs = [p.signature() for p in (resident, ksharded, xla,
                                    plain, srows, srhs)]
    assert len(set(sigs)) == len(sigs)


def test_batched_cache_key_includes_batch(rng):
    """The batched executor is cached per (signature, batch): distinct batch
    sizes never share one compiled program object."""
    from repro.exec import build_executor

    rows, cols, vals, shape = _fringe_problem(rng)
    plan = spmm.prepare(rows, cols, vals, shape, spmm.SpmmConfig(impl="xla"))
    sig = plan.signature()
    fn2 = build_executor(sig, batch=2)
    fn3 = build_executor(sig, batch=3)
    assert fn2 is not fn3
    assert build_executor(sig, batch=2) is fn2  # cache hit


# ---------------------------------------------------------------------------
# retrace counts
# ---------------------------------------------------------------------------
def test_batched_executor_traces_once_per_signature_and_batch(rng):
    a, rows, cols, vals = make_sparse(rng, 120, 100, 0.06, n_dense_rows=4)
    cfg = spmm.SpmmConfig(impl="xla")
    plan = spmm.prepare(rows, cols, vals, a.shape, cfg)
    b3 = jnp.asarray(rng.randn(3, 100, 24).astype(np.float32))
    spmm.execute(plan, b3).block_until_ready()  # trace (sig, batch=3)
    before = spmm.fused_trace_count()
    for _ in range(4):  # same (signature, batch): zero retraces
        spmm.execute(plan, b3).block_until_ready()
    # a re-prepared identical plan reuses the cached batched executor
    plan2 = spmm.prepare(rows, cols, vals, a.shape, cfg)
    assert plan2.signature() == plan.signature()
    spmm.execute(plan2, b3).block_until_ready()
    assert spmm.fused_trace_count() == before

    # a new batch size is exactly one legitimate retrace
    b5 = jnp.asarray(rng.randn(5, 100, 24).astype(np.float32))
    spmm.execute(plan, b5).block_until_ready()
    assert spmm.fused_trace_count() == before + 1
    spmm.execute(plan, b5).block_until_ready()
    assert spmm.fused_trace_count() == before + 1


def test_batched_and_unbatched_paths_do_not_alias(rng):
    """(K, N) and (1, K, N) operands produce equal math through separate
    cache entries, and neither retraces the other."""
    a, rows, cols, vals = make_sparse(rng, 80, 64, 0.08)
    plan = spmm.prepare(rows, cols, vals, a.shape,
                        spmm.SpmmConfig(impl="xla"))
    b = jnp.asarray(rng.randn(64, 16).astype(np.float32))
    flat = np.asarray(spmm.execute(plan, b))
    batched = np.asarray(spmm.execute(plan, b[None]))
    np.testing.assert_allclose(batched[0], flat, rtol=1e-6, atol=1e-6)
    before = spmm.fused_trace_count()
    spmm.execute(plan, b).block_until_ready()
    spmm.execute(plan, b[None]).block_until_ready()
    assert spmm.fused_trace_count() == before
