"""Dynamic-update parity worker on a simulated multi-device mesh.

Asserts, on 2- and 4-way meshes with forced host devices:
- ``update_values`` over a rows-sharded plan produces leaves bit-identical
  to re-running ``prepare_sharded`` with the updated values, and executes
  identically;
- structural inserts/deletes through ``DynamicPlan`` match the fp64 dense
  oracle before and after a forced compaction (which re-shards);
- sharded + delta executes as ONE dispatch (the routed sidecar merges
  inside the shard_map program; ``exec.dispatch_count`` rises by exactly 1)
  and is bit-identical to the legacy two-dispatch formulation
  (``execute_sharded`` + ``execute_delta_contribution`` post-pass).

Launched by tests/test_dynamic.py through the ``forced_mesh_run`` conftest
fixture, and runnable standalone:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python tests/_dynamic_sharded_worker.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.hostdevices import force_host_device_count  # noqa: E402

force_host_device_count(os.environ, 4)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import plan_ir, spmm  # noqa: E402
from repro.dynamic import (  # noqa: E402
    DynamicPlan, GraphDelta, build_delta_fringe, update_values,
)
from repro.exec import dispatch_count  # noqa: E402
from repro.distributed.sharding import make_spmm_mesh  # noqa: E402


def _coo(seed, m, k, density):
    rng = np.random.RandomState(seed)
    mask = rng.rand(m, k) < density
    rows, cols = np.nonzero(mask)
    return rows.astype(np.int64), cols.astype(np.int64), rng.randn(rows.size)


def _dense(rows, cols, vals, shape):
    a = np.zeros(shape, np.float64)
    np.add.at(a, (rows, cols), vals)
    return a


def check(n_shards):
    rng = np.random.RandomState(n_shards)
    m, k = 96 * n_shards // 2, 64
    rows, cols, vals = _coo(n_shards, m, k, 0.08)
    mesh = make_spmm_mesh(n_shards)
    cfg = spmm.SpmmConfig(impl="xla")
    b = jnp.asarray(rng.randn(k, 16).astype(np.float32))

    # value-only parity, bit for bit
    splan = spmm.prepare_sharded(rows, cols, vals, (m, k), mesh, cfg,
                                 shard_axis="rows")
    idx = rng.choice(rows.size, 25, replace=False)
    nv = rng.randn(25)
    updated = update_values(splan, idx, nv)
    vals2 = vals.copy()
    vals2[idx] = nv
    ref = spmm.prepare_sharded(rows, cols, vals2, (m, k), mesh, cfg,
                               shard_axis="rows")
    for i, (got, want) in enumerate(zip(updated.leaves, ref.leaves)):
        assert np.array_equal(np.asarray(got), np.asarray(want)), (
            n_shards, "leaf", i)
    assert np.array_equal(
        np.asarray(spmm.execute_sharded(updated, b)),
        np.asarray(spmm.execute_sharded(ref, b)),
    ), (n_shards, "value exec")

    # structural oracle across shards, then a forced compaction (re-shard)
    dp = DynamicPlan(updated, auto_compact=False)
    dense = _dense(rows, cols, vals2, (m, k))
    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 18, replace=False)
    iv = rng.randn(18)
    dp.update(GraphDelta.inserts(zr[pick], zc[pick], iv))
    dense[zr[pick], zc[pick]] += iv
    dpick = rng.choice(rows.size, 9, replace=False)
    dp.update(GraphDelta.deletes(rows[dpick], cols[dpick]))
    dense[rows[dpick], cols[dpick]] = 0

    def assert_close():
        out = np.asarray(dp.execute(b))
        expect = dense @ np.asarray(b, np.float64)
        scale = np.abs(expect).max() + 1e-9
        assert np.abs(out - expect).max() / scale < 1e-4, (
            n_shards, "structural")

    assert_close()

    # --- single dispatch + bit-parity with the legacy two-dispatch form ---
    delta = dp._materialize()
    assert isinstance(delta, plan_ir.ShardedDeltaFringe), type(delta)
    before = dispatch_count()
    fused = np.asarray(dp.execute(b))
    assert dispatch_count() - before == 1, (
        n_shards, "sharded+delta must be ONE executor dispatch",
        dispatch_count() - before)
    # legacy formulation: base shard_map dispatch + a standalone (global-
    # coordinate) delta contribution added as a post-pass
    keys = np.fromiter(dp._overlay, np.int64, count=len(dp._overlay))
    targets = [dp._overlay[int(key)] for key in keys]
    base_sums = dp._base_key_sums(keys)
    in_base = dp.maps.lookup(keys // k, keys % k) >= 0
    dvals = np.array([
        (-base_sums[i] if t is None
         else (t - base_sums[i] if in_base[i] else t))
        for i, t in enumerate(targets)
    ], np.float64)
    plain = build_delta_fringe(keys // k, keys % k, dvals, (m, k), cfg)
    legacy = np.asarray(spmm.execute_sharded(dp.plan, b)) + np.asarray(
        spmm.execute_delta_contribution((m, k), cfg, plain, b)
    )
    assert np.array_equal(fused, legacy), (
        n_shards, "one-dispatch result must be bit-identical to the "
        "two-dispatch post-pass", float(np.abs(fused - legacy).max()))

    dp.compact()
    assert isinstance(dp.plan, spmm.ShardedPlan)
    assert dp.plan.n_shards == n_shards
    assert dp.delta_nnz == 0
    assert_close()
    print(f"{n_shards}-way dynamic parity ok")


if __name__ == "__main__":
    for n in (2, 4):
        check(n)
    print("DYNAMIC PARITY OK")
