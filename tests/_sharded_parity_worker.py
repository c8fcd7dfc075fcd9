"""Simulated-mesh parity worker (run in a subprocess with forced devices).

Asserts ``execute_sharded`` bit-parity (fp32 tolerance) against the
single-device ``execute`` on 1/2/4/8-way meshes, including uneven window
counts, an empty shard, the RHS axis, both pallas fringe tiers, the dataset
oracle panel, and batched operands.  Exits nonzero (via assertion) on any
mismatch; prints ``PARITY OK`` on success.

Launched by tests/test_sharded_executor.py through the ``forced_mesh_run``
conftest fixture, and runnable standalone:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python tests/_sharded_parity_worker.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.hostdevices import force_host_device_count  # noqa: E402 (jax-free)

force_host_device_count(os.environ, 8)

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import spmm  # noqa: E402
from repro.data import graphs  # noqa: E402
from repro.distributed.sharding import make_spmm_mesh  # noqa: E402

ORACLE_PANEL = ["cora", "F1", "reddit"]


def _synthetic(rng, m, k, density=0.08, dense_rows=0):
    a = (rng.rand(m, k) < density).astype(np.float32) * rng.randn(
        m, k
    ).astype(np.float32)
    if dense_rows:
        picks = rng.choice(m, dense_rows, replace=False)
        a[picks] = rng.randn(dense_rows, k).astype(np.float32)
    r, c = np.nonzero(a)
    return r.astype(np.int64), c.astype(np.int64), a[r, c], (m, k)


def _dataset(name, max_dim=512):
    spec = graphs.PAPER_DATASETS[name]
    spec = dataclasses.replace(spec, m=min(spec.m, max_dim),
                               k=min(spec.k, max_dim))
    rows, cols, vals = graphs.generate(spec)
    return rows, cols, vals, (spec.m, spec.k)


def check_parity(rows, cols, vals, shape, n_shards, tag, impl="xla",
                 shard_axis="rows", n=32, budget=None, batch=None):
    cfg = spmm.SpmmConfig(impl=impl, fringe_vmem_budget=budget)
    plan = spmm.prepare(rows, cols, vals, shape, cfg)
    rng = np.random.RandomState(7)
    if batch is None:
        b = jnp.asarray(rng.randn(shape[1], n).astype(np.float32))
    else:
        b = jnp.asarray(rng.randn(batch, shape[1], n).astype(np.float32))
    ref = np.asarray(spmm.execute(plan, b))
    splan = spmm.prepare_sharded(
        rows, cols, vals, shape, make_spmm_mesh(n_shards), cfg,
        shard_axis=shard_axis,
    )
    out = np.asarray(spmm.execute_sharded(splan, b))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5, err_msg=tag)
    print(f"ok {tag}: nsh={n_shards} axis={splan.shard_axis} impl={impl}")


def main():
    assert len(jax.devices()) >= 8, (
        f"worker needs 8 forced host devices, found {len(jax.devices())}"
    )
    rng = np.random.RandomState(0)

    # mesh-size sweep on a mixed core+fringe matrix (8 windows at bm=128)
    rows, cols, vals, shape = _synthetic(rng, 1000, 200, dense_rows=8)
    for nsh in (1, 2, 4, 8):
        check_parity(rows, cols, vals, shape, nsh, f"mesh{nsh}")
    # uneven window counts across shards: 8 windows over 3 shards
    check_parity(rows, cols, vals, shape, 3, "uneven-windows")
    # empty shard: one 100-row window, two shards
    r2, c2, v2, s2 = _synthetic(rng, 100, 64)
    check_parity(r2, c2, v2, s2, 2, "empty-shard")
    # RHS axis (replicated plan, sharded B columns)
    check_parity(rows, cols, vals, shape, 4, "rhs-axis", shard_axis="rhs")
    # pallas fringe tiers under interpret mode
    r3, c3, v3, s3 = _synthetic(rng, 300, 96)
    check_parity(r3, c3, v3, s3, 4, "interp-resident",
                 impl="pallas_interpret")
    check_parity(r3, c3, v3, s3, 4, "interp-ksharded",
                 impl="pallas_interpret", budget=40_000)
    # batched multi-RHS through the sharded executor, both axes
    check_parity(rows, cols, vals, shape, 8, "batched-rows", batch=3)
    check_parity(rows, cols, vals, shape, 8, "batched-rhs",
                 shard_axis="rhs", batch=3)
    # rhs-sharded plans reject an indivisible N instead of miscomputing
    splan = spmm.prepare_sharded(
        rows, cols, vals, shape, make_spmm_mesh(4),
        spmm.SpmmConfig(impl="xla"), shard_axis="rhs",
    )
    try:
        spmm.execute_sharded(splan, jnp.ones((shape[1], 30), jnp.float32))
    except ValueError as e:
        assert "divisible" in str(e), e
        print("ok rhs-indivisible-n rejected")
    else:
        raise AssertionError("indivisible N on a 4-shard rhs plan "
                             "must raise, not miscompute")
    # dataset oracle panel on the full 8-way mesh (acceptance criterion)
    for name in ORACLE_PANEL:
        check_parity(*_dataset(name), 8, f"panel-{name}")

    print("PARITY OK")


if __name__ == "__main__":
    main()
