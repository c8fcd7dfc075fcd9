"""Forced-mesh operator-family parity worker (subprocess, 8 host devices).

Asserts sharded-plan SDDMM parity against the single-device executor on
1/2/4-way meshes (both shard axes, batched, interpret-mode pallas) and
spspmm correctness with sharded inputs.  Prints ``OPERATORS OK`` on
success; launched by tests/test_operator_family.py through the
``forced_mesh_run`` conftest fixture, and runnable standalone:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python tests/_operator_family_worker.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.hostdevices import force_host_device_count  # noqa: E402 (jax-free)

force_host_device_count(os.environ, 8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import spmm  # noqa: E402
from repro.exec import execute_sddmm, execute_spspmm  # noqa: E402
from repro.distributed.sharding import make_spmm_mesh  # noqa: E402


def _coo(rng, m, k, nnz):
    rows = rng.randint(0, m, nnz).astype(np.int64)
    cols = rng.randint(0, k, nnz).astype(np.int64)
    return rows, cols, rng.randn(nnz)


def _dense(rows, cols, vals, shape):
    a = np.zeros(shape, np.float64)
    np.add.at(a, (rows, cols), np.asarray(vals, np.float64))
    return a


def check_sddmm(rows, cols, vals, shape, n_shards, tag, impl="xla",
                shard_axis="rows", d=12, batch=None):
    cfg = spmm.SpmmConfig(impl=impl)
    plan = spmm.prepare(rows, cols, vals, shape, cfg)
    rng = np.random.RandomState(7)
    if batch is None:
        x = jnp.asarray(rng.randn(shape[0], d).astype(np.float32))
        y = jnp.asarray(rng.randn(d, shape[1]).astype(np.float32))
    else:
        x = jnp.asarray(rng.randn(batch, shape[0], d).astype(np.float32))
        y = jnp.asarray(rng.randn(batch, d, shape[1]).astype(np.float32))
    ref = np.asarray(execute_sddmm(plan, x, y))
    splan = spmm.prepare_sharded(rows, cols, vals, shape,
                                 make_spmm_mesh(n_shards), cfg,
                                 shard_axis=shard_axis)
    out = np.asarray(execute_sddmm(splan, x, y))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5, err_msg=tag)
    print(f"ok {tag}: nsh={n_shards} axis={splan.shard_axis} impl={impl}")


def main():
    assert len(jax.devices()) >= 8, (
        f"worker needs 8 forced host devices, found {len(jax.devices())}"
    )
    rng = np.random.RandomState(0)
    rows, cols, vals = _coo(rng, 1000, 200, 4000)
    shape = (1000, 200)

    # mesh-size sweep, both shard axes, batched
    for nsh in (1, 2, 4):
        check_sddmm(rows, cols, vals, shape, nsh, f"sddmm-mesh{nsh}")
    check_sddmm(rows, cols, vals, shape, 4, "sddmm-rhs", shard_axis="rhs")
    check_sddmm(rows, cols, vals, shape, 4, "sddmm-batched", batch=3)
    # interpret-mode pallas gather through the flat sharded path
    r2, c2, v2 = _coo(rng, 300, 96, 900)
    check_sddmm(r2, c2, v2, (300, 96), 2, "sddmm-interp",
                impl="pallas_interpret")

    # spspmm with sharded inputs on a real multi-device mesh
    cfg = spmm.SpmmConfig(impl="xla")
    m, k, n = 400, 200, 160
    ar, ac, av = _coo(rng, m, k, 1500)
    br, bc, bv = _coo(rng, k, n, 1200)
    sa = spmm.prepare_sharded(ar, ac, av, (m, k), make_spmm_mesh(4), cfg)
    sb = spmm.prepare_sharded(br, bc, bv, (k, n), make_spmm_mesh(2), cfg)
    cr, cc, cv, cshape = execute_spspmm(sa, sb)
    ref = _dense(ar, ac, av, (m, k)) @ _dense(br, bc, bv, (k, n))
    got = np.zeros(cshape)
    got[cr, cc] = np.asarray(cv, np.float64)
    scale = np.abs(ref).max() + 1e-9
    assert np.abs(got - ref).max() / scale < 1e-4, "sharded spspmm diverged"
    print("ok spspmm-sharded-inputs")

    print("OPERATORS OK")


if __name__ == "__main__":
    main()
