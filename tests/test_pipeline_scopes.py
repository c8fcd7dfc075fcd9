"""The fused bodies' stage scopes reach the compiled program.

Every stage of the fused SpMM and SDDMM bodies runs under one of the
``jax.named_scope`` names in ``exec.pipeline.SCOPES``; the compiled HLO
carries each as a component of its operations' ``op_name`` metadata, which
is what names a device operation's stage on a profiler trace.  A scope is
metadata only: the scoped executor's output is the dense oracle's.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan_ir, spmm
from repro.exec.pipeline import SCOPES, build_executor
from conftest import make_sparse


def _scopes_in(hlo_text: str) -> set:
    """Scope names among the ``op_name`` components; a transform wraps
    the name it maps over, as in ``vmap(matrix_path)``."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo_text):
        for part in op_name.split("/"):
            name = part.rstrip(")").rpartition("(")[2]
            if name in SCOPES:
                found.add(name)
    return found


def _plan(rng, impl):
    # alpha=0.5 sends the sparse tail to the fringe: both paths carry work
    a, rows, cols, vals = make_sparse(rng, 96, 80, 0.07, n_dense_rows=4)
    cfg = spmm.SpmmConfig(impl=impl, bn=128, alpha=0.5)
    plan = spmm.prepare(rows, cols, vals, a.shape, cfg)
    assert plan.has_core and plan.has_fringe
    return a, (rows, cols), plan


@pytest.mark.parametrize("flavor", ["spmm", "spmm_batched", "sddmm"])
def test_compiled_executor_carries_every_scope(rng, flavor):
    impl = "pallas_interpret" if flavor == "sddmm" else "xla"
    a, (rows, cols), plan = _plan(rng, impl)
    if flavor == "sddmm":
        smaps = plan_ir.build_sddmm_maps(plan)
        sig = plan_ir.tag_op(plan.signature(), "sddmm", smaps.nnz,
                             smaps.nnz_f, plan.config.fringe_vmem_budget)
        x = jnp.asarray(rng.randn(96, 8).astype(np.float32))
        y = jnp.asarray(rng.randn(8, 80).astype(np.float32))
        args = (*plan_ir.sddmm_body_leaves(plan, smaps), x, y)
        fn = build_executor(sig)
        expect = (np.asarray(x) @ np.asarray(y))[rows, cols]
    else:
        batch = 2 if flavor == "spmm_batched" else None
        shape = (80, 16) if batch is None else (batch, 80, 16)
        b = jnp.asarray(rng.randn(*shape).astype(np.float32))
        args = (*plan_ir.plan_leaves(plan), b)
        fn = build_executor(plan.signature(), batch=batch)
        expect = np.einsum("mk,...kn->...mn", a, np.asarray(b))
    compiled = jax.jit(fn).lower(*args).compile()
    assert _scopes_in(compiled.as_text()) == set(SCOPES)
    np.testing.assert_allclose(np.asarray(fn(*args)), expect,
                               rtol=1e-4, atol=1e-4)

