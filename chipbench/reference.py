"""The plain float64 references and the comparison that decides ``correct``.

``spmm_ref``, ``sddmm_ref``, ``check_health`` and ``pallas_kernels`` are
copies of those in ``chip_smoke.py``, kept here so that the yardstick does
not move when the program does.  The references import nothing of the
program and take only the run's inputs: the COO triplets and the operand
panels.
"""
from __future__ import annotations

import numpy as np


def spmm_ref(rows, cols, vals, shape, b):
    import scipy.sparse

    a = scipy.sparse.csr_matrix(
        (vals.astype(np.float64), (rows, cols)), shape=shape)
    return a @ np.asarray(b, np.float64)


def sddmm_ref(rows, cols, x, y, block=1 << 17):
    x = np.asarray(x, np.float64)
    yt = np.asarray(y, np.float64).T
    out = np.empty(rows.size, np.float64)
    for s in range(0, rows.size, block):
        r, c = rows[s:s + block], cols[s:s + block]
        out[s:s + block] = np.einsum("ij,ij->i", x[r], yt[c])
    return out


def errors(out, ref) -> dict:
    """The numbers compared: worst gap over the largest reference value
    (``rel_err``) and the gap's norm over the reference's (``rms_err``).
    A wrong shape or a value that is not finite reads infinitely wrong."""
    out = np.asarray(out, np.float64)
    if out.shape != ref.shape or not np.all(np.isfinite(out)):
        return {"rel_err": float("inf"), "rms_err": float("inf")}
    diff = out - ref
    return {
        "rel_err": float(np.max(np.abs(diff)))
        / max(float(np.max(np.abs(ref))), 1e-30),
        "rms_err": float(np.linalg.norm(diff))
        / max(float(np.linalg.norm(ref)), 1e-30),
    }


def worst(readings) -> dict:
    """Per number, the worst reading over the compared answers."""
    readings = list(readings)
    if not readings:
        return {}
    return {k: max(r[k] for r in readings) for k in readings[0]}


def check_health():
    """Reasons the run was not the accelerated path; empty when it was.

    No accelerated executor failed or fell back, and no dispatch ran on a
    ``:degraded`` signature."""
    from repro.exec.health import HEALTH
    from repro.obs import REGISTRY

    snap = HEALTH.snapshot()
    kinds = sorted(s["labels"]["kind"] for s in
                   REGISTRY.snapshot()["exec_dispatches_total"]["series"])
    degraded = [k for k in kinds if k.endswith(":degraded")]
    bad = []
    if snap["failures"] or snap["fallbacks"]:
        bad.append(f"health failures={snap['failures']} "
                   f"fallbacks={snap['fallbacks']}")
    if degraded:
        bad.append(f"degraded dispatch kinds {degraded}")
    return bad


def pallas_kernels(fn, *args):
    """Names of the Pallas kernels in the program ``fn(*args)`` traces."""
    import jax
    from jax.extend.core import ClosedJaxpr, Jaxpr

    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return sorted(set(names))
