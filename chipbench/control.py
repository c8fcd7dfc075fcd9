"""The control: the plain reference put in the program's place, computed
in the precision one step below the configurations' float32 (bfloat16).

The configurations state float32 storage at JAX's default matmul
precision, so the step that would tempt a later change is bfloat16: values,
operands, products and sums in bfloat16, each rounding made explicit so
that no backend keeps a sum in float32.  ``correct`` must come out false
when the control answers in the program's place; ``calibrate.py`` reads
the control's numbers on the chip, and ``tests/test_chipbench_control.py``
keeps the check at a size a test run holds.

``in_program_place`` patches the program's entry underneath the harness
(``repro.exec.api.execute`` / ``execute_sddmm``, ``SpmmService._execute``)
for the duration of a ``with`` block; the replacement is built from the
run's inputs alone, never from the program's plan.
"""
from __future__ import annotations

import contextlib


def _bf16(x):
    """Round to bfloat16, kept in a float32 array.  ``reduce_precision``
    is an explicit rounding that the compiler may not drop as excess
    precision, as it may drop a round trip through a bfloat16 array."""
    from jax import lax

    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _slot_bands(rows, m):
    """The nonzeros (row-major) regrouped so that each row's sum runs in
    order, one add at a time: slot ``j`` holds the ``j``-th nonzero of
    every row that has one.  Slots are packed into bands of padded width
    (a slot's width at most halves inside a band), so the padding is at
    most the nonzeros themselves.  Padding points at a dummy row ``m`` and
    a dummy nonzero ``nnz`` of value 0.  Returns a list of (index, row)
    arrays of shape (slots in band, width)."""
    import numpy as np

    rows = np.asarray(rows, np.int64)
    nnz = rows.size
    start = np.searchsorted(rows, np.arange(m))
    slot = np.arange(nnz) - start[rows]
    order = np.lexsort((rows, slot))
    sizes = np.bincount(slot)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    bands, j = [], 0
    while j < sizes.size:
        width = int(sizes[j])
        stop = j
        while stop < sizes.size and 2 * sizes[stop] > width:
            stop += 1
        idx = np.full((stop - j, width), nnz, np.int64)
        row = np.full((stop - j, width), m, np.int64)
        for t in range(j, stop):
            sel = order[offs[t]:offs[t + 1]]
            idx[t - j, :sel.size] = sel
            row[t - j, :sel.size] = rows[sel]
        bands.append((idx.astype(np.int32), row.astype(np.int32)))
        j = stop
    return bands


def bf16_spmm(rows, cols, vals, shape):
    """``b -> A @ b`` in bfloat16 for a (K, N) or (batch, K, N) operand:
    values and operands rounded to bfloat16, each product rounded, and
    each row summed in order with every partial sum rounded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    m = int(shape[0])
    bands = [(jnp.asarray(i), jnp.asarray(r)) for i, r in
             _slot_bands(rows, m)]
    c = jnp.asarray(np.append(cols, 0), jnp.int32)
    v = jnp.asarray(np.append(vals, 0), jnp.float32)

    # the pattern goes in as arguments: a constant that large would be
    # folded into the compiled program
    @jax.jit
    def one(b, bands, c, v):
        b = _bf16(b.astype(jnp.float32))
        v = _bf16(v)
        acc = jnp.zeros((m + 1, b.shape[1]), jnp.float32)
        for idx, row in bands:
            def step(t, acc, idx=idx, row=row):
                i, r = idx[t], row[t]
                prod = _bf16(v[i][:, None] * b[c[i]])
                return acc.at[r].set(_bf16(acc[r] + prod))
            acc = jax.lax.fori_loop(0, idx.shape[0], step, acc)
        return acc[:m]

    many = jax.jit(jax.vmap(one, in_axes=(0, None, None, None)))
    return lambda b: (one if b.ndim == 2 else many)(b, bands, c, v)


def bf16_sddmm(rows, cols):
    """``(x, y) -> (x @ y)[rows, cols]`` in bfloat16: operands rounded,
    each product rounded, the sum over the width taken in order with every
    partial sum rounded."""
    import jax
    import jax.numpy as jnp

    r = jnp.asarray(rows, jnp.int32)
    c = jnp.asarray(cols, jnp.int32)

    @jax.jit
    def f(x, y, r, c):
        x = _bf16(x.astype(jnp.float32))
        yt = _bf16(y.astype(jnp.float32)).T

        def step(d, acc):
            return _bf16(acc + _bf16(x[r, d] * yt[c, d]))
        return jax.lax.fori_loop(0, x.shape[1], step,
                                 jnp.zeros(r.shape, jnp.float32))

    return lambda x, y: f(x, y, r, c)


@contextlib.contextmanager
def captured_inputs():
    """Yields a list that receives each run's ``harness.Inputs``."""
    import harness

    seen = []
    orig = harness.make_inputs

    def make(*a, **k):
        inp = orig(*a, **k)
        seen.append(inp)
        return inp

    harness.make_inputs = make
    try:
        yield seen
    finally:
        harness.make_inputs = orig


@contextlib.contextmanager
def patched(obj, name, make_replacement):
    """Replace ``obj.name`` by ``make_replacement(original)`` for a block."""
    orig = getattr(obj, name)
    setattr(obj, name, make_replacement(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


@contextlib.contextmanager
def in_program_place(op: str, kind: str):
    """Answer the harness's calls with the bfloat16 control."""
    import repro.exec.api as api
    from repro.serve.spmm_service import SpmmService

    with captured_inputs() as seen:
        cache = {}

        def control():
            inp = seen[-1]
            if id(inp) not in cache:
                cache.clear()
                cache[id(inp)] = (
                    bf16_spmm(inp.rows, inp.cols, inp.vals, inp.shape)
                    if op == "spmm" else bf16_sddmm(inp.rows, inp.cols))
            return cache[id(inp)]

        if kind == "open":
            ctx = patched(SpmmService, "_execute", lambda orig: (
                lambda self, name, plan, stacked: control()(stacked)))
        elif op == "spmm":
            ctx = patched(api, "execute",
                          lambda orig: lambda plan, b: control()(b))
        else:
            ctx = patched(api, "execute_sddmm",
                          lambda orig: lambda plan, x, y: control()(x, y))
        with ctx:
            yield
