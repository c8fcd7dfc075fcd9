"""Compile every main-path Pallas kernel for a described TPU v5e.

Interpret mode (the rest of the suite) cannot see what the chip's compiler
refuses: blocks not aligned to the tiling, more VMEM or SMEM than a kernel
may use.  The TPU compiler is installed, and it compiles for a chip that is
described rather than attached, so these tests run on a CPU-only host.
Nothing is executed; a passing compile is not a chip run.

Shapes are the widths ``chip_smoke.py`` runs at — the ogbn-arxiv-scale
graph (169,343 nodes, feature width 128), the ``wiki-RfA`` / ``ogbn-arxiv``
stand-ins and ``dlmc-nm-1-32`` at width 256 — plus fringes of 2^20
nonzeros, past the ~80k-nonzero ceiling a wholly prefetched stream hit,
and the benchmark's ogbn-products plan (width 100, 2.45M rows).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dense_tile_spmm import dense_tile_spmm
from repro.kernels.gather_spmm import STEP, gather_spmm, gather_spmm_ksharded
from repro.kernels.sddmm import dense_tile_sddmm, gather_sddmm
from repro.kernels.structured_spmm import bitmap_tile_spmm, nm_tile_spmm

I32, F32 = jnp.int32, jnp.float32
BIG_FRINGE = 1 << 20

# ogbn-arxiv at its published size, as chip_smoke.py phase a prepares it
ARXIV_K = 169_344          # 169,343 padded to the bk=64 multiple
ARXIV_STEPS = 2_646        # tile steps of its matrix path (bm=128, bk=64)
# its XLA-tier fringe as prepare buckets it, (rows, width) per bucket:
# 169,292 rows (169,320 with each bucket filled to a multiple of 8),
# 1,397,552 slots for 1,051,170 nonzeros
ARXIV_LADDER = ((18_368, 1), (62_280, 2), (43_016, 4), (24_440, 8),
                (11_888, 16), (5_488, 32), (2_304, 64), (920, 128),
                (376, 256), (168, 512), (72, 1_024))

# its sddmm plan: nonzeros on the tile stream and on the fringe
ARXIV_NNZ = 1_165_769
ARXIV_CORE_NNZ = 114_599
ARXIV_FRINGE_NNZ = 1_051_170
ARXIV_CORE_ROWS = 128      # one window of X rows: 2,646 steps, one per k-block

# ogbn-products (chipbench/configs/ogbn-products.json) as prepare lays it
# out: operand width 100, PRODUCTS_STEPS tile steps, and an XLA-tier
# fringe of 86,132,352 slots in 11 buckets whose B panel (1.25 GB padded)
# lives in HBM; unrolled, the large buckets' gathers did not fit the
# v5e's HBM
PRODUCTS_K = 2_449_088     # 2,449,029 padded to the bk=64 multiple
PRODUCTS_WIDTH = 100
PRODUCTS_STEPS = 22_959
PRODUCTS_LADDER = ((1_036_296, 16), (1_039_408, 32), (277_376, 64),
                   (71_824, 128), (18_072, 256), (4_512, 512),
                   (1_200, 1_024), (288, 2_048), (64, 4_096), (24, 8_192),
                   (8, 16_384))


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent cache off.

    A compile for a described chip is written to the cache but cannot be
    read back without the chip, so the cache stays off around these.
    """
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _compile(fn, one_chip, shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _gather(k, rows, bn, nnz):
    return dict(
        fn=gather_spmm,
        shapes=[((nnz,), I32), ((nnz,), I32), ((nnz,), F32), ((k, bn), F32)],
        static=dict(num_rows=rows, bn=bn, chunk=8),
    )


def _ksharded(k, bk, rows, bn, nnz):
    n_chunks = -(-nnz // STEP) + -(-k // bk)  # STEP-padded k-buckets
    stream = n_chunks * STEP
    return dict(
        fn=gather_spmm_ksharded,
        shapes=[((n_chunks,), I32), ((stream,), I32), ((stream,), I32),
                ((stream,), F32), ((k, bn), F32)],
        static=dict(num_rows=rows, bk=bk, bn=bn, chunk=8),
    )


CASES = {
    # phase a matrix path: the largest tile stream the smoke builds
    "dense_tile_spmm-arxiv": dict(
        fn=dense_tile_spmm,
        shapes=[((ARXIV_STEPS,), I32), ((ARXIV_STEPS,), I32),
                ((ARXIV_STEPS, 128, 64), F32), ((ARXIV_K, 128), F32)],
        static=dict(num_windows=1, bm=128, bk=64, bn=128),
    ),
    # phase b: wiki-RfA's resident fringe, and a 2^20-nonzero one
    "gather_spmm-wiki-RfA": _gather(4096, 3624, 256, 34_875),
    "gather_spmm-1M": _gather(4096, 3624, 256, BIG_FRINGE),
    # phase b: the ogbn-arxiv stand-in's K-sharded fringe (bk=2048)
    "gather_spmm_ksharded-ogbn-arxiv": _ksharded(8192, 2048, 8181, 256,
                                                 66_373),
    "gather_spmm_ksharded-1M": _ksharded(ARXIV_K, 2048, 2048, 128,
                                         BIG_FRINGE),
    # phase c: dlmc-nm-1-32, 1:32 packed tiles (gk = bk/m = 2)
    "nm_tile_spmm-dlmc-nm-1-32": dict(
        fn=nm_tile_spmm,
        shapes=[((2048,), I32), ((2048,), I32), ((2048, 128, 2), F32),
                ((2048, 128, 2), I32), ((4096, 256), F32)],
        static=dict(num_windows=32, bm=128, bk=64, bn=256, n_pat=1,
                    m_pat=32),
    ),
    "bitmap_tile_spmm-dlmc": dict(
        fn=bitmap_tile_spmm,
        shapes=[((2048,), I32), ((2048,), I32), ((2048, 128, 2), I32),
                ((2048, 128, 8), F32), ((4096, 256), F32)],
        static=dict(num_windows=32, bm=128, bk=64, bn=256, row_cap=8),
    ),
    # ogbn-products' matrix path: its tile steps over a 2.45M-row panel
    "dense_tile_spmm-products": dict(
        fn=dense_tile_spmm,
        shapes=[((PRODUCTS_STEPS,), I32), ((PRODUCTS_STEPS,), I32),
                ((PRODUCTS_STEPS, 128, 64), F32), ((PRODUCTS_K, 128), F32)],
        static=dict(num_windows=1, bm=128, bk=64, bn=128),
    ),
    # phase a sddmm matrix path: one window panel against Y's k-blocks
    "dense_tile_sddmm-arxiv": dict(
        fn=dense_tile_sddmm,
        shapes=[((ARXIV_STEPS,), I32), ((ARXIV_STEPS,), I32),
                ((128, 128), F32), ((128, ARXIV_K), F32)],
        static=dict(bm=128, bk=64),
    ),
    "gather_sddmm-1M": dict(
        fn=gather_sddmm,
        shapes=[((BIG_FRINGE,), I32), ((BIG_FRINGE,), I32),
                ((4096, 128), F32), ((4096, 128), F32)],
        static=dict(chunk=8),
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    spec = CASES[case]
    _compile(spec["fn"], one_chip, spec["shapes"], **spec["static"])


def test_fused_body_scopes_name_the_tpu_kernels(one_chip):
    """The fused executor compiled for a v5e keeps each kernel's stage in
    its ``op_name``: the tile stream under ``matrix_path``, the K-sharded
    fringe under ``fringe_path`` (the names a chip trace reads)."""
    import re

    import numpy as np

    from repro.core import plan_ir, spmm
    from repro.core.cost_model import fringe_resident_bytes
    from repro.exec.pipeline import build_executor

    rng = np.random.default_rng(0)
    m = k = 2048
    # 256 dense band rows on the matrix path, a random tail on the fringe
    r1 = np.repeat(np.arange(256), 64)
    c1 = (r1 + np.tile(np.arange(64), 256)) % k
    r2 = rng.integers(256, m, 6000)
    c2 = rng.integers(0, k, 6000)
    rows, cols = np.concatenate([r1, r2]), np.concatenate([c1, c2])
    vals = rng.standard_normal(rows.size).astype(np.float32)
    cfg = spmm.SpmmConfig(
        impl="pallas", fringe_vmem_budget=fringe_resident_bytes(k, m, 128) - 1)
    plan = spmm.prepare(rows, cols, vals, (m, k), cfg)
    assert plan.has_core and plan.fringe_tier == "ksharded"
    leaves = (*plan_ir.plan_leaves(plan), jnp.zeros((k, 128), F32))
    args = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
            for x in leaves]
    text = build_executor(plan.signature()).lower(*args).compile().as_text()
    kernels = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = line.split(" = ", 1)[0].strip().lstrip("%")
        op_name = re.search(r'op_name="([^"]*)"', line)
        kernels[name] = op_name.group(1) if op_name else ""
    scope_of = {re.sub(r"\.\d+$", "", n): o for n, o in kernels.items()}
    assert "/matrix_path/" in scope_of["dense_tile_spmm"], kernels
    assert "/fringe_path/" in scope_of["gather_spmm_ksharded"], kernels


@pytest.mark.parametrize("k, width, ladder", [
    (ARXIV_K, 128, ARXIV_LADDER),
    (PRODUCTS_K, PRODUCTS_WIDTH, PRODUCTS_LADDER),
], ids=["ogbn-arxiv", "ogbn-products"])
def test_bucketed_fringe_compiles_without_sort_or_scatter(one_chip, k, width,
                                                          ladder):
    """The XLA fringe at a configuration's bucket ladder and operand width,
    as the fused body runs it (B padded to the 128 lanes of ``bn``):
    gathers and reductions only, no sort and no scatter (the unbucketed
    stream compiles to both), within the chip's HBM."""
    from repro.kernels import ops

    rows = sum(n for n, _ in ladder)
    slots = sum(n * w for n, w in ladder)

    @jax.jit
    def fringe(r, c, v, b):
        bp = jnp.pad(b, ((0, 0), (0, 128 - width)))
        return ops.fringe_spmm(r, c, v, bp, num_rows=rows, bn=128,
                               impl="pallas", tier="xla",
                               buckets=ladder)[:, :width]

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((slots,), I32), ((slots,), I32), ((slots,), F32), ((k, width), F32))]
    text = fringe.lower(*args).compile().as_text()
    assert " gather(" in text
    assert " sort(" not in text and " scatter(" not in text


def test_sddmm_merge_compiles_without_full_length_gathers(one_chip):
    """The sddmm fused body at ogbn-arxiv's plan, compiled for a v5e: the
    merge gathers only the core nonzeros out of the (T, bm, bk) tile
    stream, in the layout the kernel wrote it (no flat relayout of the
    stream), and places both paths' values by one sort; no gather
    produces a value per nonzero."""
    import re

    import numpy as np

    from repro.core import plan_ir, spmm
    from repro.exec.pipeline import build_executor

    # a small mixed plan supplies the signature; the shapes are arxiv's
    rng = np.random.default_rng(0)
    rows, cols = rng.integers(0, 300, 3000), rng.integers(0, 300, 3000)
    rows[:600] = 5  # one dense row for the tile stream
    plan = spmm.prepare(rows, cols, np.ones(3000), (300, 300),
                        spmm.SpmmConfig(impl="pallas", bn=128, alpha=0.5))
    assert plan.has_core and plan.has_fringe
    sig = list(plan.signature())
    sig[1] = (ARXIV_K - 1, ARXIV_K - 1)
    sig = plan_ir.tag_op(tuple(sig), "sddmm", ARXIV_NNZ, ARXIV_FRINGE_NNZ,
                         None)
    n, nc, nf = ARXIV_NNZ, ARXIV_CORE_NNZ, ARXIV_FRINGE_NNZ
    shapes = [(ARXIV_STEPS,), (ARXIV_STEPS,), (ARXIV_CORE_ROWS,),
              (ARXIV_K - 1,), (n,), (n,), (nc,), (n,), (nf,), (nf,)]
    args = [jax.ShapeDtypeStruct(s, I32, sharding=one_chip) for s in shapes]
    args += [jax.ShapeDtypeStruct(s, F32, sharding=one_chip)
             for s in ((ARXIV_K - 1, 128), (128, ARXIV_K - 1))]
    text = build_executor(sig).lower(*args).compile().as_text()
    assert "dense_tile_sddmm" in text
    gathers = [line.split(" gather(")[0] for line in text.splitlines()
               if " gather(" in line]
    assert not [g for g in gathers if f"f32[{n}]" in g], gathers
    assert [g for g in gathers if f"f32[{nc}]" in g], gathers
    # the core gather reads the stream as the kernel wrote it, unflattened
    stream = re.escape(f"f32[{ARXIV_STEPS},128,64]")
    assert re.search(rf"\(param[^)]*: {stream}[^)]*\) -> f32\[{nc}\]", text)
    assert f"f32[{ARXIV_STEPS * 128 * 64}]" not in text
    assert " sort(" in text
