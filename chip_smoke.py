#!/usr/bin/env python
"""Bring-up smoke test: the sparse operators and SpmmService on one TPU.

    python chip_smoke.py                # one chip: phases a-d
    python chip_smoke.py --four-chips   # four chips: the sharded path only

Runs the system's main path through the entry points a user calls
(``repro.sparse.from_coo`` / ``spmm`` / ``sddmm``, ``repro.serve.SpmmService``,
``prepare_sharded`` for four chips) with ``impl="pallas"`` and
``degrade_to_xla=False``: a kernel that fails raises instead of silently
running on XLA.  Every input is generated from ``--seed``.

Phases (one chip):

  a. ogbn-arxiv at its published size (169,343 nodes, ~1.05M edges,
     power-law), feature width 128: from_coo -> spmm -> sddmm.
  b. the PAPER_DATASETS stand-ins wiki-RfA (resident fringe tier) and
     ogbn-arxiv (K-sharded fringe tier) at width 256.
  c. dlmc-nm-1-32 (4096^2, N:M 1:32) at width 256: the N:M lane.
  d. SpmmService serving phase a's graph: 8 requests, flushed, fetched.

Each phase prints the tier each engine path ran on, the Pallas kernels in
the traced program, the error against a float64 host reference, and smoke
timings (first call with compile, steady per call) that are not benchmark
numbers.  After each phase the executor health table must show no failure
or fallback and no dispatch may be ``:degraded``.  The last line of stdout
is one JSON object naming the device.  Without a TPU the script exits 2
before printing anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# f32 operands may take a single bf16 pass through the MXU (relative
# rounding 2^-8 per product), so results are held to max|err| / max|ref|
REL_TOL = 2e-2
STEADY_CALLS = 5

# ogbn-arxiv's published size (OGB node-property leaderboard: 169,343
# nodes, 1,166,243 edges); the power-law generator lands near 1.05M
ARXIV_NODES = 169_343
ARXIV_DEGREE = 6.89
FEATURES = 128
STANDIN_WIDTH = 256
REQUESTS = 8

MATRIX_KERNEL = {"general": "dense_tile_spmm", "nm": "nm_tile_spmm",
                 "bitmap": "bitmap_tile_spmm"}
FRINGE_KERNEL = {"resident": "gather_spmm", "ksharded": "gather_spmm_ksharded",
                 "xla": "xla"}


class SmokeFailure(RuntimeError):
    pass


# --- references and checks ---------------------------------------------------


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


def check_error(what, out, ref):
    out = np.asarray(out, np.float64)
    if out.shape != ref.shape or not np.all(np.isfinite(out)):
        raise SmokeFailure(f"{what}: shape {out.shape} (want {ref.shape}) "
                           "or non-finite values")
    abs_err = float(np.max(np.abs(out - ref))) if ref.size else 0.0
    rel_err = abs_err / max(float(np.max(np.abs(ref))), 1e-30)
    line = (f"{what}: max_abs_err={abs_err:.3e} rel_err={rel_err:.3e} "
            f"(tol rel<={REL_TOL:g})")
    if rel_err > REL_TOL:
        raise SmokeFailure(line)
    return line


def check_health(tag):
    """No accelerated executor failed or fell back, nothing degraded."""
    from repro.exec.health import HEALTH
    from repro.obs import REGISTRY

    snap = HEALTH.snapshot()
    kinds = sorted(s["labels"]["kind"] for s in
                   REGISTRY.snapshot()["exec_dispatches_total"]["series"])
    degraded = [k for k in kinds if k.endswith(":degraded")]
    if snap["failures"] or snap["fallbacks"] or degraded:
        raise SmokeFailure(f"[{tag}] health {snap}, degraded {degraded}")
    return f"[{tag}] health: 0 failures, 0 fallbacks, dispatch kinds {kinds}"


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


def timed(fn):
    """(result, first-call seconds incl. compile, steady ms per call)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first_s = time.perf_counter() - t0
    steady = []
    for _ in range(STEADY_CALLS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        steady.append((time.perf_counter() - t0) * 1e3)
    return out, first_s, statistics.median(steady)


def timing_line(first_s, steady_ms):
    return (f"smoke timing (not a benchmark): first call {first_s:.2f} s "
            f"incl. compile, steady {steady_ms:.3f} ms/call "
            f"(median of {STEADY_CALLS})")


# --- phases ------------------------------------------------------------------


@dataclasses.dataclass
class Graph:
    name: str
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    shape: tuple


def make_graph(spec):
    from repro.data.graphs import generate

    rows, cols, vals = generate(spec)
    return Graph(spec.name, rows, cols, vals, (spec.m, spec.k))


def arxiv_spec(seed, nodes=ARXIV_NODES):
    from repro.data.graphs import GraphSpec

    return GraphSpec("ogbn-arxiv", nodes, nodes, ARXIV_DEGREE, "power_law",
                     1.3, seed)


def spmm_phase(tag, cfg, g, width, rng, expect_fringe=None,
               expect_format=None):
    """from_coo -> spmm, checked against float64; returns (matrix, lines)."""
    import jax.numpy as jnp
    import repro.sparse as sp

    a = sp.from_coo(g.rows, g.cols, g.vals, g.shape, config=cfg)
    st = a.plan.stats_dict
    fmt, tier = st["matrix_format"], st["fringe_tier"]
    if expect_fringe and tier != expect_fringe:
        raise SmokeFailure(f"[{tag}] {g.name}: fringe tier {tier!r}, "
                           f"expected {expect_fringe!r}")
    if expect_format and fmt != expect_format:
        raise SmokeFailure(f"[{tag}] {g.name}: matrix format {fmt!r}, "
                           f"expected {expect_format!r}")
    b = jnp.asarray(rng.standard_normal((g.shape[1], width), np.float32))
    kernels = pallas_kernels(lambda x: sp.spmm(a, x), b)
    want = {MATRIX_KERNEL[fmt]} if st["num_steps"] and st["core_nnz"] else set()
    if tier != "xla" and st["fringe_nnz"]:
        want.add(FRINGE_KERNEL[tier])
    if not want <= set(kernels):
        raise SmokeFailure(f"[{tag}] {g.name}: kernels {kernels}, "
                           f"missing {sorted(want - set(kernels))}")
    out, first_s, steady_ms = timed(lambda: sp.spmm(a, b))
    ref = spmm_ref(g.rows, g.cols, g.vals, g.shape, b)
    lines = [
        f"[{tag}] {g.name} {g.shape[0]}x{g.shape[1]} nnz={g.rows.size} "
        f"width={width}",
        f"[{tag}]   spmm tiers: matrix={MATRIX_KERNEL[fmt]} "
        f"({st['num_steps']} tile steps, {st['core_nnz']} nnz) "
        f"fringe={FRINGE_KERNEL[tier] if st['fringe_nnz'] else 'none'} "
        f"({st['fringe_nnz']} nnz"
        + (f", bk={st['fringe_bk']}" if tier == "ksharded" else "") + ")",
        f"[{tag}]   spmm pallas kernels in program: {kernels}",
        f"[{tag}]   " + check_error("spmm", out, ref),
        f"[{tag}]   spmm " + timing_line(first_s, steady_ms),
    ]
    return a, lines


def sddmm_phase(tag, a, g, width, rng):
    import jax.numpy as jnp
    import repro.sparse as sp

    x = jnp.asarray(rng.standard_normal((g.shape[0], width), np.float32))
    y = jnp.asarray(rng.standard_normal((width, g.shape[1]), np.float32))
    out, first_s, steady_ms = timed(lambda: sp.sddmm(a, x, y))
    # traced after a real call: the plan caches its sddmm maps on first use
    kernels = pallas_kernels(lambda u, v: sp.sddmm(a, u, v), x, y)
    fringe = "gather_sddmm" if "gather_sddmm" in kernels else "xla"
    ref = sddmm_ref(g.rows, g.cols, x, y)
    return [
        f"[{tag}]   sddmm tiers: matrix="
        + ("dense_tile_sddmm" if "dense_tile_sddmm" in kernels else "none")
        + f" fringe={fringe}",
        f"[{tag}]   sddmm pallas kernels in program: {kernels}",
        f"[{tag}]   " + check_error("sddmm", out, ref),
        f"[{tag}]   sddmm " + timing_line(first_s, steady_ms),
    ]


def serve_phase(tag, cfg, g, width, rng, n_requests=REQUESTS):
    import jax.numpy as jnp
    from repro.serve import SpmmService

    lines = [f"[{tag}] SpmmService serving {g.name} ({g.rows.size} nnz), "
             f"{n_requests} requests of width {width}"]
    panels = [rng.standard_normal((g.shape[1], width), np.float32)
              for _ in range(n_requests)]
    svc = SpmmService(cfg, max_batch=4)
    try:
        svc.register(g.name, g.rows, g.cols, g.vals, g.shape)
        t0 = time.perf_counter()
        tickets = [svc.submit(g.name, jnp.asarray(p)) for p in panels]
        done = svc.flush()
        outs = [np.asarray(svc.fetch(t)) for t in tickets]
        wall_s = time.perf_counter() - t0
    finally:
        svc.close()
    if done != n_requests:
        raise SmokeFailure(f"[{tag}] flush completed {done}/{n_requests}")
    worst = max((check_error(f"request {i}", o,
                             spmm_ref(g.rows, g.cols, g.vals, g.shape, p))
                 for i, (o, p) in enumerate(zip(outs, panels))),
                key=lambda s: float(s.split("rel_err=")[1].split()[0]))
    lines += [
        f"[{tag}]   all {n_requests} fetched; worst {worst}",
        f"[{tag}]   smoke timing (not a benchmark): submit+flush+fetch "
        f"{wall_s:.2f} s incl. compile",
        f"[{tag}]   service closed cleanly",
    ]
    return lines


def one_chip_phases(impl, seed, arxiv_nodes=ARXIV_NODES, standins=None,
                    nm_spec=None, vmem_budget=None):
    """Phases a-d; yields printable lines.  Sizes are arguments so the
    same code runs at a tiny size on the CPU (tests/test_chip_smoke.py)."""
    from repro.core.plan_ir import SpmmConfig
    from repro.data.graphs import PAPER_DATASETS

    rng = np.random.default_rng(seed)
    cfg = SpmmConfig(impl=impl, degrade_to_xla=False, seed=seed,
                     fringe_vmem_budget=vmem_budget)
    cfg_feat = dataclasses.replace(cfg, bn=FEATURES)

    arxiv = make_graph(arxiv_spec(seed, arxiv_nodes))
    a, lines = spmm_phase("a", cfg_feat, arxiv, FEATURES, rng)
    yield from lines
    yield from sddmm_phase("a", a, arxiv, FEATURES, rng)
    yield check_health("a")

    if standins is None:
        standins = [(dataclasses.replace(PAPER_DATASETS[n],
                                         seed=PAPER_DATASETS[n].seed + seed),
                     tier)
                    for n, tier in (("wiki-RfA", "resident"),
                                    ("ogbn-arxiv", "ksharded"))]
    for spec, tier in standins:
        _, lines = spmm_phase("b", cfg, make_graph(spec), STANDIN_WIDTH, rng,
                              expect_fringe=tier)
        yield from lines
    yield check_health("b")

    if nm_spec is None:
        base = PAPER_DATASETS["dlmc-nm-1-32"]
        nm_spec = dataclasses.replace(base, seed=base.seed + seed)
    _, lines = spmm_phase("c", cfg, make_graph(nm_spec), STANDIN_WIDTH, rng,
                          expect_format="nm")
    yield from lines
    yield check_health("c")

    yield from serve_phase("d", cfg_feat, arxiv, FEATURES, rng)
    yield check_health("d")


def four_chip_phase(impl, seed, arxiv_nodes=ARXIV_NODES, n_shards=4):
    """phase a's graph rows-sharded over n_shards devices vs one device."""
    import jax
    import jax.numpy as jnp
    import repro.sparse as sp
    from repro.core.plan_ir import SpmmConfig
    from repro.core.spmm import prepare_sharded
    from repro.distributed.sharding import make_spmm_mesh

    rng = np.random.default_rng(seed)
    cfg = SpmmConfig(impl=impl, degrade_to_xla=False, seed=seed, bn=FEATURES)
    g = make_graph(arxiv_spec(seed, arxiv_nodes))
    b = jnp.asarray(rng.standard_normal((g.shape[1], FEATURES), np.float32))
    mesh = make_spmm_mesh(n_shards)
    splan = prepare_sharded(g.rows, g.cols, g.vals, g.shape, mesh,
                            config=cfg, shard_axis="rows")
    sharded = sp.from_plan(splan)
    single = sp.from_coo(g.rows, g.cols, g.vals, g.shape, config=cfg)
    kernels = pallas_kernels(lambda x: sp.spmm(sharded, x), b)
    out4, first_s, steady_ms = timed(lambda: sp.spmm(sharded, b))
    out1 = jax.block_until_ready(sp.spmm(single, b))

    leaf_devs = {d for leaf in splan.leaves for d in leaf.sharding.device_set}
    out_devs = out4.sharding.device_set
    if len(leaf_devs) != n_shards or len(out_devs) != n_shards:
        raise SmokeFailure(f"[4chip] plan leaves on {len(leaf_devs)} and "
                           f"output on {len(out_devs)} devices, want "
                           f"{n_shards} each")
    diff = float(np.max(np.abs(np.asarray(out4) - np.asarray(out1))))
    ref = spmm_ref(g.rows, g.cols, g.vals, g.shape, b)
    yield (f"[4chip] {g.name} {g.shape[0]}x{g.shape[1]} nnz={g.rows.size} "
           f"rows-sharded over {n_shards} devices, width {FEATURES}")
    yield (f"[4chip]   shard_axis={splan.shard_axis}; pallas kernels in "
           f"program: {kernels}")
    if not kernels:
        raise SmokeFailure("[4chip] no Pallas kernel in the sharded program")
    yield (f"[4chip]   plan leaves on {len(leaf_devs)} distinct devices, "
           f"output on {len(out_devs)} distinct devices")
    yield "[4chip]   sharded " + check_error("spmm", out4, ref)
    yield "[4chip]   single-device " + check_error("spmm", out1, ref)
    yield (f"[4chip]   max |sharded - single-device| = {diff:.3e} "
           f"(rel {diff / max(float(np.max(np.abs(ref))), 1e-30):.3e})")
    if diff > REL_TOL * float(np.max(np.abs(ref))):
        raise SmokeFailure("[4chip] sharded result disagrees with one device")
    yield "[4chip]   sharded " + timing_line(first_s, steady_ms)
    yield check_health("4chip")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 4-chip sharded path and its "
                        "single-device comparison")
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax sees {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, jax sees {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    phases = (four_chip_phase("pallas", args.seed) if args.four_chips
              else one_chip_phases("pallas", args.seed))
    for line in phases:
        print(line, flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
