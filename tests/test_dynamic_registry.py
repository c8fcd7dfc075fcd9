"""Persistent plan registry: warm-start, delta state, corruption handling.

The robustness contract: a damaged or stale registry entry may cost a
re-``prepare()`` (``load_or_prepare`` falls back), but it must never be
silently served — truncated shards, mangled manifests, and format-version
drift all raise a clean :class:`RegistryError` first.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spmm
from repro.dynamic import (
    DynamicPlan, GraphDelta, PlanRegistry, RegistryError,
)
from repro.serve import SpmmService
from conftest import make_sparse

CFG = spmm.SpmmConfig(impl="xla")


def _graph(rng, m=80, k=64):
    a, rows, cols, vals = make_sparse(rng, m, k, 0.08, n_dense_rows=3)
    return a, rows, cols, vals


def _entry_dir(root, name):
    d = os.path.join(root, name)
    steps = sorted(x for x in os.listdir(d) if x.startswith("step_"))
    return os.path.join(d, steps[-1])


def test_registry_round_trip_without_prepare(rng, tmp_path):
    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, CFG))
    b = jnp.asarray(rng.randn(64, 12).astype(np.float32))
    want = np.asarray(dp.execute(b))
    reg.save("g", dp)

    before = spmm.prepare_call_count()
    restored = reg.load("g")
    assert spmm.prepare_call_count() == before  # no prepare() on restore
    assert np.array_equal(np.asarray(restored.execute(b)), want)
    # restored plans stay updatable (maps round-tripped)
    idx = rng.choice(rows.size, 5, replace=False)
    nv = rng.randn(5)
    restored.update(GraphDelta.updates(rows[idx], cols[idx], nv))
    vals2 = vals.copy().astype(np.float64)
    vals2[idx] = nv
    ref = spmm.prepare(rows, cols, vals2, a.shape, CFG)
    assert np.array_equal(np.asarray(restored.plan.fringe_vals),
                          np.asarray(ref.fringe_vals))


def test_registry_round_trips_delta_state(rng, tmp_path):
    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, CFG),
                     auto_compact=False)
    dense = np.zeros(a.shape, np.float64)
    np.add.at(dense, (rows, cols), vals)
    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 7, replace=False)
    iv = rng.randn(7)
    dp.update(GraphDelta.inserts(zr[pick], zc[pick], iv))
    dense[zr[pick], zc[pick]] += iv
    dp.update(GraphDelta.deletes(rows[:3], cols[:3]))
    dense[rows[:3], cols[:3]] = 0
    reg.save("g", dp)

    restored = reg.load("g")
    assert restored.delta_nnz == dp.delta_nnz
    b = jnp.asarray(rng.randn(64, 8).astype(np.float32))
    out = np.asarray(restored.execute(b))
    expect = dense @ np.asarray(b, np.float64)
    scale = np.abs(expect).max() + 1e-9
    assert np.abs(out - expect).max() / scale < 1e-4


def test_load_or_prepare_warm_and_cold(rng, tmp_path):
    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    dp = reg.load_or_prepare("g", rows, cols, vals, a.shape, CFG)
    assert reg.has("g")
    before = spmm.prepare_call_count()
    warm = reg.load_or_prepare("g", rows, cols, vals, a.shape, CFG)
    assert spmm.prepare_call_count() == before  # warm: no prepare
    b = jnp.asarray(rng.randn(64, 8).astype(np.float32))
    assert np.array_equal(np.asarray(warm.execute(b)),
                          np.asarray(dp.execute(b)))
    # a different matrix under the same name must NOT reuse the entry
    vals2 = vals.copy()
    vals2[0] += 1.0
    cold = reg.load_or_prepare("g", rows, cols, vals2, a.shape, CFG)
    assert spmm.prepare_call_count() > before
    a2 = a.astype(np.float64).copy()
    a2[rows[0], cols[0]] += 1.0
    out = np.asarray(cold.execute(b))
    expect = a2 @ np.asarray(b, np.float64)
    assert np.abs(out - expect).max() / (np.abs(expect).max() + 1e-9) < 1e-4


def test_truncated_shard_raises_then_falls_back(rng, tmp_path):
    """A truncated shard file is a clean RegistryError, and load_or_prepare
    answers it with a fresh prepare — never a wrong answer."""
    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    reg.save("g", DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, CFG)))
    entry = _entry_dir(str(tmp_path), "g")
    victim = os.path.join(entry, "leaf_flat_values.s0.npy")
    size = os.path.getsize(victim)
    with open(victim, "r+b") as f:
        f.truncate(size // 2)
    with pytest.raises(RegistryError, match="corrupt|truncated"):
        reg.load("g")
    before = spmm.prepare_call_count()
    dp = reg.load_or_prepare("g", rows, cols, vals, a.shape, CFG)
    assert spmm.prepare_call_count() > before  # fell back to prepare
    b = jnp.asarray(rng.randn(64, 8).astype(np.float32))
    out = np.asarray(dp.execute(b))
    expect = a.astype(np.float64) @ np.asarray(b, np.float64)
    assert np.abs(out - expect).max() / (np.abs(expect).max() + 1e-9) < 1e-4


def test_shape_mismatched_shard_is_rejected(rng, tmp_path):
    """A shard that np.load accepts but that disagrees with its manifest
    (e.g. a partial write of a valid smaller array) is still rejected."""
    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    reg.save("g", DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, CFG)))
    entry = _entry_dir(str(tmp_path), "g")
    np.save(os.path.join(entry, "maps_vals.s0.npy"),
            np.zeros(3, np.float32))
    with pytest.raises(RegistryError, match="does not match its manifest"):
        reg.load("g")


def test_corrupt_manifest_raises(rng, tmp_path):
    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    reg.save("g", DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, CFG)))
    entry = _entry_dir(str(tmp_path), "g")
    with open(os.path.join(entry, "manifest.json"), "w") as f:
        f.write("{not json")
    with pytest.raises(RegistryError, match="manifest"):
        reg.load("g")


def test_format_version_mismatch_raises(rng, tmp_path):
    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    reg.save("g", DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, CFG)))
    entry = _entry_dir(str(tmp_path), "g")
    mpath = os.path.join(entry, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["meta"]["plan_format_version"] = -1
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(RegistryError, match="plan format"):
        reg.load("g")


def test_missing_entry_and_bad_names(tmp_path):
    reg = PlanRegistry(str(tmp_path))
    with pytest.raises(RegistryError, match="no registry entry"):
        reg.load("nope")
    with pytest.raises(RegistryError, match="filesystem-safe"):
        reg.save("../evil", None)


def _sharded_dplan(rng, rows, cols, vals, shape, shard_axis="rows"):
    from repro.distributed.sharding import make_spmm_mesh

    splan = spmm.prepare_sharded(rows, cols, vals, shape, make_spmm_mesh(1),
                                 CFG, shard_axis=shard_axis)
    return DynamicPlan(splan, auto_compact=False)


def test_sharded_plan_round_trips_by_resharding(rng, tmp_path):
    """A sharded entry stores COO + config + shard axis and load() rebuilds
    the plan by re-sharding — mutations (value fast path + structural
    overlay) survive the round trip (closes the ROADMAP refusal)."""
    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    dp = _sharded_dplan(rng, rows, cols, vals, a.shape)
    # mutate both layers before persisting
    dense = a.astype(np.float64).copy()
    dp.update(GraphDelta.updates(rows[:3], cols[:3], [5.0, -1.5, 2.25]))
    dense[rows[:3], cols[:3]] = [5.0, -1.5, 2.25]
    zr, zc = np.nonzero(dense == 0)
    dp.update(GraphDelta.inserts(zr[:4], zc[:4], [1.0, 2.0, 3.0, 4.0]))
    dense[zr[:4], zc[:4]] += [1.0, 2.0, 3.0, 4.0]
    reg.save("g", dp)

    restored = reg.load("g")  # mesh=None: rebuilt from the stored n_shards
    assert restored.is_sharded
    assert restored.plan.n_shards == 1
    assert restored.delta_nnz == dp.delta_nnz
    b = jnp.asarray(rng.randn(a.shape[1], 8).astype(np.float32))
    out = np.asarray(restored.execute(b))
    expect = dense @ np.asarray(b, np.float64)
    assert np.abs(out - expect).max() / (np.abs(expect).max() + 1e-9) < 1e-4


def test_sharded_truncated_shard_raises_then_falls_back(rng, tmp_path):
    """Corruption handling mirrors the single-device entries: truncated
    data raises a clean RegistryError and load_or_prepare_sharded answers
    with a fresh prepare_sharded."""
    from repro.distributed.sharding import make_spmm_mesh

    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    reg.save("g", _sharded_dplan(rng, rows, cols, vals, a.shape))
    entry = _entry_dir(str(tmp_path), "g")
    victim = os.path.join(entry, "coo_vals.s0.npy")
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) // 2)
    with pytest.raises(RegistryError, match="corrupt|truncated"):
        reg.load("g")
    dp = reg.load_or_prepare_sharded(
        "g", rows, cols, vals, a.shape, make_spmm_mesh(1),
        CFG, shard_axis="rows",
    )
    b = jnp.asarray(rng.randn(a.shape[1], 8).astype(np.float32))
    out = np.asarray(dp.execute(b))
    expect = a.astype(np.float64) @ np.asarray(b, np.float64)
    assert np.abs(out - expect).max() / (np.abs(expect).max() + 1e-9) < 1e-4


def test_sharded_manifest_and_version_corruption(rng, tmp_path):
    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    reg.save("g", _sharded_dplan(rng, rows, cols, vals, a.shape))
    entry = _entry_dir(str(tmp_path), "g")
    mpath = os.path.join(entry, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    # version drift is rejected before any array is touched
    manifest["meta"]["plan_format_version"] = -1
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(RegistryError, match="plan format"):
        reg.load("g")
    # a mangled manifest is rejected too
    with open(mpath, "w") as f:
        f.write("{not json")
    with pytest.raises(RegistryError, match="manifest"):
        reg.load("g")


def test_sharded_warm_start_matches_fingerprint(rng, tmp_path):
    """load_or_prepare_sharded restores mutated state when the caller's COO
    matches the stored fingerprint, and prepares fresh when it doesn't."""
    from repro.distributed.sharding import make_spmm_mesh

    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    mesh = make_spmm_mesh(1)
    dp = reg.load_or_prepare_sharded("g", rows, cols, vals, a.shape, mesh,
                                     CFG, shard_axis="rows")
    dense = a.astype(np.float64).copy()
    zr, zc = np.nonzero(dense == 0)
    dp.update(GraphDelta.inserts(zr[:2], zc[:2], [7.0, -3.0]))
    dense[zr[:2], zc[:2]] += [7.0, -3.0]
    reg.save("g", dp)

    # the fingerprint binds to the *evolved* logical matrix (to_coo), so a
    # caller re-registering that state warm-starts with the overlay intact
    er, ec, ev = dp.to_coo()
    warm = reg.load_or_prepare_sharded("g", er, ec, ev, a.shape, mesh,
                                       CFG, shard_axis="rows")
    assert warm.delta_nnz == 2
    b = jnp.asarray(rng.randn(a.shape[1], 8).astype(np.float32))
    out = np.asarray(warm.execute(b))
    expect = dense @ np.asarray(b, np.float64)
    assert np.abs(out - expect).max() / (np.abs(expect).max() + 1e-9) < 1e-4

    # different COO -> fresh prepare, no stale overlay
    vals2 = vals.copy()
    vals2[0] += 1.0
    cold = reg.load_or_prepare_sharded("g2", rows, cols, vals2, a.shape,
                                       mesh, CFG, shard_axis="rows")
    assert cold.delta_nnz == 0


def test_service_warm_starts_from_registry(rng, tmp_path):
    """The acceptance path: a new service process restores from disk
    without calling prepare() and serves correct results immediately."""
    a, rows, cols, vals = _graph(rng)
    b = rng.randn(64, 8).astype(np.float32)

    reg = PlanRegistry(str(tmp_path))
    svc1 = SpmmService(CFG, max_batch=4, registry=reg)
    svc1.register("g", rows, cols, vals, a.shape)
    t = svc1.submit("g", b)
    svc1.flush()
    want = np.asarray(svc1.fetch(t))

    # "restart": a fresh service over the same registry
    svc2 = SpmmService(CFG, max_batch=4, registry=reg)
    before = spmm.prepare_call_count()
    svc2.register("g", rows, cols, vals, a.shape)
    assert spmm.prepare_call_count() == before  # warm start, no prepare
    assert svc2.stats.warm_starts == 1
    t2 = svc2.submit("g", b)
    svc2.flush()
    assert np.array_equal(np.asarray(svc2.fetch(t2)), want)

    # name-only restore (no COO in hand at startup)
    svc3 = SpmmService(CFG, max_batch=4, registry=reg)
    before = spmm.prepare_call_count()
    svc3.warm_start("g")
    assert spmm.prepare_call_count() == before
    t3 = svc3.submit("g", b)
    svc3.flush()
    assert np.array_equal(np.asarray(svc3.fetch(t3)), want)


def test_service_updates_persist_across_restart(rng, tmp_path):
    a, rows, cols, vals = _graph(rng)
    b = rng.randn(64, 8).astype(np.float32)
    reg = PlanRegistry(str(tmp_path))
    svc1 = SpmmService(CFG, max_batch=4, registry=reg)
    svc1.register("g", rows, cols, vals, a.shape)
    dense = np.zeros(a.shape, np.float64)
    np.add.at(dense, (rows, cols), vals)
    zr, zc = np.nonzero(dense == 0)
    pick = rng.choice(zr.size, 5, replace=False)
    iv = rng.randn(5)
    svc1.update_matrix("g", GraphDelta.inserts(zr[pick], zc[pick], iv))
    dense[zr[pick], zc[pick]] += iv

    svc2 = SpmmService(CFG, max_batch=4, registry=reg)
    before = spmm.prepare_call_count()
    svc2.warm_start("g")
    assert spmm.prepare_call_count() == before
    t = svc2.submit("g", b)
    svc2.flush()
    out = np.asarray(svc2.fetch(t))
    expect = dense @ np.asarray(b, np.float64)
    assert np.abs(out - expect).max() / (np.abs(expect).max() + 1e-9) < 1e-4


def test_registry_retention_keeps_newest(rng, tmp_path):
    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path), keep=2)
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, CFG))
    for _ in range(4):
        reg.save("g", dp)
    d = os.path.join(str(tmp_path), "g")
    steps = [x for x in os.listdir(d) if x.startswith("step_")]
    assert len(steps) == 2  # checkpoint-style GC
    reg.load("g")  # newest entry still loads


# ---------------------------------------------------------------------------
# write-path crash consistency: a save that dies mid-write must leave the
# previous generation as the loadable latest step (atomic tmp + os.replace)
# ---------------------------------------------------------------------------
def test_crash_during_shard_write_preserves_previous_generation(
        rng, tmp_path, monkeypatch):
    from repro.checkpoint import checkpoint as ckpt

    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, CFG))
    reg.save("g", dp)

    real_save, calls = np.save, []

    def dying_save(path, arr, **kw):
        calls.append(path)
        if len(calls) >= 2:  # first shard lands, the next write crashes
            raise OSError("disk died mid-shard")
        return real_save(path, arr, **kw)

    monkeypatch.setattr(ckpt.np, "save", dying_save)
    with pytest.raises(RegistryError, match="persist"):
        reg.save("g", dp)
    monkeypatch.setattr(ckpt.np, "save", real_save)

    # the half-written generation never replaced into place: generation 1
    # is still the latest step and loads without any fallback
    b = jnp.asarray(rng.randn(64, 8).astype(np.float32))
    restored = reg.load("g")
    assert np.array_equal(np.asarray(restored.execute(b)),
                          np.asarray(dp.execute(b)))
    assert reg.generation_fallbacks == 0


def test_interrupted_manifest_replace_preserves_previous_generation(
        rng, tmp_path, monkeypatch):
    from repro.checkpoint import checkpoint as ckpt

    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path))
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, CFG))
    reg.save("g", dp)

    def dying_replace(src, dst):
        raise OSError("power loss during rename")

    monkeypatch.setattr(ckpt.os, "replace", dying_replace)
    with pytest.raises(RegistryError, match="persist"):
        reg.save("g", dp)
    monkeypatch.undo()

    restored = reg.load("g")
    assert restored.plan.shape == a.shape
    assert reg.generation_fallbacks == 0


def test_corrupt_newest_generation_falls_back_with_warning(rng, tmp_path):
    import warnings

    a, rows, cols, vals = _graph(rng)
    reg = PlanRegistry(str(tmp_path), keep=2)
    dp = DynamicPlan(spmm.prepare(rows, cols, vals, a.shape, CFG))
    b = jnp.asarray(rng.randn(64, 8).astype(np.float32))
    want = np.asarray(dp.execute(b))
    reg.save("g", dp)
    reg.save("g", dp)

    # mangle the newest generation the way a torn write would
    with open(os.path.join(_entry_dir(str(tmp_path), "g"),
                           "manifest.json"), "w") as f:
        f.write('{"meta": {')

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        restored = reg.load("g")
    assert np.array_equal(np.asarray(restored.execute(b)), want)
    assert reg.generation_fallbacks == 1
    assert any(issubclass(w.category, RuntimeWarning)
               and "serving step_" in str(w.message) for w in caught)

    # once every retained generation is damaged, the failure aggregates
    with open(os.path.join(str(tmp_path), "g", "step_000000001",
                           "manifest.json"), "w") as f:
        f.write("not json")
    with pytest.raises(RegistryError, match="every retained generation"):
        reg.load("g")
