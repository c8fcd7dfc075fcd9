"""Persistent plan registry: serve warm without re-running ``prepare()``.

Plans (and their dynamic delta state) serialize to disk under the same
atomic manifest + sharded-``.npy`` layout as ``checkpoint/`` — writes go to
a temp directory and ``os.replace`` into place, so a crash mid-save never
corrupts the latest entry.  Layout:

    root/<name>/step_000000NN/
      manifest.json        leaf shapes/dtypes/shard counts + plan metadata
      leaf_flat_values.s0.npy ...   plan leaves
      maps_vals.s0.npy ...          COO->slot update maps
      delta_keys.s0.npy ...         structural-overlay state

Entries are keyed by matrix name and validated on load against (a) the
registry format version, (b) the plan-format version baked into every plan
signature (``core.spmm.PLAN_FORMAT_VERSION``), and (c) the signature
recomputed from the restored plan.  Any mismatch, truncated shard, or
malformed manifest raises :class:`RegistryError` — a clean failure the
caller answers with a fresh ``prepare()`` (see ``load_or_prepare``), never
a wrong answer.

Sharded plans serialize too (``kind: "sharded"``): live mesh/device state
cannot round-trip a process boundary, so the entry stores the canonical
base COO + ``SpmmConfig`` + shard axis (+ the overlay delta state) and
``load``/``warm_start`` re-shard onto a caller-provided (or freshly built)
mesh instead of refusing.  Restoring a sharded entry therefore re-runs
``prepare_sharded`` — the warm start preserves *state* (value updates and
structural deltas), not preprocessing time.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import warnings
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..checkpoint import checkpoint
from ..core import spmm
from ..distributed.sharding import make_spmm_mesh
# RegistryError lives in the shared taxonomy (repro.errors) and is
# re-exported here for the historical import path
from ..errors import RegistryError  # noqa: F401
from ..robust.faults import HARNESS
from .delta import DynamicPlan

REGISTRY_FORMAT_VERSION = 1

# NeutronPlan pytree leaves, serialized by field name
_LEAF_NAMES = (
    "step_window", "step_col", "flat_values", "core_row_map",
    "fringe_rows", "fringe_cols", "fringe_vals", "fringe_row_ids",
    "col_perm", "gather_src_matrix", "gather_src_vector",
    "fringe_kb_chunk", "fringe_kb_rows", "fringe_kb_cols", "fringe_kb_vals",
    "nm_values", "nm_codes", "bitmap_words", "bitmap_values",
)
_MAPS_NAMES = (
    "rows", "cols", "vals", "path", "core_lin", "fringe_pos", "kb_pos",
    "core_lin_sorted", "core_members_sorted", "key_sorted", "key_order",
)


# SpmmConfig fields that only tune *execution* (cache sizing, degradation
# policy), not the prepared plan's structure — excluded from the
# fingerprint so a registry entry stays valid across deployments that
# differ only in these knobs
_EXECUTION_ONLY_CONFIG_FIELDS = ("executor_cache_capacity", "degrade_to_xla")


def coo_fingerprint(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
    shape: Tuple[int, int], config: spmm.SpmmConfig,
) -> str:
    """Content hash binding a registry entry to its source matrix + config.

    Dtypes are canonicalized (int64 indices, float64 values) so the hash of
    a plan's evolved ``to_coo()`` state matches a caller re-registering the
    same logical matrix from narrower host arrays.  Execution-only config
    knobs are excluded — like ``plan.signature()``, the fingerprint keys
    plan *structure*.
    """
    h = hashlib.sha256()
    for a, dtype in ((rows, np.int64), (cols, np.int64),
                     (vals, np.float64)):
        arr = np.ascontiguousarray(np.asarray(a, dtype))
        h.update(arr.tobytes())
    h.update(repr(tuple(shape)).encode())
    cfg = dataclasses.asdict(config)
    for field in _EXECUTION_ONLY_CONFIG_FIELDS:
        cfg.pop(field, None)
    h.update(repr(sorted(cfg.items())).encode())
    return h.hexdigest()


def _safe_name(name: str) -> str:
    if not re.fullmatch(r"[A-Za-z0-9._-]+", name):
        raise RegistryError(
            f"registry names must be filesystem-safe "
            f"([A-Za-z0-9._-]+), got {name!r}"
        )
    return name


class PlanRegistry:
    """On-disk registry of prepared plans keyed by matrix name."""

    def __init__(self, root: str, keep: int = 2):
        self.root = root
        self.keep = keep
        # times load() served an older generation because the newest one
        # failed validation (surfaced through SpmmService.health())
        self.generation_fallbacks = 0
        os.makedirs(root, exist_ok=True)

    def names(self) -> List[str]:
        return sorted(
            d for d in os.listdir(self.root)
            if os.path.isdir(os.path.join(self.root, d))
        )

    def has(self, name: str) -> bool:
        d = os.path.join(self.root, _safe_name(name))
        return os.path.isdir(d) and checkpoint.latest_step(d) is not None

    # -- save ---------------------------------------------------------------
    def save(self, name: str, dplan: DynamicPlan) -> str:
        """Persist a dynamic plan (base arrays, update maps, delta state).

        Sharded plans store the canonical base COO + config + shard axis
        (mesh/device handles cannot round-trip a process); single-device
        plans store the full leaf set so ``load`` skips ``prepare()``.
        """
        _safe_name(name)
        if dplan.is_sharded:
            return self._save_sharded(name, dplan)
        plan = dplan.plan
        maps = plan.update_maps
        tree: Dict[str, np.ndarray] = {}
        for lname, leaf in zip(_LEAF_NAMES, plan.tree_flatten()[0]):
            tree[f"leaf_{lname}"] = np.asarray(leaf)
        for mname in _MAPS_NAMES:
            tree[f"maps_{mname}"] = np.asarray(getattr(maps, mname))
        tree.update(self._overlay_tree(dplan))

        rows, cols, vals = dplan.to_coo()
        meta = {
            "registry_format_version": REGISTRY_FORMAT_VERSION,
            "plan_format_version": spmm.PLAN_FORMAT_VERSION,
            "kind": "plan",
            "name": name,
            "shape": list(plan.shape),
            "config": dataclasses.asdict(plan.config),
            "stats": [list(kv) for kv in plan.stats],
            "fringe_tier": plan.fringe_tier,
            "fringe_bk": plan.fringe_bk,
            "matrix_format": plan.matrix_format,
            "format_params": list(plan.format_params),
            "fringe_buckets": [list(nw) for nw in plan.fringe_buckets],
            "signature": repr(plan.signature()),
            "coo_hash": coo_fingerprint(
                rows, cols, vals, plan.shape, plan.config
            ),
            "compactions": dplan.compactions,
        }
        return self._write_entry(name, tree, meta)

    @staticmethod
    def _overlay_tree(dplan: DynamicPlan) -> Dict[str, np.ndarray]:
        overlay = dplan._overlay
        keys = np.fromiter(overlay, np.int64, count=len(overlay))
        has_target = np.array(
            [overlay[int(key)] is not None for key in keys], bool
        )
        targets = np.array(
            [overlay[int(key)] if overlay[int(key)] is not None else 0.0
             for key in keys], np.float64,
        )
        return {"delta_keys": keys, "delta_has_target": has_target,
                "delta_targets": targets}

    def _write_entry(self, name: str, tree: Dict, meta: Dict) -> str:
        d = os.path.join(self.root, _safe_name(name))
        step = (checkpoint.latest_step(d) or 0) + 1
        try:
            HARNESS.fire("registry_write", context=name)
            return checkpoint.save(
                d, step, tree, meta=meta, num_shards=1, keep=self.keep
            )
        except RegistryError:
            raise
        except Exception as e:
            # any crash mid-save (injected or real) surfaces as a clean
            # RegistryError; the atomic tmp-dir + os.replace layout means
            # the previous generation is still the loadable latest step
            raise RegistryError(
                f"failed to persist registry entry for {name!r}: {e}"
            ) from e

    def _save_sharded(self, name: str, dplan: DynamicPlan) -> str:
        splan = dplan.plan
        maps = splan.update_maps
        # base COO (current values — the fast path advances maps.vals) plus
        # the structural overlay; load re-shards and replays the overlay
        tree: Dict[str, np.ndarray] = {
            "coo_rows": np.asarray(maps.rows, np.int64),
            "coo_cols": np.asarray(maps.cols, np.int64),
            "coo_vals": np.asarray(maps.vals),
        }
        tree.update(self._overlay_tree(dplan))
        rows, cols, vals = dplan.to_coo()
        meta = {
            "registry_format_version": REGISTRY_FORMAT_VERSION,
            "plan_format_version": spmm.PLAN_FORMAT_VERSION,
            "kind": "sharded",
            "name": name,
            "shape": list(splan.shape),
            "config": dataclasses.asdict(splan.config),
            "shard_axis": splan.shard_axis,
            "axis_name": splan.axis_name,
            "n_shards": splan.n_shards,
            "coo_hash": coo_fingerprint(
                rows, cols, vals, splan.shape, splan.config
            ),
            "compactions": dplan.compactions,
        }
        return self._write_entry(name, tree, meta)

    # -- load ---------------------------------------------------------------
    def _read_entry(self, name: str) -> Tuple[Dict, Dict[str, np.ndarray]]:
        """Read the newest valid generation of ``name``.

        Generations are tried newest -> oldest: when the latest step fails
        validation (crash-mid-save remnant, truncated shard, bad manifest)
        the previous retained generation serves instead, with a warning
        and a bump of ``generation_fallbacks`` — warm-start degrades to
        slightly stale state rather than a cold re-prepare.  Only when
        *every* generation fails does the aggregate RegistryError
        propagate.
        """
        d = os.path.join(self.root, _safe_name(name))
        steps = checkpoint.all_steps(d)
        if not steps:
            raise RegistryError(f"no registry entry for {name!r}")
        failures: List[str] = []
        for gen_idx, step in enumerate(reversed(steps)):
            try:
                meta, arrays = self._read_step(name, d, step)
            except RegistryError as e:
                failures.append(f"step_{step:09d}: {e}")
                continue
            if gen_idx:
                self.generation_fallbacks += 1
                warnings.warn(
                    f"registry entry {name!r}: newest generation failed "
                    f"validation; serving step_{step:09d} instead "
                    f"({'; '.join(failures)})",
                    RuntimeWarning, stacklevel=3,
                )
            return meta, arrays
        raise RegistryError(
            f"every retained generation of {name!r} failed validation: "
            + "; ".join(failures)
        )

    def _read_step(
        self, name: str, d: str, step: int
    ) -> Tuple[Dict, Dict[str, np.ndarray]]:
        entry = os.path.join(d, f"step_{step:09d}")
        try:
            HARNESS.fire("registry_read", context=name)
            with open(os.path.join(entry, "manifest.json")) as f:
                manifest = json.load(f)
        except RegistryError:
            raise
        except (OSError, json.JSONDecodeError) as e:
            raise RegistryError(
                f"unreadable manifest for {name!r}: {e}"
            ) from e
        except Exception as e:  # injected faults count as read corruption
            raise RegistryError(
                f"failed reading registry entry for {name!r}: {e}"
            ) from e
        meta = manifest.get("meta", {})
        if meta.get("registry_format_version") != REGISTRY_FORMAT_VERSION:
            raise RegistryError(
                f"{name!r} was saved under registry format "
                f"{meta.get('registry_format_version')}, this build reads "
                f"{REGISTRY_FORMAT_VERSION}"
            )
        if meta.get("plan_format_version") != spmm.PLAN_FORMAT_VERSION:
            raise RegistryError(
                f"{name!r} was saved under plan format "
                f"{meta.get('plan_format_version')}, this build is "
                f"{spmm.PLAN_FORMAT_VERSION}"
            )
        arrays: Dict[str, np.ndarray] = {}
        try:
            for lname, info in manifest["leaves"].items():
                chunks = [
                    np.load(os.path.join(entry, f"{lname}.s{i}.npy"),
                            allow_pickle=False)
                    for i in range(info["shards"])
                ]
                arr = (np.concatenate(chunks, axis=0) if len(chunks) > 1
                       else chunks[0])
                if list(arr.shape) != list(info["shape"]) or (
                        str(arr.dtype) != info["dtype"]):
                    raise RegistryError(
                        f"shard data for {name!r}/{lname} does not match "
                        f"its manifest (got {arr.shape}/{arr.dtype}, "
                        f"manifest says {info['shape']}/{info['dtype']})"
                    )
                arrays[lname] = arr
        except RegistryError:
            raise
        except (OSError, ValueError, KeyError, EOFError) as e:
            raise RegistryError(
                f"corrupt or truncated registry entry for {name!r}: {e}"
            ) from e
        return meta, arrays

    def load(self, name: str, mesh=None, **dynamic_kwargs) -> DynamicPlan:
        """Restore a plan as a :class:`DynamicPlan`.

        Single-device entries reconstruct without any ``prepare()``.
        Sharded entries re-shard onto ``mesh`` (or a freshly built 1-D
        mesh over the stored shard count when ``mesh`` is None) — see the
        module docstring.
        """
        meta, arrays = self._read_entry(name)
        if meta.get("kind", "plan") == "sharded":
            return self._load_sharded(name, meta, arrays, mesh,
                                      **dynamic_kwargs)
        try:
            cfg = spmm.SpmmConfig(**meta["config"])
            stats = tuple(tuple(kv) for kv in meta["stats"])
            shape = tuple(meta["shape"])
            maps = spmm.UpdateMaps(
                shape=shape,
                **{n: arrays[f"maps_{n}"] for n in _MAPS_NAMES},
            )
            leaves = tuple(
                jnp.asarray(arrays[f"leaf_{n}"]) for n in _LEAF_NAMES
            )
            plan = spmm.NeutronPlan(
                *leaves, shape=shape, config=cfg, stats=stats,
                fringe_tier=meta["fringe_tier"],
                fringe_bk=int(meta["fringe_bk"]),
                matrix_format=meta.get("matrix_format", "general"),
                format_params=tuple(meta.get("format_params", (0, 0))),
                fringe_buckets=tuple(
                    (int(n), int(w)) for n, w in meta["fringe_buckets"]),
                update_maps=maps,
            )
        except (KeyError, TypeError, ValueError) as e:
            raise RegistryError(
                f"registry entry for {name!r} does not reconstruct a "
                f"plan: {e}"
            ) from e
        if repr(plan.signature()) != meta.get("signature"):
            raise RegistryError(
                f"restored plan signature for {name!r} disagrees with the "
                "manifest — refusing to serve a structurally inconsistent "
                "plan"
            )
        dplan = DynamicPlan(plan, **dynamic_kwargs)
        self._restore_overlay(dplan, meta, arrays)
        return dplan

    @staticmethod
    def _restore_overlay(dplan: DynamicPlan, meta: Dict, arrays: Dict) -> None:
        keys = arrays["delta_keys"]
        has_target = arrays["delta_has_target"]
        targets = arrays["delta_targets"]
        dplan._overlay = {
            int(key): (float(targets[i]) if has_target[i] else None)
            for i, key in enumerate(keys)
        }
        dplan.compactions = int(meta.get("compactions", 0))

    def _load_sharded(self, name: str, meta: Dict, arrays: Dict, mesh,
                      **dynamic_kwargs) -> DynamicPlan:
        try:
            cfg = spmm.SpmmConfig(**meta["config"])
            shape = tuple(meta["shape"])
            shard_axis = meta["shard_axis"]
            axis_name = meta["axis_name"]
            n_shards = int(meta["n_shards"])
            rows = arrays["coo_rows"]
            cols = arrays["coo_cols"]
            vals = arrays["coo_vals"]
        except (KeyError, TypeError, ValueError) as e:
            raise RegistryError(
                f"sharded registry entry for {name!r} does not reconstruct "
                f"a plan: {e}"
            ) from e
        if mesh is None:
            try:
                mesh = make_spmm_mesh(n_shards, axis_name)
            except ValueError as e:
                raise RegistryError(
                    f"sharded entry {name!r} wants {n_shards} shards and no "
                    f"mesh was provided: {e}"
                ) from e
        splan = spmm.prepare_sharded(
            rows, cols, vals, shape, mesh, cfg,
            shard_axis=shard_axis, axis_name=axis_name,
        )
        dplan = DynamicPlan(splan, **dynamic_kwargs)
        self._restore_overlay(dplan, meta, arrays)
        return dplan

    def stored_coo_hash(self, name: str) -> str:
        meta, _ = self._read_entry(name)
        return meta["coo_hash"]

    def load_or_prepare(
        self,
        name: str,
        rows: np.ndarray,
        cols: np.ndarray,
        vals: np.ndarray,
        shape: Tuple[int, int],
        config: spmm.SpmmConfig = spmm.SpmmConfig(),
        **dynamic_kwargs,
    ) -> DynamicPlan:
        """Warm-start from disk when the stored entry matches this matrix;
        otherwise prepare fresh and persist.  Corruption falls back to
        re-prepare — a damaged registry can cost time, never correctness.
        """
        fp = coo_fingerprint(rows, cols, vals, shape, config)
        if self.has(name):
            try:
                meta, _ = self._read_entry(name)
                if meta.get("coo_hash") == fp:
                    return self.load(name, **dynamic_kwargs)
            except RegistryError:
                pass  # fall through to a fresh prepare
        dplan = DynamicPlan(
            spmm.prepare(rows, cols, vals, shape, config), **dynamic_kwargs
        )
        self.save(name, dplan)
        return dplan

    def load_or_prepare_sharded(
        self,
        name: str,
        rows: np.ndarray,
        cols: np.ndarray,
        vals: np.ndarray,
        shape: Tuple[int, int],
        mesh,
        config: spmm.SpmmConfig = spmm.SpmmConfig(),
        shard_axis: str = "auto",
        axis_name: Optional[str] = None,
        **dynamic_kwargs,
    ) -> DynamicPlan:
        """Sharded counterpart of :func:`load_or_prepare`.

        A matching entry (same COO fingerprint, compatible shard count)
        restores the persisted *state* — value updates and overlay deltas —
        re-sharded onto ``mesh``; anything else prepares fresh and
        persists.  Corruption falls back to re-prepare.
        """
        fp = coo_fingerprint(rows, cols, vals, shape, config)
        n_shards = int(mesh.shape[axis_name or mesh.axis_names[0]])
        if self.has(name):
            try:
                meta, _ = self._read_entry(name)
                if (meta.get("kind") == "sharded"
                        and meta.get("coo_hash") == fp
                        and int(meta.get("n_shards", -1)) == n_shards):
                    return self.load(name, mesh=mesh, **dynamic_kwargs)
            except RegistryError:
                pass  # fall through to a fresh prepare
        dplan = DynamicPlan(
            spmm.prepare_sharded(rows, cols, vals, shape, mesh, config,
                                 shard_axis=shard_axis,
                                 axis_name=axis_name),
            **dynamic_kwargs,
        )
        self.save(name, dplan)
        return dplan
