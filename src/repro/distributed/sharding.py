"""Named-sharding rules: DP / FSDP / TP (+ pod axis) for every param family.

The model code is sharding-agnostic; it calls ``constrain(x, *logical)`` at
a few activation points.  The launcher installs ``AxisRules`` mapping
logical axes onto mesh axes, and ``param_specs`` derives a PartitionSpec
pytree for any model's params by leaf name — this is what feeds
``jax.jit(in_shardings=...)`` in the dry-run/train/serve launchers.

Defaults implement Megatron-style 1D TP on the "model" axis combined with
ZeRO-3/FSDP parameter sharding on the "data" axis; the batch runs DP over
("pod", "data").  All of it is config — the §Perf hillclimb swaps rules
without touching model code.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class AxisRules:
    batch_axes: Tuple[str, ...] = ("data",)   # DP axes for the batch dim
    fsdp_axes: Tuple[str, ...] = ("data",)    # param-shard axes (ZeRO-3)
    tp_axis: Optional[str] = "model"          # tensor-parallel axis
    seq_axis: Optional[str] = None            # sequence-parallel residual
    expert_axis: Optional[str] = None         # MoE expert parallelism
    moe_fsdp: bool = True                     # False: MoE weights DP-replicated
                                              # (required by shard_map dispatch)

    @property
    def batch(self):
        return self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]

    @property
    def fsdp(self):
        if not self.fsdp_axes:
            return None
        return self.fsdp_axes if len(self.fsdp_axes) > 1 else self.fsdp_axes[0]


# ---------------------------------------------------------------------------
# shard_map + PartitionSpec helpers (used by the sharded SpMM
# executor, core/spmm.py: per-shard plan leaves ride a leading mesh axis,
# RHS-column sharding rides a trailing one)
# ---------------------------------------------------------------------------
def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the sharded SpMM
    bodies wrap pallas_call, which has no replication rule."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_spec(rank: int, pos: int, axis: Optional[str]) -> P:
    """Rank-``rank`` PartitionSpec with ``axis`` at dimension ``pos``."""
    dims: list = [None] * rank
    dims[pos] = axis
    return P(*dims)


def leading_axis_spec(rank: int, axis: Optional[str]) -> P:
    return axis_spec(rank, 0, axis)


def trailing_axis_spec(rank: int, axis: Optional[str]) -> P:
    return axis_spec(rank, rank - 1, axis)


def replicated_spec(rank: int) -> P:
    return P(*([None] * rank))


_ACTIVE: Dict[str, Any] = {"rules": None}


@contextlib.contextmanager
def use_rules(rules: Optional[AxisRules]):
    prev = _ACTIVE["rules"]
    _ACTIVE["rules"] = rules
    try:
        yield
    finally:
        _ACTIVE["rules"] = prev


def active_rules() -> Optional[AxisRules]:
    return _ACTIVE["rules"]


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply a sharding constraint if rules are installed; no-op otherwise.

    Logical names: "batch", "seq", "embed", "vocab", "heads", "ff", "expert".
    """
    rules = _ACTIVE["rules"]
    if rules is None:
        return x
    resolved = []
    for name in logical:
        if name == "batch":
            resolved.append(rules.batch)
        elif name == "seq":
            resolved.append(rules.seq_axis)
        elif name in ("heads", "ff", "vocab"):
            resolved.append(rules.tp_axis)
        elif name == "expert":
            resolved.append(rules.expert_axis)
        else:
            resolved.append(None)
    # a mesh axis may appear at most once; keep the first occurrence
    seen = set()
    deduped = []
    for r in resolved:
        axes = (r,) if isinstance(r, str) else tuple(r or ())
        if any(a in seen for a in axes):
            deduped.append(None)
            continue
        seen.update(axes)
        deduped.append(r)
    return jax.lax.with_sharding_constraint(x, P(*deduped))


# ---------------------------------------------------------------------------
# parameter specs by leaf name
# ---------------------------------------------------------------------------
_COL_PARALLEL = {  # (.., in, out) -> (.., fsdp, tp): out-dim TP-sharded
    "wq", "wk", "wv", "w_in", "w_gate", "in_proj", "shared_w_in",
    "shared_w_gate", "adapter", "lm_head", "frontend_proj",
}
_ROW_PARALLEL = {  # (.., in, out) -> (.., tp, fsdp): in-dim TP-sharded
    "wo", "w_out", "out_proj", "shared_w_out",
}
_REPLICATED = {"router"}  # small; gathered everywhere anyway


def _axes_size(axes, sizes: Dict[str, int]) -> int:
    if axes is None:
        return 1
    axs = (axes,) if isinstance(axes, str) else tuple(axes)
    n = 1
    for a in axs:
        n *= sizes.get(a, 1)
    return n


def _fit(dim: int, axes, sizes: Dict[str, int], allow_uneven: bool = False):
    """Return ``axes`` if dim is shardable over them, else None."""
    if axes is None:
        return None
    n = _axes_size(axes, sizes)
    if n <= 1:
        return None
    if dim % n == 0 or (allow_uneven and dim >= n):
        return axes
    return None


def _leaf_spec(
    path: str, shape: Tuple[int, ...], rules: AxisRules, sizes: Dict[str, int]
) -> P:
    name = path.split("/")[-1]
    rank = len(shape)
    lead = rank - 2
    fsdp, tp = rules.fsdp, rules.tp_axis
    moe_leaf = "moe" in path.split("/") and name in ("w_in", "w_gate", "w_out")
    if name == "table":  # embedding (V, D) — vocab may shard unevenly
        return P(_fit(shape[0], tp, sizes, allow_uneven=True),
                 _fit(shape[1], fsdp, sizes))
    if name == "lm_head":  # (D, V)
        return P(_fit(shape[0], fsdp, sizes),
                 _fit(shape[1], tp, sizes, allow_uneven=True))
    if rank <= 1 or name in _REPLICATED:
        return P(*([None] * rank))
    if moe_leaf and rules.expert_axis:
        # (.., E, d1, d2): expert-parallel; inner in-dim FSDP-sharded
        spec = [None] * rank
        spec[-3] = _fit(shape[-3], rules.expert_axis, sizes)
        spec[-2] = _fit(shape[-2], fsdp, sizes) if name not in _ROW_PARALLEL else None
        return P(*spec)
    if moe_leaf and not rules.moe_fsdp:
        # shard_map dispatch: ff-sharded over TP only, DP-replicated
        spec = [None] * rank
        if name in _ROW_PARALLEL:
            spec[-2] = _fit(shape[-2], tp, sizes)
        else:
            spec[-1] = _fit(shape[-1], tp, sizes)
        return P(*spec)
    if name in _COL_PARALLEL:
        return P(*([None] * lead), _fit(shape[-2], fsdp, sizes),
                 _fit(shape[-1], tp, sizes))
    if name in _ROW_PARALLEL:
        return P(*([None] * lead), _fit(shape[-2], tp, sizes),
                 _fit(shape[-1], fsdp, sizes))
    if name == "conv_w":  # (K, C)
        return P(*([None] * lead), None, _fit(shape[-1], tp, sizes))
    return P(*([None] * rank))


def _cache_leaf_spec(
    path: str, shape: Tuple[int, ...], rules: AxisRules, sizes: Dict[str, int]
) -> P:
    """Decode-cache specs: shard batch over DP and heads/channels over TP."""
    name = path.split("/")[-1]
    rank = len(shape)
    if name in ("k", "v"):  # (.., B, S, KV, hd)
        # hd-sharded (not kv): hd divides the TP degree for every arch, and
        # the decode attention path constrains to the same layout
        # (layers.blockwise_attention) — a kv/hd mismatch would reshard the
        # whole cache every decoded token.
        lead = rank - 4
        batch = _batch_axes_fit(rules, shape[lead], sizes)
        hd_tp = _fit(shape[lead + 3], rules.tp_axis, sizes)
        return P(*([None] * lead), batch, None, None, hd_tp)
    if name == "ssd":  # (.., B, H, P, N)
        lead = rank - 4
        batch = _batch_axes_fit(rules, shape[lead], sizes)
        h_tp = _fit(shape[lead + 1], rules.tp_axis, sizes)
        return P(*([None] * lead), batch, h_tp, None, None)
    if name == "conv":  # (.., B, t, C)
        lead = rank - 3
        batch = _batch_axes_fit(rules, shape[lead], sizes)
        c_tp = _fit(shape[lead + 2], rules.tp_axis, sizes)
        return P(*([None] * lead), batch, None, c_tp)
    return P(*([None] * rank))


def _batch_axes_fit(rules: AxisRules, dim: int, sizes: Dict[str, int]):
    """Longest prefix of batch axes whose product divides ``dim``."""
    axes = []
    n = 1
    for a in rules.batch_axes:
        if dim % (n * sizes.get(a, 1)) == 0:
            axes.append(a)
            n *= sizes.get(a, 1)
        else:
            break
    if not axes:
        return None
    return tuple(axes) if len(axes) > 1 else axes[0]


def param_specs(params: Any, rules: AxisRules, sizes: Optional[Dict[str, int]] = None) -> Any:
    """PartitionSpec pytree matching ``params``."""
    sizes = sizes or {}

    def spec_of(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        return _leaf_spec("/".join(str(k) for k in keys), leaf.shape, rules, sizes)

    return jax.tree_util.tree_map_with_path(spec_of, params)


def cache_specs(cache: Any, rules: AxisRules, sizes: Optional[Dict[str, int]] = None) -> Any:
    sizes = sizes or {}

    def spec_of(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        return _cache_leaf_spec("/".join(str(k) for k in keys), leaf.shape, rules, sizes)

    return jax.tree_util.tree_map_with_path(spec_of, cache)


def named_shardings(params: Any, rules: AxisRules, mesh) -> Any:
    from jax.sharding import NamedSharding
    specs = param_specs(params, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_spec(
    rules: AxisRules,
    batch_dim: int,
    extra_dims: int = 1,
    sizes: Optional[Dict[str, int]] = None,
) -> P:
    """Batch sharding over the longest divisible prefix of the DP axes."""
    axes = _batch_axes_fit(rules, batch_dim, sizes or {})
    return P(axes, *([None] * extra_dims))
