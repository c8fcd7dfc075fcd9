"""Compiled-HLO analysis: collective bytes + roofline terms.

``collective_bytes`` parses the compiled (post-SPMD) HLO text and sums the
result-shape bytes of every collective op (all-reduce payload == result
bytes; all-gather result == total gathered bytes crossing links; the
approximation is recorded as-is in EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

from ..core.cost_model import DEVICE_PEAKS

# the dry-run compiles for the production TPU v5e meshes
_V5E = DEVICE_PEAKS["TPU v5 lite"]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9\[\],{}:()#\s]*?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes in a compiled HLO module."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue  # async -done carries the same payload as -start
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        shapes = _SHAPE_RE.findall(m.group(1))
        total = sum(_shape_bytes(d, s) for d, s in shapes)
        out[kind] += total
        out["count"] += 1
    return out


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def roofline_fraction(self) -> float:
        """Fraction of the step the compute term occupies at the bound —
        1.0 means perfectly compute-bound (roofline-saturating)."""
        if self.bound_s <= 0:
            return 0.0
        return self.compute_s / self.bound_s


def roofline_terms(
    flops_pd: float, bytes_pd: float, coll_bytes_pd: float
) -> RooflineTerms:
    return RooflineTerms(
        compute_s=flops_pd / _V5E.flops_per_s,
        memory_s=bytes_pd / _V5E.bytes_per_s,
        collective_s=coll_bytes_pd / _V5E.ici_bytes_per_s,
        flops_per_device=flops_pd,
        bytes_per_device=bytes_pd,
        collective_bytes_per_device=coll_bytes_pd,
    )
