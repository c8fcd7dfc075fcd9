"""Published peaks of the chips the benchmark runs on, keyed by device_kind.

A copy of ``repro.core.cost_model.DEVICE_PEAKS``, kept with the benchmark
so that a roofline share does not move when the program's table does.
float32 operands at JAX's default matmul precision take one bfloat16 pass
through the MXU, so the bfloat16 peak bounds them.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,   # bfloat16 MXU
        "bytes_per_s": 819e9,    # HBM
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e)",
    },
}


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a kind missing from the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; add it "
                       "to chipbench/peaks.py with its source") from None


def least_time_s(flops: float, nbytes: float, device_kind: str):
    """(least seconds the chip could take, the bound: 'compute'|'memory')."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["flops_per_s"], nbytes / p["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
