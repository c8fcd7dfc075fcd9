"""Forced host device count for simulated-mesh runs (jax-free module).

The CPU device count is fixed when jax creates its backend, so multi-device
CPU coverage requires ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
in the environment before the first device query.  This module stays
importable without touching jax — the CPU tools call it first thing in
``main`` (benchmarks/collect_sharded_json.py), and it builds subprocess
environments (the ``forced_mesh_run`` fixture).  Nothing on the
library's import path calls it: on a chip the platform is never forced.
"""
from __future__ import annotations

from typing import MutableMapping

FORCE_FLAG = "xla_force_host_platform_device_count"


def force_host_device_count(
    env: MutableMapping[str, str], n_devices: int = 8
) -> MutableMapping[str, str]:
    """Pin CPU and request ``n_devices`` forced host devices in ``env``.

    ``env`` is ``os.environ`` (in-process preamble, pre-jax-import) or a
    subprocess environment dict.  A pre-existing forced count is kept —
    callers layering on top of an outer forced-mesh run (e.g. the CI mesh
    leg) must not fight it.  Returns ``env`` for chaining.
    """
    env.setdefault("JAX_PLATFORMS", "cpu")
    flags = env.get("XLA_FLAGS", "")
    if FORCE_FLAG not in flags:
        env["XLA_FLAGS"] = f"{flags} --{FORCE_FLAG}={n_devices}".strip()
    return env
