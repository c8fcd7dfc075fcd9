"""The one generator that every traffic mix (``traffic/<mix>.json``) feeds.

Two kinds of mix:

- ``closed``: one caller that keeps the device fed: it keeps ``ahead``
  calls queued behind the one it waits for, so that the device has work
  while the host dispatches or stands still.  It waits for the oldest
  call before it sends another; ``ahead`` 0 keeps one call in flight.
  Calls rotate through ``panels`` operand panels, so no result can be
  reused between calls.
- ``open``: independent users.  ``rate_per_s`` requests a second arrive
  on a schedule, whether or not earlier ones have finished.  The gaps are
  the quantiles of an exponential distribution at that rate, in an order
  drawn from the mix's ``arrival_seed``: every run replays the same
  arrival schedule, as a recorded trace would, so a seed changes neither
  the amount of work nor when it comes.  The run's seed draws the operand
  panels, which panel each request carries, and which answers are
  compared.  One loop serves them: it hands the service the requests
  that are due, at most ``max_batch`` at a time, flushes and waits for
  their answers; requests that come due meanwhile wait in the loop, and
  their latency counts from when they were due.

Keys a mix may hold: ``kind``, ``op`` (``spmm`` | ``sddmm``), ``panels``,
``samples`` (answers compared with the reference), ``ahead`` (closed
mixes, default 0), ``rate_per_s``, ``max_batch`` and ``arrival_seed``
(open mixes).  The operand width is the configuration's.
"""
from __future__ import annotations

import numpy as np

KINDS = ("closed", "open")
OPS = ("spmm", "sddmm")


def validate(mix: dict, name: str) -> dict:
    if mix.get("kind") not in KINDS:
        raise ValueError(f"mix {name}: kind must be one of {KINDS}")
    if mix.get("op") not in OPS:
        raise ValueError(f"mix {name}: op must be one of {OPS}")
    if int(mix.get("panels", 0)) < 1 or int(mix.get("samples", 0)) < 1:
        raise ValueError(f"mix {name}: panels and samples must be >= 1")
    if int(mix.get("ahead", 0)) < 0:
        raise ValueError(f"mix {name}: ahead must be >= 0")
    if mix["kind"] == "open":
        if mix["op"] != "spmm":
            raise ValueError(f"mix {name}: open mixes serve spmm requests")
        if float(mix.get("rate_per_s", 0)) <= 0:
            raise ValueError(f"mix {name}: rate_per_s must be > 0")
        if int(mix.get("max_batch", 0)) < 1:
            raise ValueError(f"mix {name}: max_batch must be >= 1")
        if "arrival_seed" not in mix:
            raise ValueError(f"mix {name}: an open mix needs arrival_seed")
    return mix


def arrivals(rate_per_s: float, seconds: float, rng) -> np.ndarray:
    """Due times (s from the window's start) of an open mix's requests.

    ``round(rate * seconds)`` requests whose gaps are the exponential
    quantiles at ``(i + 0.5) / n``, shuffled, and scaled so that the last
    falls inside the window."""
    n = max(int(round(rate_per_s * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_per_s
    gaps = rng.permutation(gaps)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    return np.cumsum(gaps)


def sample_calls(samples: int, rng, first: int = 64) -> set:
    """Indices of the closed-loop calls whose answers are compared: drawn
    from the seed among the first ``first`` calls (the last call of the
    window is compared as well)."""
    return {int(i) for i in rng.choice(first, min(samples, first),
                                       replace=False)}


def sample_requests(samples: int, n_requests: int, rng) -> set:
    """Indices of the open-loop requests whose answers are compared."""
    k = min(samples, n_requests)
    return {int(i) for i in rng.choice(n_requests, k, replace=False)}
