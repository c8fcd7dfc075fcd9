"""The executor LRU, the per-signature health table and the sddmm operand
checks of ``repro.exec``, called directly."""
import jax.numpy as jnp
import pytest

from repro.errors import PlanBuildError
from repro.exec.api import validate_sddmm_operands
from repro.exec.cache import ExecutorCache
from repro.exec.health import HealthTable


class _Builds:
    """Builder double: returns a fresh token per build and counts builds."""

    def __init__(self):
        self.n = 0

    def __call__(self, tag):
        def build():
            self.n += 1
            return (tag, self.n)
        return build


def _counts(cache):
    return cache.hits, cache.misses, cache.evictions


# --- ExecutorCache ---------------------------------------------------------


def test_cache_hit_refreshes_recency_and_lru_entry_is_evicted():
    cache, builds = ExecutorCache(capacity=2), _Builds()
    a = cache.get_or_build("a", builds("a"))
    cache.get_or_build("b", builds("b"))
    assert cache.get_or_build("a", builds("a")) is a  # hit: a is now newest
    cache.get_or_build("c", builds("c"))              # evicts b, not a
    assert cache.keys() == ["a", "c"]
    assert "b" not in cache and "a" in cache and len(cache) == 2
    assert builds.n == 3
    # b comes back as a fresh build, and evicts a (now least recent)
    cache.get_or_build("b", builds("b"))
    assert cache.keys() == ["c", "b"] and builds.n == 4


def test_cache_counters_count_hits_misses_and_evictions():
    cache, builds = ExecutorCache(capacity=1), _Builds()
    h0, m0, e0 = _counts(cache)
    cache.get_or_build("x", builds("x"))
    cache.get_or_build("x", builds("x"))
    cache.get_or_build("x", builds("x"))
    cache.get_or_build("y", builds("y"))
    h, m, e = _counts(cache)
    assert (h - h0, m - m0, e - e0) == (2, 2, 1)
    assert builds.n == 2


def test_cache_set_capacity_shrinking_evicts_least_recent():
    cache, builds = ExecutorCache(capacity=4), _Builds()
    for key in "abcd":
        cache.get_or_build(key, builds(key))
    cache.get_or_build("a", builds("a"))  # order now b, c, d, a
    e0 = cache.evictions
    cache.set_capacity(2)
    assert cache.capacity == 2
    assert cache.keys() == ["d", "a"]
    assert cache.evictions - e0 == 2
    cache.set_capacity(8)  # growing evicts nothing
    assert cache.keys() == ["d", "a"] and cache.evictions - e0 == 2


def test_cache_clear_empties_and_rebuilds():
    cache, builds = ExecutorCache(capacity=3), _Builds()
    first = cache.get_or_build("k", builds("k"))
    cache.clear()
    assert len(cache) == 0 and cache.keys() == [] and "k" not in cache
    again = cache.get_or_build("k", builds("k"))
    assert again != first and builds.n == 2


@pytest.mark.parametrize("capacity", [0, -3])
def test_cache_capacity_below_one_raises(capacity):
    with pytest.raises(PlanBuildError, match=">= 1"):
        ExecutorCache(capacity=capacity)
    cache = ExecutorCache(capacity=2)
    with pytest.raises(PlanBuildError, match=">= 1"):
        cache.set_capacity(capacity)
    assert cache.capacity == 2


# --- HealthTable -----------------------------------------------------------

SIG = ("sig", 1)


def _accel_schedule(table, sig, calls):
    return [table.should_try_accel(sig) for _ in range(calls)]


def test_health_backoff_then_sticky_demotion():
    """After failure n the accelerated tier is next tried backoff_base**n
    dispatches later; failure max_retries+1 demotes the signature."""
    t = HealthTable(max_retries=2, backoff_base=2)
    assert t.should_try_accel(SIG)              # call 1
    assert t.record_failure(SIG, RuntimeError("boom")) is True
    assert t.state(SIG) == "retrying" and t.is_degraded(SIG)
    # retry at calls_seen 1 + 2 = 3: call 2 skips, call 3 retries
    assert _accel_schedule(t, SIG, 2) == [False, True]
    assert t.record_failure(SIG, RuntimeError("boom")) is False
    # retry at 3 + 4 = 7: calls 4-6 skip, call 7 retries
    assert _accel_schedule(t, SIG, 4) == [False, False, False, True]
    t.record_failure(SIG, RuntimeError("boom"))  # third: past max_retries
    assert t.state(SIG) == "demoted"
    assert _accel_schedule(t, SIG, 20) == [False] * 20
    t.record_success(SIG)  # a demotion is sticky
    assert t.state(SIG) == "demoted"
    assert t.counters.demotions == 1 and t.counters.failures == 3


def test_health_success_inside_retry_window_resets():
    t = HealthTable(max_retries=3, backoff_base=2)
    t.should_try_accel(SIG)
    t.record_failure(SIG, ValueError("x"))
    t.record_success(SIG)
    assert t.state(SIG) == "healthy" and not t.is_degraded(SIG)
    assert t.counters.recoveries == 1
    # back to trying every call, and a later failure restarts at 2**1
    assert _accel_schedule(t, SIG, 3) == [True, True, True]
    t.record_failure(SIG, ValueError("y"))
    assert _accel_schedule(t, SIG, 2) == [False, True]
    # a success with nothing outstanding is not a recovery
    t.record_success(("other",))
    assert t.counters.recoveries == 1


def test_health_record_fallback_counts_per_table():
    t, other = HealthTable(), HealthTable()
    for _ in range(3):
        t.record_fallback(SIG)
    assert t.counters.fallbacks == 3
    assert other.counters.fallbacks == 0
    # a fallback alone does not degrade the signature
    assert t.state(SIG) == "healthy"


def test_health_last_error_snapshot_and_reset():
    t = HealthTable(max_retries=0)
    assert t.last_error(SIG) is None
    t.should_try_accel(SIG)
    t.record_failure(SIG, KeyError("lowering"))
    t.record_failure(("b",), TypeError("bad"))
    t.record_fallback(SIG)
    t.should_try_accel(("c",))
    assert t.last_error(SIG) == "KeyError: 'lowering'"
    assert t.last_error(("b",)) == "TypeError: bad"
    assert t.snapshot() == {
        "signatures": 3, "demoted": 2, "retrying": 0, "failures": 2,
        "fallbacks": 1, "demotions": 2, "recoveries": 0,
    }
    t.reset()
    assert t.snapshot() == {
        "signatures": 0, "demoted": 0, "retrying": 0, "failures": 0,
        "fallbacks": 0, "demotions": 0, "recoveries": 0,
    }
    assert t.state(SIG) == "healthy" and t.last_error(SIG) is None
    assert t.should_try_accel(SIG)


# --- validate_sddmm_operands -----------------------------------------------

SHAPE = (6, 5)  # pattern (M, K)


def _z(*shape):
    return jnp.zeros(shape, jnp.float32)


@pytest.mark.parametrize("x, y, match", [
    (_z(6), _z(3, 5), "must be"),                     # x rank 1
    (_z(1, 1, 6, 3), _z(3, 5), "must be"),            # x rank 4
    (_z(6, 3), _z(2, 2, 3, 5), "must be"),            # y rank 4
    (_z(2, 6, 3), _z(3, 5), "batched together"),      # mixed batching
    (_z(2, 6, 3), _z(3, 3, 5), "batch sizes disagree"),
    (_z(7, 3), _z(3, 5), "M=7 does not match"),
    (_z(6, 3), _z(3, 4), "K=4 does not match"),
    (_z(6, 3), _z(4, 5), "disagree on D"),
], ids=["x-rank1", "x-rank4", "y-rank4", "mixed-batch", "batch-mismatch",
        "m-mismatch", "k-mismatch", "d-mismatch"])
def test_validate_sddmm_operands_raises(x, y, match):
    with pytest.raises(ValueError, match=match):
        validate_sddmm_operands(x, y, SHAPE)


@pytest.mark.parametrize("x, y, batch", [
    (_z(6, 3), _z(3, 5), None),
    (_z(4, 6, 1), _z(4, 1, 5), 4),
], ids=["unbatched", "batched"])
def test_validate_sddmm_operands_accepts(x, y, batch):
    assert validate_sddmm_operands(x, y, SHAPE) == batch
