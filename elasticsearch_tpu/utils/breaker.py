"""Hierarchical memory circuit breakers.

Reference analog: common/breaker/MemoryCircuitBreaker.java +
indices/breaker/HierarchyCircuitBreakerService.java:43-61 — estimate-based
accounting that trips *before* an allocation OOMs, with per-breaker limits
(fielddata 60%, request 40%) under a parent total (70%).

TPU-first reinterpretation: the scarce resource is HBM, not JVM heap.
The "fielddata" breaker accounts device-resident column/posting bytes; the
"request" breaker accounts per-search transient device buffers (dense
score accumulators, agg bucket arrays). Limits default to fractions of
per-device HBM (detected from jax; overridable via settings).
"""

from __future__ import annotations

import threading

from .errors import CircuitBreakingError
from .settings import Settings

# the CPU platform reports no memory limit (tests, the CPU rehearsal):
# account against one v5e chip's 16 GB there, and only there
_CPU_TOTAL = 16 * 1024 ** 3


def _device_memory_bytes() -> int:
    """Per-device HBM from the device itself. On an accelerator a
    missing `bytes_limit` is an error — guessing a size there would
    let the breakers admit what the chip cannot hold."""
    import jax

    d = jax.devices()[0]
    if d.platform == "cpu":
        return _CPU_TOTAL
    limit = (d.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(
            f"device {d} reports no memory_stats()['bytes_limit']; the "
            f"HBM breakers cannot be sized")
    return int(limit)


class Hold:
    """One releasable breaker reservation: released at most once, from
    any exit path — `with breaker.hold(n):` for scoped transients, or
    kept and `release()`d / `shrink()`ed explicitly for reservations
    that outlive the acquiring frame (queued dispatch outputs).

    This is the structural fast path graftlint's breaker-hold rule
    recognizes: pairing is carried by the object, not by every caller
    re-deriving the byte count on each exit."""

    __slots__ = ("_breaker", "_bytes", "_released")

    def __init__(self, breaker: "CircuitBreaker", nbytes: int):
        self._breaker = breaker
        self._bytes = nbytes
        self._released = False

    @property
    def bytes(self) -> int:
        return 0 if self._released else self._bytes

    def shrink(self, new_bytes: int) -> None:
        """Downgrade the reservation (e.g. transient estimate -> queued
        output footprint), releasing the difference now."""
        if self._released or new_bytes >= self._bytes:
            return
        self._breaker.release(self._bytes - max(0, new_bytes))
        self._bytes = max(0, new_bytes)

    def release(self) -> None:
        if not self._released:
            self._released = True
            self._breaker.release(self._bytes)

    def __enter__(self) -> "Hold":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class CircuitBreaker:
    """One named breaker: add estimates, trip past the limit.

    Ref: common/breaker/MemoryCircuitBreaker.java (addEstimateBytesAndMaybeBreak).
    """

    def __init__(self, name: str, limit: int, overhead: float = 1.0,
                 parent: "HierarchyCircuitBreakerService | None" = None):
        self.name = name
        self.limit = limit
        self.overhead = overhead
        self._used = 0
        self._trips = 0
        self._lock = threading.Lock()
        self._parent = parent

    def add_estimate(self, bytes_wanted: int) -> int:
        with self._lock:
            new_used = self._used + bytes_wanted
            if self.limit > 0 and new_used * self.overhead > self.limit:
                self._trips += 1
                raise CircuitBreakingError(self.name, int(new_used * self.overhead), self.limit)
            self._used = new_used
        if self._parent is not None:
            try:
                self._parent.check_parent()
            except CircuitBreakingError:
                with self._lock:
                    # clamp: a concurrent release() may already have clamped
                    # _used to 0, so a raw subtraction could go negative and
                    # corrupt all later accounting
                    self._used = max(0, self._used - bytes_wanted)
                raise
        return self._used

    def hold(self, bytes_wanted: int) -> Hold:
        """add_estimate + a Hold owning the release (raises
        CircuitBreakingError like add_estimate when over limit, in
        which case nothing is held)."""
        self.add_estimate(bytes_wanted)
        return Hold(self, bytes_wanted)

    def add_without_breaking(self, bytes_delta: int) -> int:
        with self._lock:
            self._used += bytes_delta
            return self._used

    def release(self, bytes_freed: int) -> None:
        with self._lock:
            self._used = max(0, self._used - bytes_freed)

    @property
    def used(self) -> int:
        return self._used

    @property
    def trips(self) -> int:
        return self._trips

    def stats(self) -> dict:
        return {
            "limit_size_in_bytes": self.limit,
            "estimated_size_in_bytes": self._used,
            "overhead": self.overhead,
            "tripped": self._trips,
        }


class HierarchyCircuitBreakerService:
    """Child breakers (fielddata/request) under a parent total limit.

    Ref: indices/breaker/HierarchyCircuitBreakerService.java:43-61.
    Settings (fractions of device HBM):
      indices.breaker.total.limit    default 70%
      indices.breaker.fielddata.limit default 60%
      indices.breaker.request.limit  default 40%
    """

    def __init__(self, settings: Settings = Settings.EMPTY, total_memory: int | None = None):
        total_memory = total_memory or settings.get_bytes(
            "indices.breaker.total.memory", None) or _device_memory_bytes()
        self.total_memory = total_memory
        self.parent_limit = int(total_memory * settings.get_ratio("indices.breaker.total.limit", 0.70))
        self._breakers: dict[str, CircuitBreaker] = {}
        self._parent_trips = 0
        self.register("fielddata", int(total_memory * settings.get_ratio(
            "indices.breaker.fielddata.limit", 0.60)), overhead=1.03)
        self.register("request", int(total_memory * settings.get_ratio(
            "indices.breaker.request.limit", 0.40)), overhead=1.0)

    def register(self, name: str, limit: int, overhead: float = 1.0) -> CircuitBreaker:
        b = CircuitBreaker(name, limit, overhead, parent=self)
        self._breakers[name] = b
        return b

    def breaker(self, name: str) -> CircuitBreaker:
        return self._breakers[name]

    def check_parent(self) -> None:
        total = sum(b.used for b in self._breakers.values())
        if total > self.parent_limit:
            self._parent_trips += 1
            raise CircuitBreakingError("parent", total, self.parent_limit)

    def stats(self) -> dict:
        """Per-breaker limit/estimated/trip-count plus the parent
        budget (ref: CircuitBreakerStats incl. the `parent` entry of
        AllCircuitBreakerStats)."""
        out = {name: b.stats() for name, b in self._breakers.items()}
        out["parent"] = {
            "limit_size_in_bytes": self.parent_limit,
            "estimated_size_in_bytes": sum(
                b.used for b in self._breakers.values()),
            "overhead": 1.0,
            "tripped": self._parent_trips,
        }
        return out


_default_service: HierarchyCircuitBreakerService | None = None
_default_lock = threading.Lock()


def breaker_service(settings: Settings | None = None
                    ) -> HierarchyCircuitBreakerService:
    """Process-wide breaker service guarding the device's HBM.

    Deliberately ONE service per process even when several in-process
    test nodes exist: they share the same physical device, so a shared
    budget is the correct accounting (unlike the reference, where each
    JVM owns its heap). The FIRST caller's settings configure the
    limits — Node passes its settings at construction; later callers
    get the existing service."""
    global _default_service
    with _default_lock:
        if _default_service is None:
            _default_service = HierarchyCircuitBreakerService(
                settings or Settings.EMPTY)
        return _default_service
