"""Metrics primitives + registry.

Reference analog: common/metrics/ (CounterMetric.java, MeanMetric.java,
EWMA.java, MeterMetric.java). Python counters are GIL-atomic enough for
the host control plane; device-side timing comes from the search executor.
"""

from __future__ import annotations

import math
import threading
import time


class CounterMetric:
    """Monotonic (inc/dec) counter. Ref: common/metrics/CounterMetric.java."""

    __slots__ = ("_count", "_lock")

    def __init__(self):
        # writes are locked (+= is read-modify-write); the bare read in
        # .count is a single int load, atomic under the GIL
        # graftlint: ok(shared-state-race): GIL-atomic single-op read;
        # all writes serialize under _lock
        self._count = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._count += n

    def dec(self, n: int = 1) -> None:
        with self._lock:
            self._count -= n

    @property
    def count(self) -> int:
        return self._count


class HighWaterMetric:
    """High-water-mark gauge: record() keeps the max ever seen — ints
    (the dispatch scheduler's in-flight pipeline depth) or floats (the
    resident loop's staged-feed overlap in ms)."""

    __slots__ = ("_max", "_last", "_lock")

    def __init__(self):
        # graftlint: ok(shared-state-race): GIL-atomic single-value
        # reads in .max/.last; the compare-and-store writes serialize
        # under _lock
        self._max = 0
        # graftlint: ok(shared-state-race): GIL-atomic single-value
        # read; writes serialize under _lock
        self._last = 0
        self._lock = threading.Lock()

    def record(self, value: int | float) -> None:
        with self._lock:
            self._last = value
            if value > self._max:
                self._max = value

    @property
    def max(self) -> int | float:
        return self._max

    @property
    def last(self) -> int | float:
        return self._last


class MeanMetric:
    """Sum + count -> mean. Ref: common/metrics/MeanMetric.java."""

    __slots__ = ("_sum", "_count", "_lock")

    def __init__(self):
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def inc(self, value: float) -> None:
        with self._lock:
            self._sum += value
            self._count += 1

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    @property
    def mean(self) -> float:
        # one lock for BOTH loads: a mean computed from a sum and a
        # count out of different inc() generations is a torn read
        with self._lock:
            return self._sum / self._count if self._count else 0.0

    def snapshot(self) -> dict:
        """{"count", "sum", "mean"} of one inc() generation."""
        with self._lock:
            return {"count": self._count, "sum": self._sum,
                    "mean": self._sum / self._count if self._count else 0.0}


class EWMA:
    """Exponentially-weighted moving average. Ref: common/metrics/EWMA.java.

    Internally locked: update() is a read-modify-write shared by
    MeterMetric's rate tick and the traffic controller's adaptive
    coalescing window, both of which feed it from concurrent request
    threads — the shared-state-race pass verifies the lockset instead
    of trusting callers to serialize."""

    __slots__ = ("alpha", "_value", "_initialized", "_lock")

    def __init__(self, alpha: float = 0.3, initial: float = 0.0,
                 seeded: bool = False):
        """`seeded=True` starts the series AT `initial` (the first
        sample decays toward it) instead of replacing it — the adaptive
        window's merged-round average starts at 1.0 that way."""
        self.alpha = alpha
        self._value = initial
        self._initialized = seeded
        self._lock = threading.Lock()

    def update(self, sample: float) -> None:
        with self._lock:
            if not self._initialized:
                self._value = sample
                self._initialized = True
            else:
                self._value += self.alpha * (sample - self._value)

    def reset(self) -> None:
        """Forget the series (the adaptive window's idle reset): the
        next sample re-seeds the average instead of decaying toward it."""
        with self._lock:
            self._value = 0.0
            self._initialized = False

    @property
    def initialized(self) -> bool:
        with self._lock:
            return self._initialized

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


class MeterMetric:
    """Events/sec with 1m EWMA. Ref: common/metrics/MeterMetric.java."""

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._count = CounterMetric()
        self._start = clock()
        self._m1 = EWMA(alpha=1 - math.exp(-5.0 / 60.0))
        self._last_tick = self._start
        self._uncounted = 0
        self._lock = threading.Lock()

    def mark(self, n: int = 1) -> None:
        with self._lock:
            self._count.inc(n)
            self._uncounted += n
            self._tick_locked()

    def _tick_locked(self) -> None:
        now = self._clock()
        while now - self._last_tick >= 5.0:
            self._m1.update(self._uncounted / 5.0)
            self._uncounted = 0
            self._last_tick += 5.0

    @property
    def count(self) -> int:
        return self._count.count

    @property
    def mean_rate(self) -> float:
        elapsed = self._clock() - self._start
        return self._count.count / elapsed if elapsed > 0 else 0.0

    @property
    def one_minute_rate(self) -> float:
        # tick on read too, so an idle meter decays (reference MeterMetric
        # ticks in the getter as well as in mark)
        with self._lock:
            self._tick_locked()
            return self._m1.value


class MetricsRegistry:
    """Named metrics, for stats APIs (_nodes/stats analog)."""

    def __init__(self):
        from . import race_guard
        self._lock = threading.Lock()
        self._metrics: dict[str, object] = race_guard.guarded_dict(
            self._lock, "metrics.MetricsRegistry._metrics")

    def counter(self, name: str) -> CounterMetric:
        return self._get(name, CounterMetric)

    def mean(self, name: str) -> MeanMetric:
        return self._get(name, MeanMetric)

    def meter(self, name: str) -> MeterMetric:
        return self._get(name, MeterMetric)

    def _get(self, name: str, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls()
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise TypeError(f"metric [{name}] already registered as {type(m).__name__}")
            return m

    def snapshot(self) -> dict:
        out = {}
        # under _lock: a concurrent _get() inserting a new metric while
        # this iterates would raise RuntimeError mid-stats (the metric
        # objects themselves serialize their own reads)
        with self._lock:
            items = sorted(self._metrics.items())
        for name, m in items:
            if isinstance(m, CounterMetric):
                out[name] = m.count
            elif isinstance(m, MeanMetric):
                out[name] = m.snapshot()
            elif isinstance(m, MeterMetric):
                out[name] = {"count": m.count, "mean_rate": m.mean_rate,
                             "one_minute_rate": m.one_minute_rate}
        return out
