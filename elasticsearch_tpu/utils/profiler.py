"""Phase timers and JAX-profiler phase spans of the served search.

Reference analog: the hot_threads / JVM-profiler side of operations
tooling — here the device's time only means something beside the
host's, so both are captured on one clock: a jax.profiler trace (XLA op
timeline, HBM traffic) started and stopped over REST
(`_nodes/profiler/start|stop`) while real searches flow, with the
program's own phases in it as `query_phase:<name>` spans.

One mechanism for both. `phase(name, **args)` always times its block
into a process-wide MeanMetric (count + sum of seconds) that
`GET /_nodes/stats/dispatch` shows under `phases`, so an operator has
the phase times without a trace; while a trace is active the block is
also a span of that trace carrying `args` (`request=<id>`, or
`requests="<id>|<id>"` and `n=` where one dispatch serves several
searches). `waited(name, seconds)` feeds the same registry and opens
no span: for time in which no thread works for the request. The phases
and waits tile a search's `request`, whether it is one shard job or a
fan-out of five, alone on its reader calls or sharing them.

Spans are LEAVES: no `query_phase:` span may lie inside another on its
thread (the benchmark's trace reduction gives each device gap to the
`query_phase:` span covering most of it, and an enclosing span would
win every gap). A span that encloses others is named outside the
prefix (`enclosing`). Never enter a phase inside a jitted function: its
body runs only while tracing (graftlint's trace-purity pass flags it).
"""

from __future__ import annotations

import itertools
import threading
import time

from .metrics import MetricsRegistry

SPAN_PREFIX = "query_phase:"

_lock = threading.Lock()
# graftlint: ok(shared-state-race): GIL-atomic single-value read in
# phase/status; every rebind serializes under _lock
_active_dir: str | None = None

# process-wide, like the fused-scoring counters: the phases of one
# request run on its REST thread, a `search` pool thread and whichever
# thread leads the dispatch round, of whatever node serves it; readers
# take deltas between two snapshots
_phases = MetricsRegistry()
_request_ids = itertools.count(1)


def start(path: str) -> dict:
    global _active_dir
    with _lock:
        if _active_dir is not None:
            from .errors import IllegalArgumentError
            raise IllegalArgumentError(
                f"profiler already tracing to [{_active_dir}]")
        import jax
        # no profiler_options= here: benchmarks/run.py wraps
        # start_trace and passes that keyword itself
        jax.profiler.start_trace(path)
        _active_dir = path
    return {"tracing": True, "path": path}


def stop() -> dict:
    global _active_dir
    with _lock:
        if _active_dir is None:
            from .errors import IllegalArgumentError
            raise IllegalArgumentError("profiler is not tracing")
        import jax
        path = _active_dir
        try:
            jax.profiler.stop_trace()
        finally:
            # a failed stop must not wedge the profiler in "already
            # tracing" until process restart
            _active_dir = None
    return {"tracing": False, "path": path}


def status() -> dict:
    return {"tracing": _active_dir is not None,
            **({"path": _active_dir} if _active_dir else {})}


def next_request_id() -> int:
    """The id a request keeps from the first point the program sees it
    (next() on itertools.count is one C call: atomic under the GIL)."""
    return next(_request_ids)


class Request:
    """What one served search carries from thread to thread, explicitly
    (through `pool.submit`, the dispatch job and the reader): its id,
    which every span of it writes as `request=<id>` — nesting on one
    thread gives a span its parent, the id ties the threads together —
    and the readings for the time in which no thread works for it.

    A hand-over is measured by `hand_over()` on the giving thread and
    `taken()` on the receiving one; `waited_s` sums them until the
    owner reports the wait (`pool_wait`: REST thread to pool thread and
    back)."""

    __slots__ = ("id", "parse", "waited_s", "_handed")

    def __init__(self):
        self.id = next_request_id()
        # the REST handler's open `rest_parse` block, out of which
        # node.search takes the time the request is with other threads
        # (None for in-process callers)
        self.parse: phase | None = None
        self.waited_s = 0.0
        self._handed = 0.0

    def hand_over(self) -> None:
        self._handed = time.perf_counter()

    def taken(self) -> None:
        self.waited_s += time.perf_counter() - self._handed


def request_args(ids) -> dict:
    """Span arguments naming the searches a piece of work serves. Ids
    are joined by `|`: the trace format separates a span's arguments by
    commas, and a comma inside one cuts it short."""
    ids = [i for i in ids if i is not None]
    if not ids:
        return {}
    if len(ids) == 1:
        return {"request": ids[0]}
    return {"requests": "|".join(map(str, ids)), "n": len(ids)}


def _span(name: str, args: dict):
    import jax
    return jax.profiler.TraceAnnotation(name, **args)


class phase:
    """`with phase("bind", request=7): ...` — see the module doc. The
    block counts once and adds `seconds`, its time, when it ends;
    `weight` times where it serves that many searches at once (a reader
    call with several bodies): each of them waited through all of it,
    so the timers are in search-seconds and tile the searches' own
    `request` times whether or not dispatches are shared.

    A callee that opens a span of its own takes its time out of the
    block, so that the spans stay leaves: `pause()` ends the span and
    banks the time so far, `resume()` opens another span of the same
    name (the reader's `bind` block is paused by the executor around
    each launch; its two spans lie either side of `dispatch`).
    `switch(name)` ends the phase and begins the next leaf of the same
    block (`collect` then `unpack`), each counted under its own name."""

    __slots__ = ("name", "weight", "args", "seconds", "_t0", "_span")

    def __init__(self, name: str, weight: int = 1, **args):
        self.name = name
        self.weight = weight
        self.args = args
        self.seconds = 0.0
        self._t0: float | None = None
        self._span = None

    def __enter__(self) -> "phase":
        self.resume()
        return self

    def resume(self) -> None:
        if self._t0 is None:
            if _active_dir is not None:
                self._span = _span(SPAN_PREFIX + self.name, self.args)
                self._span.__enter__()
            self._t0 = time.perf_counter()

    def pause(self) -> None:
        if self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = None

    def switch(self, name: str) -> None:
        self.__exit__()
        self.name = name
        self.seconds = 0.0
        self.resume()

    def __exit__(self, *exc) -> None:
        timer = _phases.mean(self.name)     # before the last reading
        self.pause()
        for _ in range(self.weight):
            timer.inc(self.seconds)


def waited(name: str, seconds: float) -> None:
    """Time in which no thread worked for the request (a hand-over
    between threads, the wait for the scheduler's leader): a
    perf_counter reading is carried across and subtracted on the other
    side. A timer only, there is nothing to draw a span around."""
    _phases.mean(name).inc(seconds)


class enclosing:
    """A span around other spans, for a person at a trace viewer
    (`request:search`, `request:round`, `request:merge`): `name` lies
    outside the `query_phase:` prefix, so the trace reduction drops it,
    and it feeds no phase timer: what it covers is no tile of a search
    (the leaves inside or around it are). `timer`, where given, is the
    owner's MeanMetric and always gets the block's seconds once (the
    scheduler's `leader`, the node's `merge`: shown beside `phases`,
    never inside it). With no trace active and no timer, two clock
    readings."""

    __slots__ = ("_span", "_timer", "_t0")

    def __init__(self, name: str, timer=None, **args):
        self._span = _span(name, args) if _active_dir is not None else None
        self._timer = timer
        self._t0 = 0.0

    def __enter__(self) -> "enclosing":
        if self._span is not None:
            self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self._t0
        if self._span is not None:
            self._span.__exit__(None, None, None)
        if self._timer is not None:
            self._timer.inc(seconds)


def phase_stats() -> dict:
    """{name: {"count", "sum", "mean"}} in seconds, process-wide."""
    return _phases.snapshot()
