"""What a reader keeps of the bodies it has served: the bound plans.

A `ShardReader` is a point-in-time view (a refresh builds a new one),
so everything its submit computes before a launch from the body and
the reader alone is computed once and kept on the reader:

  * per body (`BoundBody`): the parsed request, the per-segment bound
    trees and the signature that groups it;
  * per group of bodies that one program serves (`BoundGroup`): the
    aggregation context and descriptors, the sort's terms and maps,
    and per segment the executor's `SegmentPlan` (descriptors, the
    packed wire parameters as the device array the upload returned,
    the fused decision, the breaker estimate, the output layout).

A mesh reader's searcher (`parallel/distributed.py`,
`DistributedSearcher.submit`) keeps only bodies (`BoundBody` too): the
parsed query and aggregations, the bound trees on each of the pack's
shard views, the group key (plan signature, aggregations, k, sort),
and with them its group of one (`alone`) and the last group of several
bodies it came first in (`led`), each ready to launch with its
parameters placed on the devices. Another group of bodies, each kept,
is assembled from their bound trees: one finalize over the batch, the
fused admission and the program's lookup, one upload, and no parse or
bind; only a body not kept is parsed and bound.

Keyed by the bodies' canonical JSON (`index/cache.canonical_key`, the
shard request cache's; that cache keeps results, this one keeps none:
the program runs on the device for every search). One `BoundPlans`
holds a body under its key, its group of one with it, and (a shard
reader) a larger group under the tuple of its bodies' keys in order,
with least-recently-used eviction at `CAPACITY`; a mesh reader's kept
body holds besides at most one `alone` and one `led` launch state, so
at most two groups' parameters on the devices a body, and counts as two
entries while it holds a `led`. What else the kept values read is the cache's stamp
(the mapping's version and the executor's switches, `open`): a change
empties it. What the launch reads of the device's column tree is
checked per launch against the segment's `device_epoch`
(`executor.segment_plan_valid`).

Not kept, because the parse or the bind is no function of the body and
the reader, or does what a kept copy would not repeat: a body that
read the clock or the stored scripts, and one whose bind uploaded
something (`note_volatile`, called from where either happens).

`GET /_nodes/stats/dispatch` -> `bound_plans` counts, once a reader
call's group: `hits` (launched from what was kept), `misses` (built and
kept), `bypassed` (bodies and groups that leave the grouped cold path
or may not be kept), `evictions`, and the `entries` live readers hold;
and `assembled`, the misses of a mesh reader that were assembled from
kept bodies alone, without a parse or a bind (each still uploads its
parameters, so it stays a miss).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass

from ..index.cache import canonical_key
from ..utils.metrics import MetricsRegistry

# entries a reader keeps, bodies and groups together: the track's eight
# bodies, alone and in the pairs a shared round makes, are under 40
CAPACITY = 256

_counts = MetricsRegistry()
hits = _counts.counter("hits")
misses = _counts.counter("misses")
bypassed = _counts.counter("bypassed")
assembled = _counts.counter("assembled")
_evictions = _counts.counter("evictions")
# a gauge: what live readers hold
_held = _counts.counter("entries")


def counts() -> dict:
    """Process-wide, like `launches` and `collects`: read as deltas."""
    return _counts.snapshot()


def body_key(body) -> str | None:
    """The body's canonical form, or None where it has none that tells
    two bodies apart (a value JSON has no form for, keys that do not
    sort): such a body is parsed and bound every time."""
    try:
        return canonical_key(body, strict=True)
    except (TypeError, ValueError):
        return None


@dataclass(slots=True)
class BoundBody:
    """One body, parsed and bound against every segment of the reader
    (a mesh reader: every shard's view, `sig` its group key). `parsed`
    is shared by every search with this body: read-only. `alone` is
    the body's `BoundGroup` (a mesh reader's launch state) where it was
    served as a group of one, which is how a single `_search` arrives.
    `led` is a mesh reader's (the group's body keys in order, its
    launch state) of the last group of several bodies this one came
    first in."""

    parsed: dict
    bounds: list
    sig: tuple | None
    alone: "BoundGroup | dict | None" = None
    led: tuple | None = None


@dataclass(slots=True)
class BoundGroup:
    """One group of bodies (one program a segment), ready to launch:
    what `_msearch_submit` builds between the grouping and the launches
    and `finish` reads back, and the executor's plan a segment."""

    agg_ctx: object
    agg_desc: tuple
    agg_params: list
    k: int
    sort_spec: tuple
    sort_terms: list | None
    sort_maps: list
    plans: list


class BoundPlans:
    """One reader's kept bodies and groups. Callers on several threads
    share it (the scheduler's leader, direct `reader.msearch` callers):
    every access to the dict is under the lock; two threads that miss
    the same key both build, and the later store wins."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: OrderedDict = OrderedDict()
        self._stamp = None

    def open(self, stamp: tuple) -> None:
        """Start of a reader call: `stamp` is whatever the kept values
        read besides the bodies and the reader. Nothing kept under
        another stamp is served."""
        if stamp == self._stamp:
            return
        with self._lock:
            if stamp != self._stamp:
                _held.dec(self._weights())
                self._entries.clear()
                self._stamp = stamp

    def get(self, key):
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
            return entry

    def put(self, key, entry) -> None:
        with self._lock:
            old = self._entries.get(key)
            _held.inc(_weight(entry) - (0 if old is None else _weight(old)))
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > CAPACITY:
                _key, gone = self._entries.popitem(last=False)
                _evictions.inc()
                _held.dec(_weight(gone))

    def lead(self, key, body: BoundBody, led: tuple) -> None:
        """Keep `led` on the body kept under `key` (a mesh reader's group
        of several, `BoundBody.led`): one entry more while it is held."""
        with self._lock:
            if self._entries.get(key) is body and body.led is None:
                _held.inc()
            body.led = led

    def drop(self, keys) -> None:
        """After a launch from kept values raised: they go, so that the
        next search builds them anew."""
        with self._lock:
            for key in keys:
                gone = self._entries.pop(key, None)
                if gone is not None:
                    _held.dec(_weight(gone))

    def _weights(self) -> int:
        return sum(map(_weight, self._entries.values()))

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __del__(self):
        # the reader is gone (a refresh replaced it): its entries leave
        # the process-wide gauge
        try:
            _held.dec(self._weights())
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass


def _weight(entry) -> int:
    """The entries one kept value counts for: a body with the group of
    several it led two, anything else one."""
    return 1 if getattr(entry, "led", None) is None else 2


# -- what may not be kept ----------------------------------------------------

_scopes = threading.local()


class keep_scope:
    """Around one body's parse and bind, on the calling thread: `.keep`
    turns False where something in between noted that its result is no
    function of the body and the reader. Scopes nest (a join query's
    parse searches the reader again); a note marks every open one."""

    __slots__ = ("keep",)

    def __enter__(self):
        self.keep = True
        stack = getattr(_scopes, "stack", None)
        if stack is None:
            stack = _scopes.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _scopes.stack.pop()
        return False


def note_volatile() -> None:
    """Called where a parse or a bind does what a kept copy of its
    result would not repeat or could not follow: reads the clock
    (`now`) or the stored and file scripts, which change under a
    reader; uploads to the segment's column tree, which `drop_device`
    forgets while the kept bound tree would still count on it."""
    for scope in getattr(_scopes, "stack", ()):
        scope.keep = False
