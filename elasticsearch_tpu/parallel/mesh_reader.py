"""The served reader of an index of several shards.

An index of more than one shard keeps its shards on the node's first
device, or with `index.routing.allocation.devices` equal to its
`number_of_shards` one on each of that many of the node's devices
(`Placement`). `Node.search` hands such an index to ONE reader, a
`MeshReader`, instead of one `ShardReader` a shard. The dispatch
scheduler serves it as it serves any reader (`msearch_submit`, then
`finish` on the pend), so coalescing and pipelining apply to it. Its
calls are every mesh reader's (`distributed.submit_mesh`): one program
a signature group and generation over the index's devices
(`DistributedSearcher`'s `_segment_program_mesh`), in which a device
runs each shard row it holds and reduces its rows, and the devices then
reduce the shards' top-k (by score, or by a numeric field's values),
totals and aggregation buckets among them. The host builds the response
and loads the `_source` of the hits only. An index of one shard has no
placement and keeps its `ShardReader`.

The mesh view is `MeshIndex` over the shard readers a search acquires,
packed as they are: the node sends a search to the program only while
every shard's view is one segment with every row live
(`packed_as_is`), where the program's answers are the shards' readers'
to the last tie; after a refresh that leaves a shard two segments or a
deleted row, the shards' readers serve the index until a force-merge
(or a reopening of a merged commit) makes it one segment a shard again,
and the view is packed anew then (timed under `mesh.refresh`): the
index's refresh does it (`Placement.refresh`), else the next search. A
`MeshReader` lives as long as those readers and holds the searchers of
that point in time: what they make of a body is kept on them
(search/bound_plans.py): its parse, its bound trees on every shard's
view, its group key, its group of one and the last group of several
bodies it came first in, with their parameters placed on the devices,
so a body served alone before, or such a group served again, goes from
its key to the launch. Another group of several bodies, each kept, is
assembled from them: it skips the parse and the bind on the shard
views, and does the finalize of its batch, the fused admission, the
program's lookup and the upload of its parameters
(`bound_plans.assembled`, counted among the misses); a body not kept is
parsed and bound, and only it.

What the program cannot run (`refusal`), or a program that fails (a
shard's fault, a plan it cannot build), is served by the shards' own
`ShardReader`s, each on its own device (`Engine.device`), and counted.
`GET /_nodes/stats/dispatch` -> `mesh`: `searches` (bodies answered by
one program over the index's shards), `fallbacks` by reason, and
`refresh` (count and seconds of the mesh view's packs).
"""

from __future__ import annotations

import threading
import time

from ..search.aggregations import parse_aggs
from ..utils.errors import IllegalArgumentError, ShardFailedError
from ..utils.metrics import MetricsRegistry
from .distributed import MeshIndex, mesh_searches, mesh_sort, submit_mesh

DEVICES_SETTING = "index.routing.allocation.devices"

# what a body may hold for the program to serve it: anything else (a
# highlight, a rescore, suggest, knn, scripts, stored fields, ...) goes
# to the shards' own readers
BODY_KEYS = frozenset({"query", "size", "from", "sort", "aggs",
                       "aggregations", "_source", "stats"})
# aggregations the program reduces exactly in place (psum of aligned
# bucket arrays over mesh-global ordinals and extents)
AGG_KINDS = frozenset({"terms", "date_histogram", "histogram", "avg",
                       "sum", "min", "max", "stats", "value_count"})

_fallbacks = MetricsRegistry()
_refresh = MetricsRegistry().mean("refresh")


def counts() -> dict:
    """Process-wide, like `launches`: read as deltas."""
    return {"searches": mesh_searches.count,
            "fallbacks": _fallbacks.snapshot(),
            "refresh": _refresh.snapshot()}


def record_fallback(reason: str) -> None:
    _fallbacks.counter(reason).inc()


def node_devices() -> list:
    """The devices this node can place shards on."""
    import jax
    return jax.devices()


def allocation_devices(settings, num_shards: int) -> int:
    """The number of the node's devices the index's shards are spread
    over, checked: 1 (every shard on the first) or one a shard, and no
    more than the node has."""
    n = settings.get_int(DEVICES_SETTING, 1)
    if n != 1 and n != num_shards:
        raise IllegalArgumentError(
            f"[{DEVICES_SETTING}] is {n}, and the index has "
            f"[number_of_shards] {num_shards}: shards are placed on one "
            f"device or one a device, so it must be 1 or {num_shards}")
    have = len(node_devices())
    if n > have:
        raise IllegalArgumentError(
            f"[{DEVICES_SETTING}] asks for {n} devices, and this node "
            f"has {have}")
    return n


def packed_as_is(readers: list) -> bool:
    """Whether the program's pack would be these shard views as they
    are: each at most one segment, every row of it live, no nested rows.
    Only then are its answers the shards' readers' to the last tie: a
    shard of several segments or with deleted rows would be packed
    merged (its text impacts baked again over the live rows) or as a
    base and a tail (ties ordered by generation before shard)."""
    for r in readers:
        if len(r.segments) > 1:
            return False
        for seg in r.segments:
            if seg.parent_of is not None \
                    or not r.live_all[seg.seg_id][: seg.num_docs].all():
                return False
    return True


def refusal(body: dict, mappers, search_type: str | None = None,
            scroll: str | None = None, timed: bool = False) -> str | None:
    """Why the program cannot serve this search, or None where it can.
    `timed`: the search has a time limit (the body's, or the node's
    default), where the shards' readers answer with the shards that
    finished in time and the program answers for all or none."""
    if search_type not in (None, "query_then_fetch"):
        return "search_type"
    if scroll is not None:
        return "scroll"
    if timed:
        return "timeout"
    if set(body) - BODY_KEYS:
        return "body"
    if mesh_sort(body, mappers) is None:
        return "sort"
    for spec in parse_aggs(body.get("aggs") or body.get("aggregations")):
        if spec.kind not in AGG_KINDS or any(
                m.kind not in AGG_KINDS for m in spec.sub_metrics) \
                or getattr(spec, "sig_subs", None):
            return "aggs"
    return None


class Placement:
    """An index's shards on the node's first device, or one a device:
    the mesh, the mesh view and the reader over the current
    point-in-time views."""

    def __init__(self, svc, n_devices: int):
        from .mesh import build_mesh
        self.svc = svc
        self.devices = node_devices()[:n_devices]
        self.mesh = build_mesh(n_devices, 1, devices=self.devices)
        self._lock = threading.Lock()
        self._mesh_index = None
        # the shard views last asked about, and their reader (None where
        # they are not packed as they are)
        self._seen: list | None = None
        self._reader: MeshReader | None = None

    def refresh(self) -> None:
        """Pack the shards' views into the mesh view now: the index's
        refresh calls it once every shard has refreshed, so that its
        next search does not pay for it. Where a shard has failed, the
        next search finds out and goes to the shards' own readers."""
        try:
            readers = [eng.acquire_searcher()
                       for _sid, eng in sorted(self.svc.shards.items())]
        except ShardFailedError:
            return
        self.reader(readers)

    def reader(self, readers: list) -> "MeshReader | None":
        """The mesh reader over these shard readers (one a shard, as a
        search acquired them): the same object while they are the same
        views (so the request cache, which keys a reader it cannot ask
        for a generation by its identity, keys this view); the mesh view
        packed anew from them where any of them is new. None where they
        are not packed as they are (`packed_as_is`)."""
        with self._lock:
            if self._seen is not None \
                    and all(a is b for a, b in zip(self._seen, readers)):
                return self._reader
            self._seen, self._reader = list(readers), None
            if not packed_as_is(readers):
                # the old pack leaves the device with the last search
                # that holds it
                self._mesh_index = None
                return None
            t0 = time.perf_counter()
            if self._mesh_index is None:
                self._mesh_index = MeshIndex(
                    None, self.svc.name, self.mesh, svc=self.svc,
                    readers=readers, shard_stats=True)
            else:
                self._mesh_index.repack(readers)
            _refresh.inc(time.perf_counter() - t0)
            self._reader = MeshReader(self.svc.name, readers,
                                      self._mesh_index.searchers())
            return self._reader


class MeshReader:
    """One reader for every shard of the index (see the module doc):
    the shard readers it was made over, and the mesh searchers of that
    point in time."""

    def __init__(self, index_name: str, readers: list, searchers: list):
        self.index_name = index_name
        self.readers = list(readers)
        # what a failure names: every shard this reader serves
        self.shard_id = None
        self.shard_ids = tuple(r.shard_id for r in readers)
        self.searchers = searchers

    @property
    def shards_served(self) -> int:
        return len(self.readers)

    def msearch(self, bodies: list[dict], with_partials: bool = False,
                deadline: float | None = None, requests: list | None = None,
                keys: list | None = None) -> list[dict]:
        return self.msearch_submit(bodies, with_partials, deadline=deadline,
                                   requests=requests, keys=keys).finish()

    def msearch_submit(self, bodies: list[dict],
                       with_partials: bool = False,
                       deadline: float | None = None,
                       requests: list | None = None,
                       keys: list | None = None):
        return submit_mesh(self.searchers, bodies, with_partials,
                           deadline=deadline, requests=requests, keys=keys)
