"""HTTP JSON REST API.

Reference analog: rest/ (RestController.java PathTrie dispatch :48-162,
handlers under rest/action/*) + http/netty/NettyHttpServerTransport.java.
Route shapes follow rest-api-spec/api/*.json so existing ES clients and
the YAML conformance suites can drive this server.

Implementation: stdlib ThreadingHTTPServer — the control plane is
host-side Python; the device does the heavy lifting, so a native event
loop buys nothing until multi-host RPC lands (transport/).
"""

from __future__ import annotations

import json
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import urlparse, parse_qs, unquote

from ..node import Node
from ..utils import profiler
from ..utils.errors import (ElasticsearchTpuError, IllegalArgumentError,
                            IndexNotFoundError)
from .. import __version__

_scan_json = json.JSONDecoder().scan_once


def _ndjson(text: str) -> list:
    """`json.loads` of every non-blank line. A `_bulk` body is ten
    thousand of them, so a line goes straight to the decoder's scanner;
    one that the scanner does not take whole (surrounding whitespace,
    trailing data, no JSON at all) goes to `json.loads`, for its result
    or its error."""
    out = []
    for line in text.splitlines():
        if not line.strip():
            continue
        try:
            obj, end = _scan_json(line, 0)
        except StopIteration:
            end = -1
        out.append(obj if end == len(line) else json.loads(line))
    return out


class Route:
    def __init__(self, method: str, pattern: str, handler):
        self.method = method
        self.handler = handler
        parts = pattern.strip("/").split("/")
        regex = []
        self.params: list[str] = []
        for p in parts:
            if p.startswith("{"):
                name = p[1:-1]
                self.params.append(name)
                regex.append(r"(?P<%s>[^/]+)" % name)
            else:
                regex.append(re.escape(p))
        self.regex = re.compile("^/" + "/".join(regex) + "/?$")
        # literal segments outrank {param} segments position-by-position
        # (ref: RestController PathTrie wildcard fallback); lexicographic
        # comparison of this key picks the most-literal matching route
        self.spec_key = tuple(1 if p.startswith("{") else 0 for p in parts)

    def match(self, method: str, path: str):
        if method != self.method:
            return None
        m = self.regex.match(path)
        if m is None:
            return None
        # decode AFTER segment split so %2F inside an id stays one
        # segment (the reference's PathTrie decodes per part too)
        return {k: unquote(v) for k, v in m.groupdict().items()}


class RestDispatcher:
    """Method+path -> handler registry (ref: RestController PathTrie)."""

    def __init__(self, node: Node):
        self.node = node
        self.routes: list[Route] = []
        register_routes(self)
        # plugin routes register last so they can't shadow core routes
        # (ref: plugins contribute RestHandlers via onModule(RestModule))
        plugins = getattr(node, "plugins", None)
        if plugins is not None:
            plugins.apply_rest_hooks(self)

    def route(self, method: str, pattern: str):
        def deco(fn):
            self.routes.append(Route(method, pattern, fn))
            return fn
        return deco

    def dispatch(self, method: str, path: str, params: dict, body):
        effective = "GET" if method == "HEAD" else method
        if method == "HEAD":
            # a few handlers differ between GET and exists-style HEAD
            # (e.g. alias exists -> 404); expose the real verb
            params = dict(params, __method="HEAD")
        best = None
        for r in self.routes:
            kw = r.match(effective, path)
            if kw is not None and (best is None
                                   or r.spec_key < best[0].spec_key):
                best = (r, kw)
        if best is not None:
            return best[0].handler(self.node, params, body, **best[1])
        raise IllegalArgumentError(
            f"no handler found for uri [{path}] and method [{method}]")


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _truthy(params: dict, key: str) -> bool:
    """REST boolean params accept true/1/'' (bare flag) — ref:
    rest/RestRequest.paramAsBoolean."""
    return params.get(key) in ("true", "1", "", "wait_for")


class RestStatus:
    """Wrap a payload with an explicit HTTP status (e.g. 404 delete)."""

    def __init__(self, status: int, payload):
        self.status = status
        self.payload = payload


def _search_body(params: dict, body) -> dict:
    """Search body + URL params every search route honors (ref:
    RestSearchAction parseSearchRequest: queryCache -> the shard request
    cache override)."""
    b = _body_query(params, body)
    if params.get("query_cache") is not None:
        b = dict(b)
        b["query_cache"] = params["query_cache"]
    # failure-semantics controls (ref: RestSearchAction: request.timeout
    # + allow_partial_search_results); the URL param wins over the body
    if params.get("timeout") is not None:
        b = dict(b)
        b["timeout"] = params["timeout"]
    if params.get("allow_partial_search_results") is not None:
        b = dict(b)
        b["allow_partial_search_results"] = _truthy(
            params, "allow_partial_search_results")
    return b


def _body_query(params: dict, body) -> dict:
    """Merge URI params (q, size, from, sort) into a search body.
    Ref: RestSearchAction.parseSearchRequest."""
    body = dict(body or {})
    q = params.get("q")
    if q and "query" not in body:
        body["query"] = {"query_string": {"query": q}}
    for key in ("size", "from"):
        if key in params:
            body[key] = int(params[key])
    if "sort" in params and "sort" not in body:
        entries = []
        for part in params["sort"].split(","):
            if ":" in part:
                f, o = part.split(":", 1)
                entries.append({f: o})
            else:
                entries.append({part: "asc"})
        body["sort"] = entries
    # URI-level source filtering overrides the body's _source (ref:
    # RestSearchAction.parseSearchSource fetchSource handling)
    inc = params.get("_source_include") or params.get("_source_includes")
    exc = params.get("_source_exclude") or params.get("_source_excludes")
    if inc or exc:
        body["_source"] = {"includes": inc.split(",") if inc else [],
                           "excludes": exc.split(",") if exc else []}
    elif "_source" in params:
        v = params["_source"]
        body["_source"] = (True if v == "true" else
                           False if v == "false" else v.split(","))
    return body


# Column schemas per _cat endpoint: (name, alias, description).
# Ref: each rest/action/cat/Rest*Action.getTableWithHeader — the help
# listing and column aliases come from these, independent of row data.
CAT_COLUMNS: dict[str, list[tuple[str, str, str]]] = {
    "aliases": [("alias", "a", "alias name"),
                ("index", "i", "index the alias points to"),
                ("filter", "fi", "filter"),
                ("routing.index", "ri", "index routing"),
                ("routing.search", "rs", "search routing")],
    "allocation": [("shards", "s", "number of shards on node"),
                   ("disk.used", "du", "disk used (total, not just ES)"),
                   ("disk.avail", "da", "disk available"),
                   ("disk.total", "dt", "total capacity of all volumes"),
                   ("disk.percent", "dp", "percent disk used"),
                   ("host", "h", "host of node"),
                   ("ip", "", "ip of node"),
                   ("node", "n", "name of node")],
    "count": [("epoch", "t", "seconds since 1970-01-01 00:00:00"),
              ("timestamp", "ts", "time in HH:MM:SS"),
              ("count", "dc", "the document count")],
    "fielddata": [("id", "", "node id"),
                  ("host", "h", "host of node"),
                  ("ip", "", "ip of node"),
                  ("node", "n", "name of node"),
                  ("total", "", "total field data usage")],
    "health": [("epoch", "t", "seconds since 1970-01-01 00:00:00"),
               ("timestamp", "ts", "time in HH:MM:SS"),
               ("cluster", "cl", "cluster name"),
               ("status", "st", "health status"),
               ("node.total", "nt", "total number of nodes"),
               ("node.data", "nd", "number of nodes that can store data"),
               ("shards", "t", "total number of shards"),
               ("pri", "p", "number of primary shards"),
               ("relo", "r", "number of relocating nodes"),
               ("init", "i", "number of initializing nodes"),
               ("unassign", "u", "number of unassigned shards"),
               ("pending_tasks", "pt", "number of pending tasks")],
    "indices": [("health", "h", "current health status"),
                ("status", "s", "open/close status"),
                ("index", "i", "index name"),
                ("pri", "p", "number of primary shards"),
                ("rep", "r", "number of replica shards"),
                ("docs.count", "dc", "available docs"),
                ("docs.deleted", "dd", "deleted docs"),
                ("store.size", "ss", "store size of primaries & replicas"),
                ("pri.store.size", "", "store size of primaries")],
    "master": [("id", "", "node id"),
               ("host", "h", "host name"),
               ("ip", "", "ip address"),
               ("node", "n", "node name")],
    "nodes": [("host", "h", "host name"),
              ("ip", "i", "ip address"),
              ("heap.current", "hc", "used heap", False),
              ("heap.percent", "hp", "used heap ratio"),
              ("heap.max", "hm", "max configured heap", False),
              ("ram.percent", "rp", "used machine memory ratio"),
              ("file_desc.current", "fdc",
               "used file descriptors", False),
              ("file_desc.percent", "fdp",
               "used file descriptor ratio", False),
              ("file_desc.max", "fdm", "max file descriptors", False),
              ("load", "l", "most recent load avg"),
              ("node.role", "r", "d:data node, c:client node"),
              ("master", "m", "m:master-eligible, *:current master"),
              ("name", "n", "node name")],
    "plugins": [("id", "", "unique node id"),
                ("name", "n", "node name"),
                ("component", "c", "component name"),
                ("version", "v", "component version"),
                ("type", "t", "plugin type"),
                ("url", "u", "url for site plugins"),
                ("description", "d", "plugin details")],
    "recovery": [("index", "i", "index name"),
                 ("shard", "s", "shard name"),
                 ("time", "t", "recovery time"),
                 ("type", "ty", "recovery type"),
                 ("stage", "st", "recovery stage"),
                 ("source_host", "shost", "source host"),
                 ("target_host", "thost", "target host"),
                 ("repository", "rep", "repository"),
                 ("snapshot", "snap", "snapshot"),
                 ("files", "f", "number of files to recover"),
                 ("files_percent", "fp", "percent of files recovered"),
                 ("bytes", "b", "size to recover in bytes"),
                 ("bytes_percent", "bp", "percent of bytes recovered"),
                 ("total_files", "tf", "total number of files"),
                 ("total_bytes", "tb", "total number of bytes"),
                 ("translog", "tr", "translog operations recovered"),
                 ("translog_percent", "trp",
                  "percent of translog recovery"),
                 ("total_translog", "trt",
                  "current number of translog operations")],
    "segments": [("index", "i", "index name"),
                 ("shard", "s", "shard name"),
                 ("prirep", "p", "primary or replica"),
                 ("ip", "", "ip of node where it lives"),
                 ("id", "", "unique id of node where it lives", False),
                 ("segment", "seg", "segment name"),
                 ("generation", "g", "segment generation"),
                 ("docs.count", "dc", "number of docs in segment"),
                 ("docs.deleted", "dd", "number of deleted docs"),
                 ("size", "si", "segment size in bytes"),
                 ("size.memory", "sm", "segment memory in bytes"),
                 ("committed", "ic", "is segment committed"),
                 ("searchable", "is", "is segment searched"),
                 ("version", "v", "version"),
                 ("compound", "ico", "is segment compound")],
    "shards": [("index", "i", "index name"),
               ("shard", "s", "shard name"),
               ("prirep", "p", "primary or replica"),
               ("state", "st", "shard state"),
               ("docs", "d", "number of docs"),
               ("store", "sto", "store size"),
               ("ip", "", "ip of node"),
               ("id", "", "unique id of node", False),
               ("node", "n", "name of node")],
    "thread_pool": [("pid", "p", "process id", False),
                    ("id", "nodeId", "unique node id", False),
                    ("host", "h", "host name"),
                    ("ip", "i", "ip address"),
                    ("port", "po", "bound transport port", False),
                    ("bulk.active", "ba", "number of active bulk threads"),
                    ("bulk.queue", "bq", "number of bulk threads in queue"),
                    ("bulk.rejected", "br",
                     "number of rejected bulk threads"),
                    ("index.active", "ia",
                     "number of active index threads"),
                    ("index.queue", "iq",
                     "number of index threads in queue"),
                    ("index.rejected", "ir",
                     "number of rejected index threads"),
                    ("search.active", "sa",
                     "number of active search threads"),
                    ("search.queue", "sq",
                     "number of search threads in queue"),
                    ("search.rejected", "sr",
                     "number of rejected search threads")],
}

# thread pools: every pool exposes hidden active/queue/rejected columns
# selectable by alias (ref: RestThreadPoolAction SUPPORTED_NAMES/ALIASES)
_POOL_ALIASES = [("bulk", "b"), ("flush", "f"), ("generic", "ge"),
                 ("get", "g"), ("index", "i"), ("listener", "li"),
                 ("management", "ma"), ("optimize", "o"),
                 ("percolate", "p"), ("refresh", "r"), ("search", "s"),
                 ("snapshot", "sn"), ("suggest", "su"), ("warmer", "w")]
_DEFAULT_POOLS = {"bulk", "index", "search"}
for _pool, _pa in _POOL_ALIASES:
    for _suffix, _sa in (("active", "a"), ("queue", "q"),
                         ("rejected", "r")):
        _shown = _pool in _DEFAULT_POOLS
        _entry = (f"{_pool}.{_suffix}", f"{_pa}{_sa}",
                  f"number of {_suffix} {_pool} threads", _shown)
        if not any(e[0] == _entry[0]
                   for e in CAT_COLUMNS["thread_pool"]):
            CAT_COLUMNS["thread_pool"].append(_entry)

# cat.shards exposes the full per-shard stats column set (hidden by
# default) — ref: RestShardsAction.getTableWithHeader
CAT_COLUMNS["shards"] += [
    (n, "", d, False) for n, d in [
        ("completion.size", "size of completion"),
        ("fielddata.memory_size", "used fielddata cache"),
        ("fielddata.evictions", "fielddata evictions"),
        ("filter_cache.memory_size", "used filter cache"),
        ("filter_cache.evictions", "filter cache evictions"),
        ("flush.total", "number of flushes"),
        ("flush.total_time", "time spent in flush"),
        ("get.current", "number of current get ops"),
        ("get.time", "time spent in get"),
        ("get.total", "number of get ops"),
        ("get.exists_time", "time spent in successful gets"),
        ("get.exists_total", "number of successful gets"),
        ("get.missing_time", "time spent in failed gets"),
        ("get.missing_total", "number of failed gets"),
        ("id_cache.memory_size", "used id cache"),
        ("indexing.delete_current", "number of current deletions"),
        ("indexing.delete_time", "time spent in deletions"),
        ("indexing.delete_total", "number of delete ops"),
        ("indexing.index_current", "number of current indexing ops"),
        ("indexing.index_time", "time spent in indexing"),
        ("indexing.index_total", "number of indexing ops"),
        ("merges.current", "number of current merges"),
        ("merges.current_docs", "number of current merging docs"),
        ("merges.current_size", "size of current merges"),
        ("merges.total", "number of completed merge ops"),
        ("merges.total_docs", "docs merged"),
        ("merges.total_size", "size merged"),
        ("merges.total_time", "time spent in merges"),
        ("percolate.current", "number of current percolations"),
        ("percolate.memory_size", "memory used by percolator"),
        ("percolate.queries", "number of registered percolation queries"),
        ("percolate.time", "time spent percolating"),
        ("percolate.total", "total percolations"),
        ("refresh.total", "total refreshes"),
        ("refresh.time", "time spent in refreshes"),
        ("search.fetch_current", "current fetch phase ops"),
        ("search.fetch_time", "time spent in fetch phase"),
        ("search.fetch_total", "total fetch ops"),
        ("search.open_contexts", "open search contexts"),
        ("search.query_current", "current query phase ops"),
        ("search.query_time", "time spent in query phase"),
        ("search.query_total", "total query phase ops"),
        ("segments.count", "number of segments"),
        ("segments.memory", "memory used by segments"),
        ("segments.index_writer_memory", "memory used by index writer"),
        ("segments.index_writer_max_memory",
         "maximum memory index writer may use"),
        ("segments.version_map_memory", "memory used by version map"),
        ("segments.fixed_bitset_memory",
         "memory used by fixed bit sets"),
        ("warmer.current", "current warmer ops"),
        ("warmer.total", "total warmer ops"),
        ("warmer.total_time", "time spent in warmers"),
    ]]

# byte-valued columns (raw ints in rows) per endpoint: rendered human
# by default, or scaled by the ?bytes= unit (ref: RestTable byte cells)
CAT_BYTE_COLS: dict[str, set] = {
    "allocation": {"disk.used", "disk.avail", "disk.total"},
    "indices": {"store.size", "pri.store.size"},
    "shards": {"store"},
    "segments": {"size"},
    "nodes": {"heap.current", "heap.max"},
    "fielddata": "ALL_BUT_META",   # every per-field column + total
}
_BYTE_UNITS_CAT = {"b": 1, "k": 1024, "kb": 1024, "m": 1024 ** 2,
                   "mb": 1024 ** 2, "g": 1024 ** 3, "gb": 1024 ** 3,
                   "t": 1024 ** 4, "tb": 1024 ** 4}
_NUMERIC_CELL_RE = re.compile(
    r"^-?\d+(\.\d+)?([kmgtp]?b|%)?$")


def _cat_node_id(name: str) -> str:
    """Stable 4-char node id for _cat rows (md5, not the per-process
    randomized str hash, so ids match across endpoints and restarts)."""
    import hashlib
    return hashlib.md5(name.encode()).hexdigest()[:4]


def _human_bytes(n: int) -> str:
    """ES ByteSizeValue.toString: one decimal, trailing .0 dropped."""
    n = int(n)
    for unit, div in (("gb", 1024 ** 3), ("mb", 1024 ** 2),
                      ("kb", 1024)):
        if n >= div:
            v = n / div
            s = f"{v:.1f}"
            if s.endswith(".0"):
                s = s[:-2]
            return s + unit
    return f"{n}b"


def _cat_text(rows, params: dict, endpoint: str | None = None) -> str:
    """Render a _cat result as the aligned text table the reference's
    RestTable produces: every cell padded to the column width plus one
    trailing space, numeric columns right-justified. Supports v (header
    row), h (column select incl. aliases), help (column listing), bytes
    (byte-unit scaling)."""
    if not isinstance(rows, list):
        return str(rows)
    spec = [(e[0], e[1], e[2], e[3] if len(e) > 3 else True)
            for e in CAT_COLUMNS.get(endpoint or "", [])]
    if params.get("help") in ("true", ""):
        if spec:
            w_n = max(len(n) for n, _a, _d, _s in spec)
            w_a = max((len(a) for _n, a, _d, _s in spec), default=0)
            return "".join(
                f"{n.ljust(w_n)} | {a.ljust(w_a)} | {d}\n"
                for n, a, d, _s in spec)
        cols: list[str] = []
        for r in rows:
            for k in r:
                if k not in cols:
                    cols.append(k)
        return "".join(f"{c} | | \n" for c in cols) or "\n"
    # column order: schema order (default-visible) when declared, else
    # first-row insertion order
    if spec:
        columns = [n for n, _a, _d, shown in spec if shown]
        alias_map = {a: n for n, a, _d, _s in spec if a}
    else:
        columns = []
        for r in rows:
            for k in r:
                if k not in columns:
                    columns.append(k)
        alias_map = {}
    labels = None
    if params.get("h"):
        # header shows the REQUESTED token (alias text included); value
        # lookup resolves through the alias map. Unknown tokens are
        # dropped silently (ref: RestTable display headers)
        spec_names = {n for n, _a, _d, _s in spec}
        row_keys = {k for r in rows for k in r}
        columns, labels = [], []
        for tok in params["h"].split(","):
            resolved = alias_map.get(tok, tok)
            if resolved in spec_names or resolved in row_keys:
                columns.append(resolved)
                labels.append(tok)
    if not rows:
        return "\n"
    # byte-valued cells: human units by default, ?bytes= scales
    byte_cols = CAT_BYTE_COLS.get(endpoint or "")
    unit = _BYTE_UNITS_CAT.get(str(params.get("bytes", "")).lower())

    def fmt(col: str, v) -> str:
        if v is None:
            return ""
        is_bytes = byte_cols is not None and (
            byte_cols == "ALL_BUT_META"
            and col not in ("id", "host", "ip", "node")
            or isinstance(byte_cols, set) and col in byte_cols)
        if is_bytes and isinstance(v, (int, float)):
            if unit:
                return str(int(v) // unit)
            return _human_bytes(int(v))
        if isinstance(v, bool):
            return "true" if v else "false"
        return str(v)

    cells = [[fmt(c, r.get(c)) for c in columns] for r in rows]
    header = ([list(labels or columns)]
              if params.get("v") in ("true", "") else [])
    table = header + cells
    widths = [max(len(row[i]) for row in table)
              for i in range(len(columns))]
    # a column whose every non-empty DATA cell is numeric/size/percent
    # right-justifies (ref: RestTable alignment by cell type)
    right = []
    for i in range(len(columns)):
        vals = [row[i] for row in cells if row[i] != ""]
        right.append(bool(vals) and all(
            _NUMERIC_CELL_RE.match(v) for v in vals))
    lines = []
    for ri, row in enumerate(table):
        is_header = header and ri == 0
        # RestTable pads every cell (also the last) and separates with
        # one space, leaving trailing whitespace the YAML regexes expect
        lines.append(" ".join(
            (cell.ljust(widths[i]) if is_header or not right[i]
             else cell.rjust(widths[i]))
            for i, cell in enumerate(row)) + " ")
    return "\n".join(lines) + "\n"


def register_routes(d: RestDispatcher) -> None:
    @d.route("GET", "/")
    def root(node, params, body):
        return {
            "name": node.name,
            "cluster_name": node.cluster_name,
            "version": {"number": __version__,
                        "build_flavor": "tpu-native",
                        # jax stands where lucene stood in the reference
                        "lucene_version": "5.1.0-jax"},
            "tagline": "You Know, for (TPU) Search",
        }

    # -- cluster ----------------------------------------------------------
    @d.route("GET", "/_cluster/health")
    @d.route("GET", "/_cluster/health/{index}")
    def cluster_health(node, params, body, index=None):
        return node.cluster_health(level=params.get("level"), index=index)

    @d.route("GET", "/_cluster/stats")
    def cluster_stats(node, params, body):
        return node.stats()

    @d.route("GET", "/_nodes/stats")
    @d.route("GET", "/_nodes/stats/{metric}")
    @d.route("GET", "/_nodes/{node_id}/stats")
    @d.route("GET", "/_nodes/{node_id}/stats/{metric}")
    def nodes_stats(node, params, body, metric=None, node_id=None):
        r = node.nodes_stats()
        if metric:
            keep = {m.strip() for m in metric.split(",")}
            for nid, stats in r.get("nodes", {}).items():
                base = {k: stats[k] for k in ("name", "timestamp")
                        if k in stats}
                base.update({k: v for k, v in stats.items() if k in keep})
                r["nodes"][nid] = base
        return r

    @d.route("GET", "/_nodes")
    def nodes_info(node, params, body):
        return node.nodes_info()

    # literal /_nodes/X routes MUST register before /_nodes/{metric}:
    # dispatch is first-match, so the wildcard would shadow them
    @d.route("GET", "/_nodes/hot_threads")
    @d.route("GET", "/_nodes/{node_id}/hot_threads")
    def hot_threads(node, params, body, node_id=None):
        from ..node import parse_time_value
        n = int(params.get("threads", 3))
        ms = parse_time_value(params.get("interval", "500ms"), 500)
        return node.hot_threads(n, ms)

    @d.route("GET", "/_nodes/{metric}")
    @d.route("GET", "/_nodes/{node_id}/info/{metric}")
    def nodes_info_filtered(node, params, body, metric, node_id=None):
        r = node.nodes_info()
        keep = {m.strip() for m in metric.split(",")}
        for nid, info in r.get("nodes", {}).items():
            base = {k: info[k] for k in ("name", "version", "roles")
                    if k in info}
            base.update({k: v for k, v in info.items() if k in keep})
            r["nodes"][nid] = base
        return r

    @d.route("GET", "/_cluster/pending_tasks")
    def pending_tasks(node, params, body):
        return {"tasks": getattr(node, "pending_cluster_tasks", lambda: [])()}

    # -- device profiler (ref: hot_threads-class ops tooling; the hot
    # time here is on the DEVICE, so the capture is a jax.profiler
    # trace of live traffic) -------------------------------------------
    @d.route("POST", "/_nodes/profiler/start")
    def profiler_start(node, params, body):
        import os as _os
        from ..utils import profiler
        path = (body or {}).get("path") or params.get("path")
        if not path:
            raise IllegalArgumentError(
                "profiler start requires [path] (trace output dir)")
        # REST callers must not write trace artifact trees to arbitrary
        # node directories: the dir is resolved UNDER data_path, with
        # absolute and parent-escaping paths rejected
        path = str(path)
        if not node.data_path:
            raise IllegalArgumentError(
                "profiler start requires a node [path.data] to resolve "
                "the trace dir under")
        if _os.path.isabs(path) or ".." in path.split(_os.sep):
            raise IllegalArgumentError(
                f"profiler [path] must be relative to the node data "
                f"path (no absolute or '..' components): [{path}]")
        base = _os.path.realpath(node.data_path)
        target = _os.path.realpath(_os.path.join(base, path))
        if target != base and not target.startswith(base + _os.sep):
            raise IllegalArgumentError(
                f"profiler [path] escapes the node data path: [{path}]")
        return profiler.start(target)

    @d.route("POST", "/_nodes/profiler/stop")
    def profiler_stop(node, params, body):
        from ..utils import profiler
        return profiler.stop()

    @d.route("GET", "/_nodes/profiler")
    def profiler_status(node, params, body):
        from ..utils import profiler
        return profiler.status()

    @d.route("GET", "/_cluster/allocation/explain")
    @d.route("POST", "/_cluster/allocation/explain")
    def allocation_explain(node, params, body):
        """Per-node, per-decider allocation decisions for one shard
        copy. The embedded node mirrors itself into a one-node
        ClusterState and runs the REAL deciders; multi-node clusters
        answer through ClusterNode.allocation_explain."""
        from ..cluster.allocation import AllocationService
        from ..cluster.state import (ClusterState, DiscoveryNode,
                                     DiscoveryNodes, IndexMetadata,
                                     IndexRoutingTable, Metadata,
                                     RoutingTable, ShardState)
        body = body or {}
        index = body.get("index", params.get("index"))
        if index is None:
            if not node.indices:
                raise IllegalArgumentError(
                    "no unassigned shard to explain; specify index/"
                    "shard/primary")
            index = next(iter(node.indices))
        svc = node._index(str(index))
        shard_id = int(body.get("shard", params.get("shard", 0)))
        primary = str(body.get("primary",
                               params.get("primary", True))).lower() \
            not in ("false", "0")
        local = DiscoveryNode(node_id=node.name or "local")
        tbl = IndexRoutingTable.new(str(index), svc.num_shards, 0)
        started = IndexRoutingTable(
            str(index), tuple(
                type(g)(g.index, g.shard, tuple(
                    c.initialize(local.node_id).start()
                    for c in g.copies))
                for g in tbl.shards))
        state = ClusterState(
            nodes=DiscoveryNodes(nodes={local.node_id: local},
                                 master_node_id=local.node_id,
                                 local_node_id=local.node_id),
            metadata=Metadata(indices={str(index): IndexMetadata(
                index=str(index), number_of_shards=svc.num_shards,
                number_of_replicas=0)}),
            routing_table=RoutingTable(
                indices={str(index): started}))
        return AllocationService().explain_shard(state, str(index),
                                                 shard_id, primary)

    @d.route("POST", "/_cluster/reroute")
    def cluster_reroute(node, params, body):
        # single-node: commands validated and acked; allocation is
        # identity (ref: action/admin/cluster/reroute/ +
        # RoutingExplanations when ?explain)
        out: dict = {"acknowledged": True,
                     "state": {"cluster_name": node.cluster_name}}
        metric = params.get("metric")
        if metric:
            state = node.cluster_state(metric)
            state.pop("cluster_name", None)
            out["state"].update(state)
        if _truthy(params, "explain"):
            explanations = []
            for cmd in (body or {}).get("commands") or []:
                name, args = next(iter(cmd.items()))
                args = dict(args or {})
                if name == "cancel":
                    args.setdefault("allow_primary", False)
                    decision = {
                        "decider": "cancel_allocation_command",
                        "decision": "NO",
                        "explanation":
                            f"can't cancel [{args.get('shard')}] on "
                            f"node [{args.get('node')}]: shard not "
                            f"found or not cancellable"}
                else:
                    decision = {"decider": f"{name}_allocation_command",
                                "decision": "NO",
                                "explanation": f"single-node cluster "
                                               f"cannot [{name}]"}
                explanations.append({"command": name,
                                     "parameters": args,
                                     "decisions": [decision]})
            out["explanations"] = explanations
        return out

    @d.route("GET", "/_cat/thread_pool")
    def cat_thread_pool(node, params, body):
        import os as _os
        st = node.thread_pool.stats()

        def pool(name):
            s = st.get(name, {})
            return (s.get("active", 0), s.get("queue", 0),
                    s.get("rejected", 0))
        row = {"pid": _os.getpid(), "id": _cat_node_id(node.name),
               "host": "127.0.0.1", "ip": "127.0.0.1", "port": "-"}
        for pname, _alias in _POOL_ALIASES:
            a, q, rj = pool(pname)
            row[f"{pname}.active"] = a
            row[f"{pname}.queue"] = q
            row[f"{pname}.rejected"] = rj
            row[f"{pname}.type"] = "fixed"
            row[f"{pname}.size"] = 4
            row[f"{pname}.queueSize"] = ""
            row[f"{pname}.largest"] = a
            row[f"{pname}.completed"] = 0
            row[f"{pname}.min"] = ""
            row[f"{pname}.max"] = ""
            row[f"{pname}.keepAlive"] = ""
        return [row]

    @d.route("GET", "/_cat/allocation")
    @d.route("GET", "/_cat/allocation/{node_id}")
    def cat_allocation(node, params, body, node_id=None):
        if node_id is not None and node_id not in (
                "_master", "_local", node.name, "*"):
            return []
        shards = sum(len(s.shards) for s in node.indices.values())
        used = sum(seg.nbytes() for svc in node.indices.values()
                   for eng in svc.shards.values()
                   for seg in eng.segments)
        avail = 1 << 30
        total = used + avail
        return [{"shards": shards, "disk.used": used,
                 "disk.avail": avail, "disk.total": total,
                 "disk.percent": int(used * 100 / total),
                 "host": "127.0.0.1", "ip": "127.0.0.1",
                 "node": node.name}]

    @d.route("GET", "/_cat/pending_tasks")
    def cat_pending_tasks(node, params, body):
        return []

    @d.route("GET", "/_cat/plugins")
    def cat_plugins(node, params, body):
        return [{"id": _cat_node_id(node.name), "name": node.name,
                 "component": p["name"], "version": p["version"],
                 "type": "j", "url": "",
                 "description": p["description"]}
                for p in node.plugins.info()]

    @d.route("GET", "/_cat/nodeattrs")
    def cat_nodeattrs(node, params, body):
        return [{"node": node.name, "attr": "accelerator",
                 "value": "tpu"}]

    @d.route("GET", "/_cat/fielddata")
    @d.route("GET", "/_cat/fielddata/{fields}")
    def cat_fielddata(node, params, body, fields=None):
        # one row per node: total + one byte column per loaded field
        # (ref: RestFielddataAction)
        per_field: dict[str, int] = {}
        for name, svc in sorted(node.indices.items()):
            for sid, eng in svc.shards.items():
                for seg in eng.segments:
                    for col in (*seg.keywords.values(),
                                *seg.numerics.values()):
                        fname = col.name
                        if fname.endswith(".keyword") \
                                and fname[:-8] in seg.text:
                            # dynamic keyword twin: fielddata loaded on
                            # behalf of the parent text field
                            fname = fname[:-8]
                        per_field[fname] = (
                            per_field.get(fname, 0) + col.nbytes())
        want = (params.get("fields") or fields)
        if want:
            sel = [f.strip() for f in want.split(",")]
            shown = {f: per_field.get(f, 0) for f in sel
                     if f in per_field}
        else:
            shown = per_field
        row = {"id": _cat_node_id(node.name),
               "host": "127.0.0.1", "ip": "127.0.0.1",
               "node": node.name,
               "total": sum(per_field.values())}
        row.update(sorted(shown.items()))
        return [row]

    @d.route("GET", "/_cat/recovery")
    @d.route("GET", "/_cat/recovery/{index}")
    def cat_recovery(node, params, body, index=None):
        out = []
        for name, svc in sorted(node.indices.items()):
            if index and name != index:
                continue
            for sid, eng in svc.shards.items():
                size = eng.segment_stats()["memory_in_bytes"]
                nfiles = len(eng.segments)
                out.append({
                    "index": name, "shard": sid, "time": 0,
                    "type": "gateway",
                    # a corrupt-contained shard surfaces here too
                    # (recovery_status carries the structured reason)
                    "stage": ("failed" if eng.failed is not None
                              else "done"),
                    "source_host": "127.0.0.1",
                    "target_host": "127.0.0.1",
                    "repository": "n/a", "snapshot": "n/a",
                    "files": nfiles, "files_percent": "100.0%",
                    "bytes": size, "bytes_percent": "100.0%",
                    "total_files": nfiles, "total_bytes": size,
                    "translog": 0, "translog_percent": "100.0%",
                    "total_translog": 0})
        return out

    @d.route("GET", "/_cat/repositories")
    def cat_repositories(node, params, body):
        repos = getattr(node.snapshots, "repositories", {})
        return [{"id": rid, "type": "fs"} for rid in sorted(repos)]

    @d.route("GET", "/_cat/snapshots/{repo}")
    def cat_snapshots(node, params, body, repo):
        r = node.snapshots.repositories.get(repo)
        if r is None:
            return []
        return [{"id": sid, "status": "SUCCESS"}
                for sid in r.list_snapshots()]

    def _stats_params(params):
        def _csv(key):
            return params[key].split(",") if params.get(key) else None
        return {
            "level": params.get("level", "indices"),
            "types": _csv("types"),
            "groups": _csv("groups"),
            "fields": _csv("fields"),
            "fielddata_fields": _csv("fielddata_fields"),
            "completion_fields": _csv("completion_fields"),
        }

    @d.route("GET", "/_stats")
    @d.route("GET", "/_stats/{metric}")
    def stats(node, params, body, metric=None):
        return node.indices_stats(None, metric, **_stats_params(params))

    @d.route("GET", "/_cat/indices")
    def cat_indices(node, params, body):
        return node.cat_indices()

    @d.route("GET", "/_cat/health")
    def cat_health(node, params, body):
        import datetime
        h = node.cluster_health()
        now = datetime.datetime.now(datetime.timezone.utc)
        row = {}
        if params.get("ts") != "false":
            row["epoch"] = int(now.timestamp())
            row["timestamp"] = now.strftime("%H:%M:%S")
        row.update({
            "cluster": h["cluster_name"], "status": h["status"],
            "node.total": h["number_of_nodes"],
            "node.data": h.get("number_of_data_nodes",
                               h["number_of_nodes"]),
            "shards": h["active_shards"],
            "pri": h.get("active_primary_shards", h["active_shards"]),
            "relo": h.get("relocating_shards", 0),
            "init": h.get("initializing_shards", 0),
            "unassign": h.get("unassigned_shards", 0),
            "pending_tasks": h.get("number_of_pending_tasks", 0)})
        return [row]

    # -- search (order matters: register before /{index} wildcards) -------
    @d.route("GET", "/_search")
    @d.route("POST", "/_search")
    def search_all(node, params, body):
        return node.search(None, _search_body(params, body),
                           scroll=params.get("scroll"),
                           search_type=params.get("search_type"),
                           tenant=params.get("tenant_id"),
                           request=params.get("__request"))

    @d.route("GET", "/{index}/_search")
    @d.route("POST", "/{index}/_search")
    def search(node, params, body, index):
        return node.search(index, _search_body(params, body),
                           scroll=params.get("scroll"),
                           search_type=params.get("search_type"),
                           tenant=params.get("tenant_id"),
                           request=params.get("__request"))

    # indexed search templates (ref: RestPutSearchTemplateAction — ES 2.0
    # stored them in the .scripts index under lang `mustache`)
    @d.route("PUT", "/_search/template/{id}")
    @d.route("POST", "/_search/template/{id}")
    def put_indexed_template(node, params, body, id):
        body = body or {}
        src = body.get("template", body)
        if isinstance(src, dict):
            # compact separators: the stored form is matched by regex in
            # clients/tests (query\S\S\S\Smatch_all)
            src = json.dumps(src, separators=(",", ":"))
        src = str(src)
        if "{{}}" in src:
            # ref: MustacheScriptEngineService compile failure on an
            # empty mustache tag
            raise IllegalArgumentError(
                f"Unable to parse template [{src[:80]}]")
        node.put_stored_script(f"__template__{id}", src)
        return {"acknowledged": True, "_id": id, "created": True,
                "_version": 1}

    @d.route("GET", "/_search/template/{id}")
    def get_indexed_template(node, params, body, id):
        from ..script import ScriptService
        try:
            src = ScriptService.instance().get_stored(f"__template__{id}")
        except ElasticsearchTpuError:
            return RestStatus(404, {"_index": ".scripts", "_id": id,
                                    "found": False, "lang": "mustache"})
        return {"_index": ".scripts", "_id": id, "found": True,
                "lang": "mustache", "template": src, "_version": 1}

    @d.route("DELETE", "/_search/template/{id}")
    def delete_indexed_template(node, params, body, id):
        found = node.delete_stored_script(f"__template__{id}")
        if not found:
            return RestStatus(404, {"found": False,
                                    "_index": ".scripts", "_id": id,
                                    "_version": 1})
        return {"found": True, "_index": ".scripts", "_id": id,
                "_version": 2, "acknowledged": True}

    @d.route("GET", "/_search/template")
    @d.route("POST", "/_search/template")
    def search_template_all(node, params, body):
        return node.search_template(None, body)

    @d.route("GET", "/{index}/_search/template")
    @d.route("POST", "/{index}/_search/template")
    def search_template(node, params, body, index):
        return node.search_template(index, body)

    @d.route("GET", "/_render/template")
    @d.route("POST", "/_render/template")
    def render_template(node, params, body):
        return node.render_template(body)

    def _tv_body(params, body):
        body = dict(body or {})
        for flag in ("term_statistics", "field_statistics", "positions",
                     "offsets", "payloads", "realtime"):
            if flag in params and flag not in body:
                body[flag] = params[flag] in ("true", "1", "", "True")
        return body

    @d.route("GET", "/{index}/_termvectors/{id}")
    @d.route("POST", "/{index}/_termvectors/{id}")
    def termvectors(node, params, body, index, id):
        fields = params.get("fields")
        return node.term_vectors(index, id, _tv_body(params, body),
                                 fields.split(",") if fields else None)

    @d.route("GET", "/{index}/{type}/{id}/_termvectors")
    @d.route("POST", "/{index}/{type}/{id}/_termvectors")
    @d.route("GET", "/{index}/{type}/{id}/_termvector")
    @d.route("POST", "/{index}/{type}/{id}/_termvector")
    def termvectors_typed(node, params, body, index, type, id):
        fields = params.get("fields")
        r = node.term_vectors(index, id, _tv_body(params, body),
                              fields.split(",") if fields else None)
        r["_type"] = type
        return r

    @d.route("GET", "/_mtermvectors")
    @d.route("POST", "/_mtermvectors")
    @d.route("GET", "/{index}/_mtermvectors")
    @d.route("POST", "/{index}/_mtermvectors")
    @d.route("GET", "/{index}/{type}/_mtermvectors")
    @d.route("POST", "/{index}/{type}/_mtermvectors")
    def mtermvectors(node, params, body, index=None, type=None):
        if body is None and params.get("ids"):
            body = {"docs": [{"_id": i}
                             for i in params["ids"].split(",")]}
        body = dict(body or {})
        defaults = _tv_body(params, {})
        if defaults and body.get("docs"):
            body["docs"] = [{**defaults, **spec}
                            for spec in body["docs"]]
        return node.mtermvectors(index, body)

    @d.route("POST", "/_msearch")
    @d.route("POST", "/{index}/_msearch")
    def msearch(node, params, body, index=None):
        # body is a list of (header, body) pairs from ndjson. The whole
        # batch rides ONE dispatch-scheduler pass (node.msearch):
        # identical-plan items coalesce into one batched device program,
        # the rest pipeline their dispatch round trips; items answer with
        # their own took/status. Headers may carry a per-item
        # search_type (ref: RestMultiSearchAction header parsing).
        requests = []
        lines = body if isinstance(body, list) else []
        for i in range(0, len(lines) - 1, 2):
            header, search_body = lines[i] or {}, lines[i + 1]
            requests.append((header.get("index", index), search_body,
                             header.get("search_type",
                                        params.get("search_type"))))
        return node.msearch(requests, tenant=params.get("tenant_id"))

    @d.route("GET", "/_count")
    @d.route("POST", "/_count")
    def count_all(node, params, body):
        return node.count(None, _body_query(params, body))

    @d.route("GET", "/{index}/_count")
    @d.route("POST", "/{index}/_count")
    def count(node, params, body, index):
        return node.count(index, _body_query(params, body))

    # -- bulk -------------------------------------------------------------
    @d.route("POST", "/_bulk")
    @d.route("PUT", "/_bulk")
    @d.route("POST", "/{index}/_bulk")
    def bulk(node, params, body, index=None, type=None):
        lines = body if isinstance(body, list) else []
        ops = []
        i = 0
        while i < len(lines):
            action_line = lines[i]
            action, meta = next(iter(action_line.items()))
            meta = meta or {}
            did = meta.get("_id")
            payload = {"_index": meta.get("_index", index),
                       "_id": str(did) if did is not None else None,
                       "_type": meta.get("_type", type),
                       "_routing": meta.get("_routing",
                                            meta.get("routing"))}
            if action in ("index", "create", "update"):
                i += 1
                payload["doc"] = lines[i] if i < len(lines) else {}
            ops.append((action, payload))
            i += 1
        refresh = params.get("refresh") in ("true", "", "wait_for")
        return node.bulk(ops, refresh=refresh)

    @d.route("POST", "/{index}/{type}/_bulk")
    @d.route("PUT", "/{index}/{type}/_bulk")
    def bulk_typed(node, params, body, index, type):
        return bulk(node, params, body, index, type)

    # -- maintenance ------------------------------------------------------
    @d.route("POST", "/_refresh")
    @d.route("POST", "/{index}/_refresh")
    @d.route("GET", "/{index}/_refresh")
    def refresh(node, params, body, index=None):
        return node.refresh(index)

    @d.route("POST", "/_flush")
    @d.route("POST", "/{index}/_flush")
    def flush(node, params, body, index=None):
        return node.flush(index)

    @d.route("POST", "/{index}/_forcemerge")
    @d.route("POST", "/{index}/_optimize")  # legacy 2.x name
    def forcemerge(node, params, body, index):
        return node.force_merge(index,
                                int(params.get("max_num_segments", 1)))

    # -- mappings / settings ----------------------------------------------
    @d.route("GET", "/_mapping")
    def get_mapping_all(node, params, body):
        return node.get_mapping(
            None, expand_wildcards=params.get("expand_wildcards", "open"))

    @d.route("GET", "/{index}/_mapping")
    def get_mapping(node, params, body, index):
        return node.get_mapping(
            index, expand_wildcards=params.get("expand_wildcards", "open"))

    @d.route("PUT", "/{index}/_mapping")
    @d.route("POST", "/{index}/_mapping")
    def put_mapping(node, params, body, index):
        return node.put_mapping(index, body or {})

    @d.route("GET", "/_settings")
    @d.route("GET", "/{index}/_settings")
    @d.route("GET", "/_settings/{name}")
    @d.route("GET", "/{index}/_settings/{name}")
    def get_settings(node, params, body, index=None, name=None):
        return node.get_settings(
            index, flat=params.get("flat_settings") in ("true", ""),
            name=name,
            expand_wildcards=params.get("expand_wildcards", "open"))

    # -- documents --------------------------------------------------------
    @d.route("POST", "/{index}/_doc")
    def index_auto_id(node, params, body, index):
        return node.index_doc(index, None, body or {},
                              refresh=params.get("refresh") == "true")

    @d.route("PUT", "/{index}/_create/{id}")
    @d.route("POST", "/{index}/_create/{id}")
    def create_doc(node, params, body, index, id):
        params = {**params, "op_type": "create"}
        return index_doc(node, params, body, index, id)

    @d.route("PUT", "/{index}/_doc/{id}")
    @d.route("POST", "/{index}/_doc/{id}")
    def index_doc(node, params, body, index, id, doc_type=None):
        version = params.get("version")
        vt = params.get("version_type", "internal")
        # op_type=create fails on ANY existing doc, independent of
        # version type: the engine decides, under its lock (ref:
        # TransportIndexAction autogenerate/create →
        # DocumentAlreadyExistsException)
        op_type = "create" if params.get("op_type") == "create" else "index"
        return node.index_doc(index, id, body or {},
                              version=int(version) if version else None,
                              routing=params.get("routing"),
                              refresh=_truthy(params, "refresh"),
                              ttl=params.get("ttl"),
                              doc_type=doc_type,
                              version_type=vt,
                              parent=params.get("parent"),
                              timestamp=params.get("timestamp"),
                              op_type=op_type)

    @d.route("GET", "/{index}/_doc/{id}")
    def get_doc(node, params, body, index, id, doc_type=None):
        realtime = params.get("realtime") not in ("false", "0")
        if _truthy(params, "refresh"):
            node.refresh(index)   # refresh-before-read (ref: GetRequest.refresh)
        r = node.get_doc(index, id, routing=params.get("routing"),
                         doc_type=doc_type, realtime=realtime,
                         parent=params.get("parent"))
        want_version = params.get("version")
        # internal/external/external_gte all require equality on reads;
        # force skips the check (ref: common/lucene/uid/Versions +
        # VersionType read-conflict rules)
        if want_version and params.get("version_type") != "force" \
                and int(want_version) != r.get("_version"):
            # ref: get API version check → VersionConflictEngineException
            from ..utils.errors import VersionConflictError
            raise VersionConflictError(index, id, r.get("_version", -1),
                                       int(want_version))
        src = r.get("_source")
        obj = (json.loads(src) if isinstance(src, (bytes, str))
               else (src or {}))
        field_list = ([f.strip() for f in str(params["fields"]).split(",")]
                      if params.get("fields") else None)
        if field_list is not None:
            flds = {}
            for f in field_list:
                if f in ("_routing", "_parent"):
                    if f in r:
                        flds[f] = r[f]
                elif f == "_timestamp":
                    ts = node._index(index).doc_ts.get(id)
                    if ts is not None:
                        flds[f] = ts
                elif f == "_ttl":
                    # remaining ttl ms from the stored expiry column
                    # (ref: TTLFieldMapper value = expiry - now)
                    try:
                        svc = node._index(index)
                        raw = svc.shard_for(
                            id, r.get("_routing")).get(id)
                        rob = raw.get("_source")
                        rob = (json.loads(rob)
                               if isinstance(rob, (bytes, str)) else rob)
                        exp = (rob or {}).get("_ttl_expiry")
                        if exp:
                            import time as _t
                            flds[f] = int(exp - _t.time() * 1000)
                    except ElasticsearchTpuError:
                        pass
                elif f in obj:
                    v = obj[f]
                    flds[f] = v if isinstance(v, list) else [v]
            if flds:
                r["fields"] = flds
            # an explicit fields list suppresses _source unless requested
            if "_source" not in field_list and "_source" not in params:
                r.pop("_source", None)
                return r
        # GET-level source filtering (ref: RestGetAction fetchSource)
        from ..search.shard_searcher import filter_source
        inc = params.get("_source_include") or params.get("_source_includes")
        exc = params.get("_source_exclude") or params.get("_source_excludes")
        sparam = params.get("_source")
        if inc or exc:
            obj = filter_source(obj, {
                "includes": inc.split(",") if inc else [],
                "excludes": exc.split(",") if exc else []})
        elif sparam == "false":
            r.pop("_source", None)
            return r
        elif sparam and sparam != "true":
            obj = filter_source(obj, sparam.split(","))
        r["_source"] = obj
        return r

    @d.route("DELETE", "/{index}/_doc/{id}")
    def delete_doc(node, params, body, index, id, doc_type=None):
        version = params.get("version")
        r = node.delete_doc(index, id,
                            version=int(version) if version else None,
                            routing=params.get("routing"),
                            refresh=_truthy(params, "refresh"),
                            doc_type=doc_type,
                            version_type=params.get("version_type",
                                                    "internal"),
                            parent=params.get("parent"))
        if not r.get("found"):
            # delete of a missing doc is a 404 with found:false
            # (ref: RestDeleteAction status mapping)
            return RestStatus(404, {**r, "found": False})
        return r

    @d.route("POST", "/{index}/_update/{id}")
    def update_doc(node, params, body, index, id, doc_type=None):
        vt = params.get("version_type", "internal")
        if vt not in ("internal", "force"):
            # ref: UpdateRequest.validate — external versioning is not
            # supported by the update API
            raise IllegalArgumentError(
                "Validation Failed: 1: version type [" + vt +
                "] is not supported by the update API;")
        version = params.get("version")
        fields = params.get("fields")
        body = dict(body or {})
        # 1.x accepted script/lang as URL params (ref: RestUpdateAction
        # request.param("script")); a body script wins over the URL one
        if params.get("script") is not None and body.get("script") is None:
            body["script"] = params["script"]
        if params.get("lang") is not None and body.get("lang") is None:
            body["lang"] = params["lang"]
        return node.update_doc(index, id, body or {},
                               refresh=_truthy(params, "refresh"),
                               doc_type=doc_type,
                               routing=params.get("routing"),
                               parent=params.get("parent"),
                               version=int(version) if version else None,
                               fields=(fields.split(",") if fields
                                       else None),
                               ttl=params.get("ttl"),
                               timestamp=params.get("timestamp"))

    # -- stored scripts (ref: RestPutIndexedScriptAction; ES 2.0 kept
    # these in the .scripts index) -------------------------------------
    @d.route("PUT", "/_scripts/{id}")
    @d.route("POST", "/_scripts/{id}")
    def put_script(node, params, body, id):
        # accepts expression scripts AND mustache search templates, with
        # string or object sources (ref: RestPutStoredScriptAction)
        body = body or {}
        spec = body.get("script", body)
        if isinstance(spec, dict):
            src = spec.get("source", spec.get("inline"))
        else:
            src = spec
        if src is None:
            raise IllegalArgumentError("stored script requires [source]")
        if isinstance(src, dict):
            src = json.dumps(src)
        node.put_stored_script(id, str(src))
        return {"acknowledged": True, "_id": id}

    @d.route("GET", "/_scripts/{id}")
    def get_script(node, params, body, id):
        from ..script import ScriptService
        # get_stored raises ScriptMissingError (404) when absent
        src = ScriptService.instance().get_stored(id)
        return {"_id": id, "found": True,
                "script": {"lang": "expression", "source": src}}

    @d.route("DELETE", "/_scripts/{id}")
    def delete_script(node, params, body, id):
        found = node.delete_stored_script(id)
        return {"acknowledged": found, "found": found}

    # -- lang-scoped indexed scripts (the 1.x .scripts-index API shape;
    # ref: RestPutIndexedScriptAction + ScriptService indexed scripts,
    # full index/get/delete version semantics) -------------------------
    def _script_version_params(params):
        v = params.get("version")
        return (int(v) if v is not None else None,
                params.get("version_type", "internal"))

    @d.route("PUT", "/_scripts/{lang}/{id}")
    @d.route("POST", "/_scripts/{lang}/{id}")
    def put_script_lang(node, params, body, lang, id):
        body = body or {}
        spec = body.get("script", body)
        if isinstance(spec, dict):
            src = spec.get("source") or spec.get("inline")
        else:
            src = spec
        if src is None:
            raise IllegalArgumentError("stored script requires [script]")
        if isinstance(src, dict):
            src = json.dumps(src)
        version, vtype = _script_version_params(params)
        v, created = node.put_stored_script_versioned(id, str(src),
                                                      lang=lang,
                                                      version=version,
                                                      version_type=vtype)
        return {"acknowledged": True, "_index": ".scripts", "_type": lang,
                "_id": id, "_version": v, "created": created}

    @d.route("GET", "/_scripts/{lang}/{id}")
    def get_script_lang(node, params, body, lang, id):
        from ..script import ScriptService
        svc = ScriptService.instance()
        meta = svc.get_meta(id)
        # indexed scripts are keyed (lang, id): .scripts stores lang as
        # the doc _type, so a different lang is a different document
        if meta is None or meta["lang"] != lang:
            return RestStatus(404, {"found": False, "lang": lang,
                                    "_index": ".scripts", "_id": id})
        version, vtype = _script_version_params(params)
        svc.check_read_version(id, version, vtype)
        return {"found": True, "lang": meta["lang"], "_index": ".scripts",
                "_id": id, "_version": meta["version"],
                "script": meta["source"]}

    @d.route("DELETE", "/_scripts/{lang}/{id}")
    def delete_script_lang(node, params, body, lang, id):
        from ..script import ScriptService
        meta = ScriptService.instance().get_meta(id)
        version, vtype = _script_version_params(params)
        if meta is not None and meta["lang"] != lang:
            meta = None  # other-lang doc: this (lang, id) is absent
        v = (node.delete_stored_script_versioned(id, version=version,
                                                 version_type=vtype)
             if meta is not None else None)
        if v is None:
            # ES deletes of missing docs answer version 1
            return RestStatus(404, {"found": False, "_index": ".scripts",
                                    "_type": lang, "_id": id,
                                    "_version": 1})
        return {"found": True, "_index": ".scripts", "_type": lang,
                "_id": id, "_version": v}

    @d.route("POST", "/_mget")
    @d.route("GET", "/_mget")
    @d.route("POST", "/{index}/_mget")
    def mget(node, params, body, index=None, type=None):
        body = body or {}
        specs = body.get("docs")
        if specs is None and "ids" in body:
            specs = [{"_id": i} for i in body["ids"]]
        if not specs:
            raise IllegalArgumentError(
                "ActionRequestValidationException: Validation Failed: "
                "1: no documents to get;")
        realtime = params.get("realtime") not in ("false", "0")
        if _truthy(params, "refresh"):
            node.refresh(index)
        url_source = params.get("_source")
        url_inc = (params.get("_source_include")
                   or params.get("_source_includes"))
        url_exc = (params.get("_source_exclude")
                   or params.get("_source_excludes"))
        url_fields = (params["fields"].split(",")
                      if params.get("fields") else None)
        docs = []
        for spec in specs:
            idx = spec.get("_index", index)
            typ = spec.get("_type", type)
            did = spec.get("_id")
            if idx is None or did is None:
                raise IllegalArgumentError(
                    "ActionRequestValidationException: Validation "
                    "Failed: 1: index is missing;"
                    if idx is None else
                    "ActionRequestValidationException: Validation "
                    "Failed: 1: id is missing;")
            did = str(did)
            routing = spec.get("routing", spec.get("_routing"))
            parent = spec.get("parent", spec.get("_parent"))
            try:
                r = node.get_doc(
                    idx, did, doc_type=typ,
                    routing=str(routing) if routing is not None else None,
                    parent=str(parent) if parent is not None else None,
                    realtime=realtime)
                if not r.get("found", True):
                    docs.append({"_index": idx, "_type": typ or "_doc",
                                 "_id": did, "found": False})
                    continue
                src = r["_source"]
                obj = (json.loads(src)
                       if isinstance(src, (bytes, str)) else src)
                r["_index"] = idx
                if typ is not None:
                    r["_type"] = typ
                want_fields = spec.get("fields", spec.get("_fields",
                                                          url_fields))
                src_spec = spec.get("_source")
                if src_spec is None and (url_inc or url_exc):
                    src_spec = {
                        "includes": url_inc.split(",") if url_inc else [],
                        "excludes": url_exc.split(",") if url_exc else []}
                if src_spec is None and url_source is not None:
                    src_spec = (True if url_source == "true" else
                                False if url_source == "false" else
                                url_source.split(","))
                if want_fields:
                    if isinstance(want_fields, str):
                        want_fields = [want_fields]
                    flds = {}
                    for f in want_fields:
                        if f in ("_routing", "_parent"):
                            if f in r:
                                flds[f] = r[f]
                        elif f in obj:
                            v = obj[f]
                            flds[f] = v if isinstance(v, list) else [v]
                    if flds:
                        r["fields"] = flds
                    if "_source" in want_fields:
                        r["_source"] = obj
                    else:
                        r.pop("_source", None)
                elif src_spec is not None:
                    from ..search.shard_searcher import filter_source
                    filtered = filter_source(obj, src_spec)
                    if filtered is None:
                        r.pop("_source", None)
                    else:
                        r["_source"] = filtered
                else:
                    r["_source"] = obj
                docs.append(r)
            except ElasticsearchTpuError:
                docs.append({"_index": idx, "_type": typ or "_doc",
                             "_id": did, "found": False})
        return {"docs": docs}

    @d.route("POST", "/{index}/{type}/_mget")
    @d.route("GET", "/{index}/{type}/_mget")
    def mget_typed(node, params, body, index, type):
        return mget(node, params, body, index, type)

    @d.route("POST", "/{index}/_analyze")
    @d.route("GET", "/{index}/_analyze")
    @d.route("POST", "/_analyze")
    @d.route("GET", "/_analyze")
    def analyze(node, params, body, index=None):
        body = body or {}
        text = body.get("text") or params.get("text") or ""
        field = body.get("field") or params.get("field")
        tokenizer_name = body.get("tokenizer") or params.get("tokenizer")
        filter_names = body.get("filters") or params.get("filters") \
            or body.get("filter") or params.get("filter")
        svc = node.indices.get(index) if index is not None else None
        if field is not None and svc is not None:
            # analyze with the FIELD's own analyzer (ref:
            # TransportAnalyzeAction field resolution)
            analyzer = svc.mappers.search_analyzer_for(field)
            fm = svc.mappers.field(field)
            if fm is not None and fm.type == "text":
                analyzer = svc.mappers.analysis.analyzer(fm.analyzer)
        elif tokenizer_name is not None:
            # ad-hoc tokenizer + filter chain (ref:
            # TransportAnalyzeAction custom analyzer assembly)
            from ..index.analysis import (Analyzer, TOKENIZER_FACTORIES,
                                          TOKEN_FILTERS)
            from ..utils.settings import Settings as _S
            tk = TOKENIZER_FACTORIES.get(tokenizer_name)
            if tk is None:
                raise IllegalArgumentError(
                    f"failed to find tokenizer [{tokenizer_name}]")
            if isinstance(filter_names, str):
                filter_names = filter_names.split(",")
            filters = []
            for fn in filter_names or []:
                f = TOKEN_FILTERS.get(fn)
                if f is None:
                    raise IllegalArgumentError(
                        f"failed to find token filter [{fn}]")
                filters.append(f)
            analyzer = Analyzer("_custom_", tk(_S.EMPTY), filters)
        else:
            name = (body.get("analyzer") or params.get("analyzer")
                    or "standard")
            if svc is not None:
                analyzer = svc.mappers.analysis.analyzer(name)
            else:
                from ..index.analysis import AnalysisService
                analyzer = AnalysisService().analyzer(name)
        texts = text if isinstance(text, list) else [text]
        tokens = []
        pos = 0
        for t in texts:
            for tok in analyzer.analyze(str(t)):
                tokens.append({"token": tok, "position": pos})
                pos += 1
        return {"tokens": tokens}

    # -- scroll (ref: RestSearchScrollAction/RestClearScrollAction) -------
    @d.route("POST", "/_search/scroll")
    @d.route("GET", "/_search/scroll")
    def scroll(node, params, body, **kw):
        body = body or {}
        sid = body.get("scroll_id") or params.get("scroll_id")
        keepalive = body.get("scroll") or params.get("scroll")
        return node.scroll(sid, keepalive,
                           tenant=params.get("tenant_id"))

    @d.route("DELETE", "/_search/scroll")
    def clear_scroll(node, params, body, **kw):
        ids = (body or {}).get("scroll_id")
        if isinstance(ids, str):
            ids = [ids]
        r = node.clear_scroll(ids)
        if r.pop("_missing", False):
            return RestStatus(404, r)
        return r

    # -- validate / explain / segments ------------------------------------
    @d.route("GET", "/_validate/query")
    @d.route("POST", "/_validate/query")
    @d.route("GET", "/{index}/_validate/query")
    @d.route("POST", "/{index}/_validate/query")
    def validate_query(node, params, body, index=None):
        return node.validate_query(index, _body_query(params, body),
                                   explain=params.get("explain") == "true")

    @d.route("GET", "/_search_shards")
    @d.route("POST", "/_search_shards")
    @d.route("GET", "/{index}/_search_shards")
    @d.route("POST", "/{index}/_search_shards")
    def search_shards(node, params, body, index=None):
        # ref: action/admin/cluster/shards/ClusterSearchShardsAction —
        # which shard copies a search against `index` would touch
        nid = node.name
        shards = []
        for svc in node._resolve(index):
            for sid in sorted(svc.shards):
                shards.append([{"index": svc.name, "node": nid,
                                "shard": sid, "primary": True,
                                "state": "STARTED",
                                "relocating_node": None}])
        return {"nodes": {nid: {"name": nid,
                                "transport_address": "local"}},
                "shards": shards}

    @d.route("GET", "/{index}/_explain/{id}")
    @d.route("POST", "/{index}/_explain/{id}")
    def explain(node, params, body, index, id):
        return node.explain_doc(index, id, _body_query(params, body))

    @d.route("GET", "/_segments")
    @d.route("GET", "/{index}/_segments")
    def segments(node, params, body, index=None):
        return node.segments(
            index,
            ignore_unavailable=_truthy(params, "ignore_unavailable"),
            allow_no_indices=params.get("allow_no_indices") != "false")

    # -- aliases ----------------------------------------------------------
    @d.route("POST", "/_aliases")
    def update_aliases(node, params, body, **kw):
        return node.update_aliases((body or {}).get("actions") or [])

    @d.route("PUT", "/{index}/_alias/{alias}")
    @d.route("POST", "/{index}/_alias/{alias}")
    @d.route("PUT", "/{index}/_aliases/{alias}")
    @d.route("POST", "/{index}/_aliases/{alias}")
    def put_alias(node, params, body, index, alias):
        return node.put_alias(index, alias, body)

    @d.route("PUT", "/_alias/{alias}")
    @d.route("POST", "/_alias/{alias}")
    def put_alias_noindex(node, params, body, alias):
        # ref: IndicesAliasesRequest.validate — add requires an index
        raise IllegalArgumentError("alias action requires an [index]")

    @d.route("DELETE", "/{index}/_alias/{alias}")
    @d.route("DELETE", "/{index}/_aliases/{alias}")
    def delete_alias(node, params, body, index, alias):
        return node.delete_alias(index, alias)

    @d.route("GET", "/_alias")
    @d.route("GET", "/{index}/_alias")
    def get_alias_all(node, params, body, index=None):
        return node.get_aliases(index, include_empty=True)

    @d.route("GET", "/_aliases")
    @d.route("GET", "/{index}/_aliases")
    @d.route("GET", "/_aliases/{name}")
    @d.route("GET", "/{index}/_aliases/{name}")
    def get_aliases(node, params, body, index=None, name=None):
        # /_aliases always lists every resolved index (empty map when
        # no alias matches) — ref: RestGetIndicesAliasesAction
        return node.get_aliases(index, name=name, include_empty=True)

    @d.route("GET", "/_alias/{name}")
    @d.route("GET", "/{index}/_alias/{name}")
    def get_alias_by_name(node, params, body, name, index=None):
        r = node.get_aliases(index, name=name)
        if not any(v.get("aliases") for v in r.values()):
            # exists_alias (HEAD) needs the 404, as does a cluster-wide
            # GET for an absent alias; an index-scoped GET returns the
            # empty body with 200 (ref: RestAliasesExistAction vs
            # RestGetAliasesAction missing-alias handling)
            if params.get("__method") == "HEAD" or index is None:
                return RestStatus(404, r)
        return r

    # -- templates --------------------------------------------------------
    @d.route("PUT", "/_template/{name}")
    @d.route("POST", "/_template/{name}")
    def put_template(node, params, body, name):
        return node.put_template(name, body or {},
                                 create=_truthy(params, "create"))

    @d.route("GET", "/_template")
    @d.route("GET", "/_template/{name}")
    def get_template(node, params, body, name=None):
        return node.get_templates(
            name, flat=_truthy(params, "flat_settings"))

    @d.route("DELETE", "/_template/{name}")
    def delete_template(node, params, body, name):
        return node.delete_template(name)

    # -- open/close -------------------------------------------------------
    @d.route("POST", "/{index}/_close")
    def close_index(node, params, body, index):
        return node.close_index(index)

    @d.route("POST", "/{index}/_open")
    def open_index(node, params, body, index):
        return node.open_index(index)

    # -- snapshots (ref: rest/action/admin/cluster/snapshots/) ------------
    @d.route("PUT", "/_snapshot/{repo}")
    @d.route("POST", "/_snapshot/{repo}")
    def put_repository(node, params, body, repo):
        body = body or {}
        return node.snapshots.put_repository(
            repo, body.get("type", "fs"), body.get("settings") or {})

    @d.route("PUT", "/_snapshot/{repo}/{snap}")
    def create_snapshot(node, params, body, repo, snap):
        return node.snapshots.create_snapshot(
            repo, snap, (body or {}).get("indices"))

    @d.route("GET", "/_snapshot")
    @d.route("GET", "/_snapshot/{repo}")
    def get_repository(node, params, body, repo=None):
        return node.snapshots.get_repositories(repo)

    @d.route("POST", "/_snapshot/{repo}/_verify")
    def verify_repository(node, params, body, repo):
        return node.snapshots.verify_repository(repo)

    @d.route("GET", "/_snapshot/{repo}/{snap}")
    def get_snapshots(node, params, body, repo, snap):
        return node.snapshots.get_snapshots(repo, snap)

    @d.route("DELETE", "/_snapshot/{repo}/{snap}")
    def delete_snapshot(node, params, body, repo, snap):
        return node.snapshots.delete_snapshot(repo, snap)

    @d.route("POST", "/_snapshot/{repo}/{snap}/_restore")
    def restore_snapshot(node, params, body, repo, snap):
        body = body or {}
        return node.snapshots.restore_snapshot(
            repo, snap, body.get("indices"),
            body.get("rename_pattern"), body.get("rename_replacement"))

    # -- cluster state / settings / cat -----------------------------------
    @d.route("GET", "/_cluster/state")
    def cluster_state(node, params, body):
        return node.cluster_state()

    @d.route("GET", "/_cluster/state/{metrics}")
    @d.route("GET", "/_cluster/state/{metrics}/{index}")
    def cluster_state_filtered(node, params, body, metrics, index=None):
        return node.cluster_state(
            metrics, index,
            expand_wildcards=params.get("expand_wildcards", "open"),
            ignore_unavailable=_truthy(params, "ignore_unavailable"),
            allow_no_indices=params.get("allow_no_indices") != "false")

    @d.route("GET", "/_cluster/settings")
    def get_cluster_settings(node, params, body):
        return node.get_cluster_settings()

    @d.route("PUT", "/_cluster/settings")
    def put_cluster_settings(node, params, body):
        return node.put_cluster_settings(body or {})

    @d.route("GET", "/_cat/shards")
    @d.route("GET", "/_cat/shards/{index}")
    def cat_shards(node, params, body, index=None):
        return node.cat_shards(index)

    @d.route("GET", "/_cat/count")
    @d.route("GET", "/_cat/count/{index}")
    def cat_count(node, params, body, index=None):
        return node.cat_count(index)

    @d.route("GET", "/_cat/nodes")
    def cat_nodes(node, params, body):
        from ..utils import monitor
        rt = monitor.runtime_stats()
        heap_used = rt.get("mem", {}).get("resident_in_bytes", 1 << 20)
        heap_max = max(heap_used * 2, 1)
        try:
            load = __import__("os").getloadavg()[0]
        except OSError:
            load = 0.0
        return [{"host": "127.0.0.1", "ip": "127.0.0.1",
                 "heap.current": heap_used,
                 "heap.percent": int(heap_used * 100 / heap_max),
                 "heap.max": heap_max,
                 "ram.percent": 42,
                 "file_desc.current": 1, "file_desc.percent": 1,
                 "file_desc.max": 1024,
                 "load": round(load, 2),
                 "node.role": "d", "master": "*",
                 "name": node.name}]

    @d.route("GET", "/_cat/master")
    def cat_master(node, params, body):
        return [{"node": node.name}]

    @d.route("GET", "/_cat/aliases")
    @d.route("GET", "/_cat/aliases/{name}")
    def cat_aliases(node, params, body, name=None):
        import fnmatch
        out = []
        for a, targets in sorted(node._aliases.items()):
            if name is not None and not any(
                    fnmatch.fnmatch(a, p) for p in name.split(",")):
                continue
            for i in sorted(targets):
                meta = node.alias_meta(a, i)
                out.append({"alias": a, "index": i,
                            "filter": "*" if meta.get("filter") else "-",
                            "routing.index":
                                meta.get("index_routing", "-"),
                            "routing.search":
                                meta.get("search_routing", "-")})
        return out

    @d.route("GET", "/_cat/templates")
    def cat_templates(node, params, body):
        return [{"name": n, "index_patterns": t["patterns"],
                 "order": t["order"]}
                for n, t in sorted(node._templates.items())]

    @d.route("GET", "/_cat/segments")
    @d.route("GET", "/_cat/segments/{index}")
    def cat_segments(node, params, body, index=None):
        # one row per segment (ref: RestSegmentsAction row shape;
        # version is Lucene-style numeric — the jax build reports the
        # columnar format version)
        out = []
        for name, svc in sorted(node.indices.items()):
            if index is not None and name not in {
                    x.name for x in node._resolve(index)}:
                continue
            for sid, eng in svc.shards.items():
                for i, seg in enumerate(eng.segments):
                    live = eng.live.get(seg.seg_id)
                    n_live = (int(live.sum()) if live is not None
                              else seg.num_docs)
                    out.append({
                        "index": name, "shard": sid, "prirep": "p",
                        "ip": "127.0.0.1",
                        "id": _cat_node_id(node.name),
                        "segment": f"_{i}", "generation": i,
                        "docs.count": n_live,
                        "docs.deleted": seg.num_docs - n_live,
                        "size": seg.nbytes(),
                        "size.memory": seg.nbytes(),
                        "committed": False, "searchable": True,
                        "version": "5.1.0", "compound": False})
        return out

    # -- index admin (register LAST: bare /{index} patterns) --------------
    @d.route("PUT", "/{index}")
    def create_index(node, params, body, index):
        body = body or {}
        return node.create_index(index, body.get("settings"),
                                 body.get("mappings"),
                                 aliases=body.get("aliases"),
                                 warmers=body.get("warmers"))

    @d.route("DELETE", "/{index}")
    def delete_index(node, params, body, index):
        return node.delete_index(index)

    @d.route("GET", "/{index}")
    @d.route("GET", "/{index}/{feature}")
    def get_index(node, params, body, index, feature=None):
        # ref: RestGetIndicesAction — optional feature list
        # (_settings,_mappings,_warmers,_aliases) trims the response
        if feature is not None and not feature.startswith("_"):
            if params.get("__method") == "HEAD":
                # HEAD /{index}/{type} = exists_type (ref:
                # RestTypesExistsAction)
                import fnmatch
                tpats = [p.strip() for p in feature.split(",")]
                for svc in node._resolve(index, metadata_op=True):
                    if any(fnmatch.fnmatch(t, p)
                           for t in svc.mapping_types for p in tpats):
                        return {}
                return RestStatus(404, {})
            raise IllegalArgumentError(
                f"no handler found for uri [/{index}/{feature}]")
        feats = {f.strip().removesuffix("s") for f in
                 (feature or "_settings,_mappings,_warmers,_aliases"
                  ).split(",")}
        svcs = node._resolve(
            index,
            expand_wildcards=params.get("expand_wildcards", "open"),
            ignore_unavailable=_truthy(params, "ignore_unavailable"),
            metadata_op=True)
        out = {}
        for svc in svcs:
            name = svc.name
            entry: dict = {}
            if "_mapping" in feats:
                entry.update(node.get_mapping(name)[name])
            if "_setting" in feats:
                entry.update(node.get_settings(name)[name])
            if "_aliase" in feats or "_alias" in feats \
                    or "_alia" in feats:
                entry.update(node.get_aliases(
                    name, include_empty=True)[name])
            if "_warmer" in feats:
                entry["warmers"] = {
                    wn: {"types": [], "source": wsrc}
                    for wn, wsrc in
                    getattr(svc, "warmers", {}).items()}
            out[name] = entry
        if not out and index is not None \
                and not _truthy(params, "ignore_unavailable") \
                and ("*" not in index
                     or params.get("allow_no_indices") == "false"):
            raise IndexNotFoundError(index)
        return out

    # query-driven writes / ttl / warmers / cache / recovery
    @d.route("POST", "/_delete_by_query")
    @d.route("POST", "/{index}/_delete_by_query")
    @d.route("DELETE", "/{index}/_query")     # legacy 2.0 shape
    def delete_by_query(node, params, body, index=None):
        return node.delete_by_query(index, _body_query(params, body))

    @d.route("POST", "/_update_by_query")
    @d.route("POST", "/{index}/_update_by_query")
    def update_by_query(node, params, body, index=None):
        return node.update_by_query(index, body)

    @d.route("PUT", "/_warmer/{name}")
    @d.route("POST", "/_warmer/{name}")
    @d.route("PUT", "/_warmers/{name}")
    @d.route("POST", "/_warmers/{name}")
    def put_warmer_all(node, params, body, name):
        return node.put_warmer(None, name, body)

    @d.route("PUT", "/{index}/_warmer/{name}")
    @d.route("POST", "/{index}/_warmer/{name}")
    @d.route("PUT", "/{index}/_warmers/{name}")
    @d.route("POST", "/{index}/_warmers/{name}")
    def put_warmer(node, params, body, index, name):
        return node.put_warmer(index, name, body)

    @d.route("GET", "/_warmer")
    @d.route("GET", "/_warmer/{name}")
    @d.route("GET", "/_warmers")
    @d.route("GET", "/_warmers/{name}")
    def get_warmer_all(node, params, body, name=None):
        return node.get_warmers(None, name)

    @d.route("GET", "/{index}/_warmer")
    @d.route("GET", "/{index}/_warmer/{name}")
    @d.route("GET", "/{index}/_warmers")
    @d.route("GET", "/{index}/_warmers/{name}")
    def get_warmer(node, params, body, index, name=None):
        return node.get_warmers(index, name)

    @d.route("DELETE", "/{index}/_warmer/{name}")
    @d.route("DELETE", "/{index}/_warmers/{name}")
    @d.route("DELETE", "/{index}/_warmer")
    @d.route("DELETE", "/{index}/_warmers")
    def delete_warmer(node, params, body, index, name=None):
        return node.delete_warmer(index, params.get("name", name))

    @d.route("POST", "/_cache/clear")
    @d.route("POST", "/{index}/_cache/clear")
    def clear_cache(node, params, body, index=None):
        return node.clear_cache(index)

    @d.route("GET", "/_recovery")
    @d.route("GET", "/{index}/_recovery")
    def recovery(node, params, body, index=None):
        return node.recovery_status(index)

    # percolator (ref: rest/action/percolate/RestPercolateAction; queries
    # live under the .percolator type as in ES 2.0)
    @d.route("GET", "/{index}/_percolate")
    @d.route("POST", "/{index}/_percolate")
    def percolate(node, params, body, index):
        return node.percolate(index, _body_query(params, body))

    @d.route("GET", "/{index}/{type}/_percolate")
    @d.route("POST", "/{index}/{type}/_percolate")
    def percolate_typed(node, params, body, index, type):
        return node.percolate(index, _body_query(params, body))

    @d.route("GET", "/{index}/_percolate/count")
    @d.route("POST", "/{index}/_percolate/count")
    @d.route("GET", "/{index}/{type}/_percolate/count")
    @d.route("POST", "/{index}/{type}/_percolate/count")
    def percolate_count(node, params, body, index, type=None):
        return node.percolate(index, _body_query(params, body),
                              count_only=True)

    @d.route("GET", "/{index}/{type}/{id}/_percolate")
    @d.route("POST", "/{index}/{type}/{id}/_percolate")
    def percolate_existing(node, params, body, index, type, id):
        # percolate an EXISTING doc: fetch it, then run the registered
        # queries against its source (ref: RestPercolateAction existing-
        # doc variant; percolate_index may redirect the query set)
        doc = node.get_doc(index, id, routing=params.get("routing"))
        want_version = params.get("version")
        if want_version is not None \
                and int(want_version) != doc.get("_version"):
            # ref: TransportPercolateAction existing-doc version check
            from ..utils.errors import VersionConflictError
            raise VersionConflictError(index, id,
                                       doc.get("_version", -1),
                                       int(want_version))
        src = doc["_source"]
        if isinstance(src, (bytes, str)):
            src = json.loads(src)
        target = params.get("percolate_index", index)
        req = dict(body or {})
        req["doc"] = src
        return node.percolate(target, req)

    @d.route("GET", "/{index}/{type}/{id}/_percolate/count")
    @d.route("POST", "/{index}/{type}/{id}/_percolate/count")
    def percolate_existing_count(node, params, body, index, type, id):
        doc = node.get_doc(index, id, routing=params.get("routing"))
        src = doc["_source"]
        if isinstance(src, (bytes, str)):
            src = json.loads(src)
        target = params.get("percolate_index", index)
        req = dict(body or {})
        req["doc"] = src
        return node.percolate(target, req, count_only=True)

    @d.route("POST", "/_mpercolate")
    def mpercolate(node, params, body):
        return node.mpercolate(body if isinstance(body, list) else [])

    # legacy typed operation routes (ES 2.0 per-type paths; single-type
    # internally, the type segment is accepted and echoed)
    @d.route("GET", "/{index}/{type}/_search")
    @d.route("POST", "/{index}/{type}/_search")
    def search_typed(node, params, body, index, type):
        idx = None if index in ("_all", "*") else index
        return node.search(idx, _search_body(params, body),
                           scroll=params.get("scroll"),
                           search_type=params.get("search_type"),
                           request=params.get("__request"))

    @d.route("GET", "/{index}/{type}/_count")
    @d.route("POST", "/{index}/{type}/_count")
    def count_typed(node, params, body, index, type):
        idx = None if index in ("_all", "*") else index
        return node.count(idx, _body_query(params, body))

    @d.route("POST", "/{index}/{type}/{id}/_update")
    def update_typed(node, params, body, index, type, id):
        r = update_doc(node, params, body, index, id, doc_type=type)
        r.setdefault("_type", type)
        return r

    @d.route("GET", "/{index}/{type}/{id}/_source")
    def get_source_typed(node, params, body, index, type, id):
        realtime = params.get("realtime") not in ("false", "0")
        if _truthy(params, "refresh"):
            node.refresh(index)
        r = node.get_doc(index, id, doc_type=type,
                         routing=params.get("routing"),
                         realtime=realtime,
                         parent=params.get("parent"))
        src = r["_source"]
        obj = json.loads(src) if isinstance(src, (bytes, str)) else src
        from ..search.shard_searcher import filter_source
        inc = params.get("_source_include") or params.get("_source_includes")
        exc = params.get("_source_exclude") or params.get("_source_excludes")
        if inc or exc:
            obj = filter_source(obj, {
                "includes": inc.split(",") if inc else [],
                "excludes": exc.split(",") if exc else []})
        return obj

    @d.route("GET", "/{index}/{type}/{id}/_explain")
    @d.route("POST", "/{index}/{type}/{id}/_explain")
    def explain_typed(node, params, body, index, type, id):
        return node.explain_doc(index, id, _body_query(params, body))

    @d.route("GET", "/{index}/{type}/{id}/_mlt")
    @d.route("POST", "/{index}/{type}/{id}/_mlt")
    def mlt_typed(node, params, body, index, type, id):
        # ref: rest/action/mlt/RestMoreLikeThisAction — search with a
        # more_like_this query seeded by the doc
        mlt: dict = {"like": [{"_id": id}],
                     "min_term_freq": int(params.get("min_term_freq", 1)),
                     "min_doc_freq": int(params.get("min_doc_freq", 1))}
        if params.get("mlt_fields"):
            mlt["fields"] = params["mlt_fields"].split(",")
        sbody = dict(body or {})
        sbody["query"] = {"more_like_this": mlt}
        return node.search(index, sbody)

    @d.route("GET", "/_suggest")
    @d.route("POST", "/_suggest")
    @d.route("GET", "/{index}/_suggest")
    @d.route("POST", "/{index}/_suggest")
    def suggest_endpoint(node, params, body, index=None):
        # ref: rest/action/suggest/RestSuggestAction — bare suggest
        # request = search with only a suggest section
        r = node.search(index, {"suggest": body or {}, "size": 0})
        out = {"_shards": r["_shards"]}
        out.update(r.get("suggest", {}))
        return out

    @d.route("GET", "/_search/scroll/{scroll_id}")
    @d.route("POST", "/_search/scroll/{scroll_id}")
    def scroll_path(node, params, body, scroll_id):
        return node.scroll(scroll_id, params.get("scroll")
                           or (body or {}).get("scroll"))

    @d.route("DELETE", "/_search/scroll/{scroll_id}")
    def clear_scroll_path(node, params, body, scroll_id):
        r = node.clear_scroll(scroll_id.split(","))
        if r.pop("_missing", False):
            return RestStatus(404, r)
        return r

    @d.route("GET", "/{index}/_stats")
    @d.route("GET", "/{index}/_stats/{metric}")
    def index_stats(node, params, body, index, metric=None):
        return node.indices_stats(index, metric, **_stats_params(params))

    @d.route("PUT", "/{index}/_settings")
    @d.route("PUT", "/_settings")
    def put_settings(node, params, body, index=None):
        return node.update_index_settings(
            index, body or {},
            ignore_unavailable=_truthy(params, "ignore_unavailable"))

    @d.route("GET", "/_mapping/{type}")
    @d.route("GET", "/{index}/_mapping/{type}")
    @d.route("GET", "/_mappings/{type}")
    @d.route("GET", "/{index}/_mappings/{type}")
    def get_mapping_typed(node, params, body, index=None, type=None):
        return node.get_mapping(index, type,
                                params.get("expand_wildcards", "open"))

    @d.route("PUT", "/{index}/{type}/_mapping")
    @d.route("POST", "/{index}/{type}/_mapping")
    @d.route("PUT", "/{index}/{type}/_mappings")
    @d.route("POST", "/{index}/{type}/_mappings")
    @d.route("PUT", "/{index}/_mapping/{type}")
    @d.route("POST", "/{index}/_mapping/{type}")
    @d.route("PUT", "/{index}/_mappings/{type}")
    @d.route("POST", "/{index}/_mappings/{type}")
    @d.route("PUT", "/_mapping/{type}")
    @d.route("POST", "/_mapping/{type}")
    @d.route("PUT", "/_mappings/{type}")
    @d.route("POST", "/_mappings/{type}")
    def put_mapping_typed2(node, params, body, index=None, type=None):
        return node.put_mapping(index, body or {}, doc_type=type)

    @d.route("GET", "/_mapping/field/{fields}")
    @d.route("GET", "/{index}/_mapping/field/{fields}")
    @d.route("GET", "/_mapping/{type}/field/{fields}")
    @d.route("GET", "/{index}/_mapping/{type}/field/{fields}")
    def get_field_mapping(node, params, body, fields, index=None,
                          type=None):
        return node.get_field_mapping(
            index, fields, doc_type=type,
            include_defaults=_truthy(params, "include_defaults"))

    # legacy typed doc routes /{index}/{type}/{id}
    @d.route("PUT", "/{index}/{type}/{id}")
    @d.route("POST", "/{index}/{type}/{id}")
    def index_doc_typed(node, params, body, index, type, id):
        if type == ".percolator":
            return node.register_percolator(index, id, body)
        if type.startswith("_"):
            raise IllegalArgumentError(f"no handler for type [{type}]")
        return index_doc(node, params, body, index, id, doc_type=type)

    @d.route("POST", "/{index}/{type}")
    def index_auto_id_typed(node, params, body, index, type):
        if type.startswith("_"):
            raise IllegalArgumentError(f"no handler for type [{type}]")
        return node.index_doc(index, None, body or {},
                              refresh=params.get("refresh") == "true",
                              routing=params.get("routing"),
                              doc_type=type)

    @d.route("PUT", "/{index}/{type}/{id}/_create")
    @d.route("POST", "/{index}/{type}/{id}/_create")
    def create_doc_typed(node, params, body, index, type, id):
        params = {**params, "op_type": "create"}
        return index_doc(node, params, body, index, id, doc_type=type)

    @d.route("GET", "/{index}/{type}/{id}")
    def get_doc_typed(node, params, body, index, type, id):
        if type == ".percolator":
            return node.get_percolator(index, id)
        if type.startswith("_") and type != "_all":
            raise IllegalArgumentError(f"no handler for type [{type}]")
        return get_doc(node, params, body, index, id,
                       doc_type=type)

    @d.route("DELETE", "/{index}/{type}/{id}")
    def delete_doc_typed(node, params, body, index, type, id):
        if type == ".percolator":
            return node.unregister_percolator(index, id)
        if type.startswith("_") and type != "_all":
            raise IllegalArgumentError(f"no handler for type [{type}]")
        return delete_doc(node, params, body, index, id,
                          doc_type=type)


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------


class RestServer:
    """HTTP front end for a Node (ref: HttpServer + RestController)."""

    def __init__(self, node: Node, host: str = "127.0.0.1", port: int = 9200):
        self.node = node
        self.dispatcher = RestDispatcher(node)
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet by default
                pass

            # the search this connection's thread is handling, if it is
            # one (utils/profiler.Request): `_respond` is then its
            # `respond` phase
            _search = None

            def _respond(self, status: int, payload, pretty: bool = False,
                         head_only: bool = False, fmt: str | None = None,
                         headers: dict | None = None):
                if self._search is not None:
                    # the REST thread's last leaf, to the end of the
                    # block that `_handle` opened as `rest_parse`
                    self._search.parse.switch("respond")
                if isinstance(payload, (dict, list)):
                    if fmt and fmt != "json":
                        from ..utils.xcontent import render_body
                        data, ctype = render_body(payload, fmt, pretty)
                    else:
                        data = json.dumps(
                            payload,
                            indent=2 if pretty else None).encode()
                        ctype = "application/json"
                else:
                    data = str(payload).encode()
                    ctype = "text/plain"
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                for hk, hv in (headers or {}).items():
                    self.send_header(hk, hv)
                self.end_headers()
                if not head_only:
                    self.wfile.write(data)

            def _handle(self, method: str):
                """A `_search` is timed from this first line to its last
                write. One block tiles this thread's part: `rest_parse`,
                out of which node.search takes the time the request is
                with other threads, then `respond`; the whole is the
                `request` timer, and `request:search` the enclosing span
                for a person at a trace viewer."""
                if not self.path.partition("?")[0].rstrip("/").endswith(
                        "/_search"):
                    self._search = None
                    return self._serve(method, None)
                t0 = time.perf_counter()
                req = self._search = profiler.Request()
                with profiler.enclosing("request:search", request=req.id):
                    with profiler.phase("rest_parse",
                                        request=req.id) as req.parse:
                        self._serve(method, req)
                profiler.waited("request", time.perf_counter() - t0)

            def _serve(self, method: str, search):
                parsed = urlparse(self.path)
                req_path = parsed.path
                params = {k: v[0] for k, v in parse_qs(parsed.query).items()
                          if v}
                if search is not None:
                    params["__request"] = search
                # bare flags like ?pretty
                for flag in parsed.query.split("&"):
                    if flag and "=" not in flag:
                        params[flag] = "true"
                # tenant id for the traffic control plane (search/
                # traffic.py): header or ?tenant_id= param, the param
                # winning (ref: the reference resolves auth principals
                # at the REST filter layer, before any action runs)
                tenant_hdr = self.headers.get("X-Tenant-Id")
                if tenant_hdr and "tenant_id" not in params:
                    params["tenant_id"] = tenant_hdr
                length = int(self.headers.get("Content-Length") or 0)
                raw = self.rfile.read(length) if length else b""
                try:
                    from ..utils.xcontent import parse_body
                    body = None
                    if raw.strip():
                        # ndjson is decided by ENDPOINT, not by newline
                        # count — a one-action _bulk body is still ndjson
                        if req_path.rstrip("/").endswith(
                                ("_bulk", "_msearch", "_mpercolate")):
                            body = _ndjson(raw.decode("utf-8"))
                        else:
                            # content negotiation: JSON/YAML/CBOR bodies
                            # (ref: common/xcontent/XContentFactory)
                            body = parse_body(
                                raw, self.headers.get("Content-Type"))
                    result = outer.dispatcher.dispatch(
                        method, req_path, params, body)
                    accept_json = "application/json" in (
                        self.headers.get("Accept") or "")
                    if req_path.startswith("/_cat") \
                            and params.get("format") not in (
                                "json", "yaml", "cbor") \
                            and not accept_json:
                        # _cat endpoints speak aligned plain text (ref:
                        # rest/action/cat/AbstractCatAction + RestTable)
                        seg = req_path.strip("/").split("/")
                        endpoint = seg[1] if len(seg) > 1 else ""
                        result = _cat_text(result, params, endpoint)
                    status = 200
                    if isinstance(result, RestStatus):
                        status, result = result.status, result.payload
                    elif method in ("POST", "PUT") \
                            and isinstance(result, dict) \
                            and result.get("created"):
                        status = 201
                    self._respond(status, result,
                                  pretty=params.get("pretty") == "true",
                                  head_only=(method == "HEAD"),
                                  fmt=params.get("format"))
                except ElasticsearchTpuError as e:
                    # errors honor the negotiated format too — a CBOR/
                    # YAML client must be able to parse the failure.
                    # Admission-control sheds (429) carry the throttle
                    # horizon as a Retry-After header so well-behaved
                    # clients back off instead of hot-looping.
                    hdrs = None
                    ra = getattr(e, "retry_after_s", None)
                    if ra is not None:
                        from ..search.traffic import retry_after_header
                        hdrs = {"Retry-After": retry_after_header(ra)}
                    try:
                        self._respond(e.status,
                                      {"error": e.to_dict(),
                                       "status": e.status},
                                      head_only=(method == "HEAD"),
                                      fmt=params.get("format"),
                                      headers=hdrs)
                    except Exception:
                        self._respond(e.status,
                                      {"error": e.to_dict(),
                                       "status": e.status},
                                      head_only=(method == "HEAD"),
                                      headers=hdrs)
                except json.JSONDecodeError as e:
                    self._respond(400, {"error": {
                        "type": "parse_exception",
                        "reason": f"request body is not valid JSON: {e}"},
                        "status": 400})
                except Exception as e:  # noqa: BLE001 - the 500 boundary
                    self._respond(500, {"error": {
                        "type": type(e).__name__, "reason": str(e)},
                        "status": 500})

            def do_GET(self):
                self._handle("GET")

            def do_POST(self):
                self._handle("POST")

            def do_PUT(self):
                self._handle("PUT")

            def do_DELETE(self):
                self._handle("DELETE")

            def do_HEAD(self):
                self._handle("HEAD")

        self._server = ThreadingHTTPServer((host, port), Handler)
        self.host = host
        self.port = self._server.server_address[1]
        self._thread: threading.Thread | None = None

    def start(self) -> "RestServer":
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()


def main():  # pragma: no cover - CLI entry (ref: bootstrap/Elasticsearch)
    import argparse

    ap = argparse.ArgumentParser(description="elasticsearch_tpu node")
    ap.add_argument("--port", type=int, default=9200)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--data", default=None, help="data path (durable mode)")
    ap.add_argument("--shards", type=int, default=None)
    ap.add_argument("--config", default=None,
                    help="elasticsearch.yml / .json config file "
                         "(layered under ES_TPU_* env and CLI flags, "
                         "ref: InternalSettingsPreparer)")
    args = ap.parse_args()
    from ..utils.settings import Settings
    overrides: dict = {}
    if args.data:
        overrides["path.data"] = args.data
    if args.shards is not None:
        overrides["index.number_of_shards"] = args.shards
    node = Node(Settings.prepare(overrides, config_path=args.config))
    server = RestServer(node, args.host, args.port).start()
    print(f"node [{node.name}] listening on http://{server.host}:{server.port}")
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        server.stop()
        node.close()


if __name__ == "__main__":  # pragma: no cover
    main()
