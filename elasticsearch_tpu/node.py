"""Node: the composition root tying indices, search fan-out, and APIs.

Reference analog: node/Node.java (builds the module graph :166-200,
starts services :230-273) — but composition is plain Python. One Node
owns an IndicesService-equivalent registry and exposes the operations the
action layer (action/) implements in the reference: index/bulk/get/
delete/search/count/admin. The distributed fan-out across shards of one
process mirrors TransportSearchAction's QUERY_THEN_FETCH flow with the
SearchPhaseController merge (host path); multi-chip execution of the
same search is parallel/distributed.py.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

from .utils.settings import Settings, parse_time_value as _parse_time_value
from .utils.errors import (IndexNotFoundError, IndexAlreadyExistsError,
                           ElasticsearchTpuError, IllegalArgumentError,
                           PowerLossError, SearchTimeoutError,
                           ShardFailedError)
from .utils import profiler
from .utils.metrics import MetricsRegistry
from .index.engine import IndexOp
from .index.index_service import IndexService
from .search.controller import (merge_shard_results, shards_header,
                                shard_failure)
from .search.aggregations import parse_aggs
from .search.suggest import parse_suggest, merge_suggests
from .search.shard_searcher import ShardReader


def parse_time_value(v, default_ms: int = 60_000) -> int:
    """'5m' / '30s' -> millis; wraps the shared helper with the API error
    type (ref: common/unit/TimeValue)."""
    try:
        return _parse_time_value(v, default_ms)
    except ValueError as e:
        raise IllegalArgumentError(str(e))


class Node:
    def __init__(self, settings: Settings | dict | None = None):
        self.settings = (settings if isinstance(settings, Settings)
                         else Settings(settings or {}))
        # before the first jit of this process: a stable persistent
        # compile cache (utils/compile_cache.py)
        from .utils.compile_cache import configure_compile_cache
        configure_compile_cache()
        # seed the process-wide HBM breakers with this node's limits
        # (first constructor wins; see utils/breaker.breaker_service)
        from .utils.breaker import breaker_service
        breaker_service(self.settings)
        self.name = self.settings.get_str("node.name", "node-0")
        self.cluster_name = self.settings.get_str("cluster.name",
                                                  "elasticsearch-tpu")
        self.data_path = self.settings.get_str("path.data")
        self._node_lock_fh = None
        if self.data_path:
            os.makedirs(self.data_path, exist_ok=True)
            # exclusive node lock: two nodes must never share a data
            # dir (ref: env/NodeEnvironment.java acquiring node.lock
            # per node path)
            import fcntl
            lock_path = os.path.join(self.data_path, "node.lock")
            fh = open(lock_path, "a+")
            try:
                fcntl.flock(fh, fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                fh.close()
                raise IllegalArgumentError(
                    f"failed to obtain node lock on [{self.data_path}]: "
                    f"is another node using the same data path?")
            self._node_lock_fh = fh
            # fused-scoring autotuner choices persist under the data
            # path, keyed by pack fingerprint (so a refreshed pack
            # re-tunes instead of serving a stale choice). The store is
            # process-global: first node wins, and only the owner tears
            # it down on close
            from .search.executor import configure_autotune_persistence
            store = os.path.join(self.data_path, "fused_autotune.json")
            # atomic claim: only the node that actually configured the
            # process-global store owns (and later tears down) it
            self._autotune_store = store if configure_autotune_persistence(
                store, only_if_unset=True) else None
        self.indices: dict[str, IndexService] = {}
        self.metrics = MetricsRegistry()
        self._started_at = time.time()
        # scroll contexts: id -> {"readers", "body", "pos", "expires_at"}
        # (ref: SearchService.activeContexts :138 + keepalive reaper :168)
        self._scrolls: dict[str, dict] = {}
        from .snapshots import SnapshotsService
        self.snapshots = SnapshotsService(self)
        # alias -> {index names}; ref: cluster/metadata/AliasMetaData +
        # MetaDataIndexAliasesService
        self._aliases: dict[str, set[str]] = {}
        # (alias, index) -> {filter?, index_routing?, search_routing?}
        self._alias_meta: dict[tuple[str, str], dict] = {}
        # index templates; ref: cluster/metadata/MetaDataIndexTemplateService
        self._templates: dict[str, dict] = {}
        self._closed: set[str] = set()
        # named host-side pools (ref: threadpool/ThreadPool.java; the
        # device collapses the reference's search/bulk pool pressure)
        from .utils.threadpool import ThreadPoolService
        self.thread_pool = ThreadPoolService()
        # traffic control plane (search/traffic.py): per-tenant
        # token-bucket/concurrency admission BEFORE any breaker hold,
        # priority lanes for the scheduler's weighted drain, the
        # adaptive coalescing window, and the query-cache hit-rate
        # surface. Quotas come from `search.traffic.tenant.<id>.*`,
        # dynamically updatable via _cluster/settings.
        from .search.traffic import controller_from_settings
        self.traffic = controller_from_settings(self.settings)
        # search dispatch scheduler: cross-request coalescing + pipelined
        # fan-out (search/dispatch.py). ES_TPU_COALESCE_WINDOW_MS
        # overrides the setting at drain time; with neither set the
        # traffic controller's adaptive window drives coalescing.
        from .search.dispatch import DispatchScheduler
        from .search import dispatch as _dispatch_mod
        self._dispatch = DispatchScheduler(
            window_ms=float(self.settings.get_str(
                "search.dispatch.coalesce_window_ms", "0") or 0),
            traffic=self.traffic)
        # process-wide failover/eviction/membership counters: install
        # FRESH objects so this node never double-counts into (or
        # inherits) another in-process node's numbers; close() resets
        # them only while they are still this node's — the
        # fault-registry ownership convention
        self._process_stats = _dispatch_mod.install_process_stats()
        # durability counters (index/durability.py), same ownership
        # convention — installed BEFORE _load_existing_indices so
        # recovery-time salvage/containment events land in THIS node's
        # block
        from .index import durability as _durability_mod
        self._durability_stats = _durability_mod.install_process_stats()
        # elastic degraded mesh (parallel/repack.py): eviction
        # threshold + re-expansion probe cadence. Module-global
        # defaults like the resident cache; imported only when set so
        # mesh-less nodes never pay the import.
        ev_threshold = self.settings.get_int(
            "mesh.eviction.failure_threshold")
        ev_probe = self.settings.get_str("mesh.eviction.probe_interval")
        self._eviction_cfg = None
        if ev_threshold is not None or ev_probe is not None:
            from .parallel import repack as _repack
            _repack.configure(
                failure_threshold=ev_threshold,
                probe_interval_ms=(
                    float(parse_time_value(ev_probe, 5000))
                    if ev_probe is not None else None))
            self._eviction_cfg = _repack.config_snapshot()
        # resident query loop (search/resident.py, ES_TPU_RESIDENT_LOOP
        # opt-in): cap on pinned AOT executables. Process-global like
        # the executor itself; the last configured node wins.
        from .search import resident as _resident
        max_entries = self.settings.get_int("search.resident.max_entries")
        if max_entries is not None:
            _resident.configure(max_entries=max_entries)
        # tiered tile residency (index/tiering.py, ES_TPU_TIERED_PACK /
        # index.tiering.enabled opt-in): HBM as a cache over host-RAM
        # forward-index tiles. Process-global config like the resident
        # cache; close() resets only while this node configured it.
        self._tiering_cfg = None
        t_enabled = self.settings.get_bool("index.tiering.enabled", None)
        t_budget = self.settings.get_bytes("index.tiering.budget_bytes",
                                           None)
        t_chunk = self.settings.get_int("index.tiering.chunk_tiles")
        if t_enabled is not None or t_budget is not None \
                or t_chunk is not None:
            from .index import tiering as _tiering
            self._tiering_cfg = _tiering.configure(
                enabled=t_enabled, budget_bytes=t_budget,
                chunk_tiles=t_chunk)
        # IVF vector search (index/ann.py): exact-scan -> coarse-
        # quantized crossover + declared recall / nprobe. Process-
        # global config like tiering; close() resets only while this
        # node configured it.
        self._ann_cfg = None
        a_min = self.settings.get_int("index.ann.min_docs")
        a_nprobe = self.settings.get_int("index.ann.nprobe")
        a_recall = self.settings.get_float("index.ann.recall")
        if a_min is not None or a_nprobe is not None \
                or a_recall is not None:
            from .index import ann as _ann
            self._ann_cfg = _ann.configure(
                min_docs=a_min, nprobe=a_nprobe, recall=a_recall)
        # runtime hot-path hygiene guard (utils/trace_guard.py,
        # ES_TPU_TRACE_GUARD opt-in): disallow implicit device<->host
        # transfers + count compiles; bench runs then report
        # transfer_guard_trips/recompiles in nodes_stats()["dispatch"].
        # Process-wide and idempotent, like the breaker service.
        from .utils import trace_guard as _trace_guard
        if _trace_guard.env_requested():
            _trace_guard.arm()
        # runtime race sanitizer (utils/race_guard.py,
        # ES_TPU_RACE_GUARD opt-in): declared-shared structures assert
        # their lock is held on every mutation; trips surface as
        # nodes_stats()["dispatch"]["race_guard_trips"] while armed
        from .utils import race_guard as _race_guard
        if _race_guard.env_requested():
            _race_guard.arm()
        # deterministic fault injection (utils/faults.py): the setting
        # installs the process-wide registry; close() clears it again
        # ONLY while the installed registry is still this node's (test
        # nodes must not leak faults, but must not clobber a registry
        # someone configured after them either)
        self._fault_registry = None
        fault_spec = self.settings.get_str("search.fault_injection")
        if fault_spec is not None:
            from .utils import faults
            self._fault_registry = faults.configure(fault_spec)
        # plugins (ref: PluginsService loaded before any index exists so
        # analysis/query contributions are visible to every mapping)
        from .plugins import PluginsService
        self.plugins = PluginsService(self.settings)
        self.plugins.apply_analysis_hooks()
        self.plugins.apply_query_hooks()
        # resource watcher + file scripts (ref: ResourceWatcherService
        # watching config/scripts for ScriptService file reload)
        from .utils.watcher import ResourceWatcherService
        self.resource_watcher = ResourceWatcherService(self.settings)
        self._watch_file_scripts()
        # hunspell dictionaries under <path.conf|path.data>/hunspell/
        # <locale>/*.aff|*.dic (ref: indices/analysis/HunspellService)
        from .index.hunspell import HunspellService
        for base in (self.settings.get_str("path.conf"), self.data_path):
            if base:
                HunspellService.instance().add_root(
                    os.path.join(base, "hunspell"))
        if self.data_path:
            self._load_existing_indices()
            self._load_stored_scripts()
            if self._autotune_store is not None:
                # sweep persisted autotuner entries whose pack no
                # longer exists on disk (a long-lived node's refresh/
                # merge/compaction history otherwise accumulates dead
                # fingerprints in fused_autotune.json forever); runs
                # AFTER recovery so the live key set is complete
                from .search.executor import sweep_autotune_store
                # engine segments are the complete live set FOR THIS
                # NODE: the store is only ever written by the timed
                # single-chip tuner (resolve_fused_backend persists
                # solely on the run_backend path; the mesh passes
                # run_backend=None and can only LOOK UP entries, under
                # per-shard keys that equal these when content matches)
                # — so no mesh-only key can exist to be swept. Caveat:
                # the store is process-global (first node wins), so a
                # SECOND in-process node's choices persist into this
                # file under packs this sweep can't see; they are swept
                # at the owner's next startup and that node re-tunes
                # once per pack — accepted, matching the breaker
                # first-wins convention (one node per process in prod)
                live = set()
                for svc in self.indices.values():
                    for eng in svc.shards.values():
                        for seg in eng.segments:
                            live.add(seg.fingerprint())
                            live.add(seg.cache_key())
                sweep_autotune_store(live)
        # TTL sweep (ref: IndicesTTLService, indices.ttl.interval 60s)
        import threading as _threading
        self._ttl_stop = _threading.Event()
        ttl_interval = parse_time_value(
            self.settings.get_str("indices.ttl.interval", "60s"), 60_000)

        def _ttl_loop():
            while not self._ttl_stop.wait(ttl_interval / 1000.0):
                try:
                    self.purge_expired()
                except Exception:
                    pass  # the sweep must never kill the node

        self._ttl_thread = _threading.Thread(
            target=_ttl_loop, name="ttl-purger", daemon=True)
        self._ttl_thread.start()
        self.plugins.apply_node_hooks(self)

    def _watch_file_scripts(self) -> None:
        """File scripts: `<path.scripts>` (default <path.data>/scripts)
        loaded by name-minus-extension and hot-reloaded through the
        resource watcher (ref: ScriptService.java ScriptChangesListener
        on config/scripts)."""
        path = self.settings.get_str("path.scripts") or (
            os.path.join(self.data_path, "scripts")
            if self.data_path else None)
        if not path:
            return
        # register even when the dir does not exist yet: FileWatcher
        # tolerates a missing path, so a later-created dir starts
        # loading at the next poll instead of requiring a restart
        from .script import ScriptService
        from .utils.watcher import FileChangesListener, FileWatcher, HIGH

        svc = ScriptService.instance()

        # only extensions a script engine owns load (ref: ScriptService
        # registers per-engine extensions; editor backups etc. are
        # ignored rather than shadowing the real script)
        _EXTS = (".expression", ".painless", ".mustache", ".txt")

        class _Listener(FileChangesListener):
            def on_file_created(self, p):
                self._load(p)

            def on_file_changed(self, p):
                self._load(p)

            @staticmethod
            def on_file_deleted(p):
                # scripts key on the file STEM; another script extension
                # with the same stem may still provide the script —
                # reload from a survivor instead of dropping blindly
                if not p.endswith(_EXTS):
                    return
                name = os.path.splitext(os.path.basename(p))[0]
                d = os.path.dirname(p)
                try:
                    survivor = next(
                        (os.path.join(d, f) for f in sorted(os.listdir(d))
                         if os.path.splitext(f)[0] == name
                         and f.endswith(_EXTS)
                         and os.path.isfile(os.path.join(d, f))), None)
                except OSError:
                    survivor = None
                if survivor is not None:
                    _Listener._load(survivor)
                else:
                    svc.file_scripts.pop(name, None)

            @staticmethod
            def _load(p):
                if not p.endswith(_EXTS):
                    return
                name = os.path.splitext(os.path.basename(p))[0]
                try:
                    with open(p) as f:
                        svc.file_scripts[name] = f.read().strip()
                except OSError:
                    pass

        w = FileWatcher(path)
        w.add_listener(_Listener())
        self.resource_watcher.add(w, HIGH)
        self._script_watcher = w

    # -- stored scripts (ref: ScriptService indexed scripts in .scripts;
    # persisted here like gateway metadata) ----------------------------
    def _scripts_file(self) -> str:
        return os.path.join(self.data_path, "scripts.json")

    def _load_stored_scripts(self) -> None:
        from .script import ScriptService
        path = self._scripts_file()
        if os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
            svc = ScriptService.instance()
            if "sources" in data and isinstance(data["sources"], dict):
                svc.stored.update(data["sources"])
                svc.meta.update(data.get("meta", {}))
            else:  # pre-versioning flat format
                for sid, src in data.items():
                    svc.stored[sid] = src

    def put_stored_script(self, script_id: str, source: str) -> None:
        from .script import ScriptService
        ScriptService.instance().put_stored(script_id, source)
        self._persist_stored_scripts()

    def delete_stored_script(self, script_id: str) -> bool:
        from .script import ScriptService
        found = ScriptService.instance().delete_stored(script_id)
        self._persist_stored_scripts()
        return found

    def put_stored_script_versioned(self, script_id: str, source: str,
                                    lang: str, version: int | None = None,
                                    version_type: str = "internal"
                                    ) -> tuple[int, bool]:
        from .script import ScriptService
        v, created = ScriptService.instance().put_versioned(
            script_id, source, lang, version=version,
            version_type=version_type)
        self._persist_stored_scripts()
        return v, created

    def delete_stored_script_versioned(self, script_id: str,
                                       version: int | None = None,
                                       version_type: str = "internal"
                                       ) -> int | None:
        from .script import ScriptService
        v = ScriptService.instance().delete_versioned(
            script_id, version=version, version_type=version_type)
        self._persist_stored_scripts()
        return v

    def _persist_stored_scripts(self) -> None:
        if not self.data_path:
            return
        from .script import ScriptService
        svc = ScriptService.instance()
        tmp = self._scripts_file() + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"sources": svc.stored, "meta": svc.meta}, f)
        os.replace(tmp, self._scripts_file())

    # -- index admin (ref: MetaDataCreateIndexService etc.) ----------------
    def create_index(self, name: str, settings: dict | None = None,
                     mappings: dict | None = None,
                     aliases: dict | None = None,
                     warmers: dict | None = None) -> dict:
        if name in self.indices:
            raise IndexAlreadyExistsError(name)
        if not name or name != name.lower() or name.startswith(("_", "-", "+")):
            raise IllegalArgumentError(f"invalid index name [{name}]")
        # apply matching index templates, lowest order first so higher
        # orders override (ref: MetaDataCreateIndexService template merge)
        import fnmatch
        matching = sorted(
            (t for t in self._templates.values()
             if any(fnmatch.fnmatch(name, p) for p in t["patterns"])),
            key=lambda t: t.get("order", 0))
        merged_settings: dict = {}
        merged_mappings: dict = {}
        def register_alias(alias: str, spec) -> None:
            self._aliases.setdefault(alias, set()).add(name)
            meta: dict = {}
            spec = spec if isinstance(spec, dict) else {}
            if spec.get("filter") is not None:
                meta["filter"] = spec["filter"]
            routing = spec.get("routing")
            ir = spec.get("index_routing", routing)
            sr = spec.get("search_routing", routing)
            if ir is not None:
                meta["index_routing"] = str(ir)
            if sr is not None:
                meta["search_routing"] = str(sr)
            self._alias_meta[(alias, name)] = meta

        for t in matching:
            merged_settings.update(t.get("settings") or {})
            _deep_merge(merged_mappings, t.get("mappings") or {})
            for alias, aspec in (t.get("aliases") or {}).items():
                register_alias(alias, aspec)
        merged_settings.update(settings or {})
        if merged_mappings:
            m2 = dict(mappings or {})
            _deep_merge(merged_mappings, m2)
            mappings = merged_mappings
        settings = merged_settings
        for alias, aspec in (aliases or {}).items():
            register_alias(alias, aspec)
        # bare index-level keys ("number_of_shards") normalize to the
        # canonical "index."-prefixed form (ref: IndexMetaData.Builder
        # settings handling) so IndexService sees them uniformly
        flat = Settings(settings or {}).as_dict()
        settings = {k if k.startswith("index.") else f"index.{k}": v
                    for k, v in flat.items()}
        idx_settings = self.settings.merged_with(settings)
        mapping = None
        type_mappings = None
        if mappings:
            # accept both {"properties": ...} and {"<type>": {"properties"...}}
            if "properties" in mappings or not mappings:
                mapping = mappings
            else:
                type_mappings = mappings
        svc = IndexService(name, idx_settings, mapping,
                           data_path=self.data_path,
                           type_mappings=type_mappings)
        svc.mapping_types = set(type_mappings or ())
        if warmers:
            # create-body warmers: {name: {source: <search body>, types}}
            # (ref: search/warmer/IndexWarmersMetaData.java fromXContent)
            svc.warmers = {
                wn: (w.get("source") or {"query": {"match_all": {}}})
                if isinstance(w, dict) else {"query": {"match_all": {}}}
                for wn, w in warmers.items()}
        self.indices[name] = svc
        if self.data_path:
            self._persist_index_meta(svc, settings or {})
        return {"acknowledged": True, "index": name}

    def delete_index(self, name: str) -> dict:
        svc = self._index(name)
        svc.close()
        del self.indices[name]
        self._closed.discard(name)
        if self.data_path:
            import shutil
            shutil.rmtree(os.path.join(self.data_path, name), ignore_errors=True)
        return {"acknowledged": True}

    def _index(self, name: str) -> IndexService:
        svc = self.indices.get(name)
        if svc is None and name in self._aliases:
            targets = self._aliases[name]
            if len(targets) == 1:
                return self.indices[next(iter(targets))]
            raise IllegalArgumentError(
                f"Alias [{name}] has more than one indices associated with "
                f"it, can't execute a single index op")
        if svc is None:
            raise IndexNotFoundError(name)
        return svc

    def _resolve(self, names: str | None,
                 expand_wildcards: str = "open",
                 ignore_unavailable: bool = False,
                 metadata_op: bool = False) -> list[IndexService]:
        """Index name resolution incl. _all, comma lists, wildcards, and
        aliases (ref: cluster/metadata/IndexNameExpressionResolver).
        `expand_wildcards` (open|closed|none|all, comma-combinable)
        controls which states wildcard/_all expressions expand to.
        `metadata_op` lets concretely-named CLOSED indices resolve —
        mapping/alias/settings updates are cluster-metadata operations
        that apply to closed indices in the reference."""
        states = {s.strip() for s in str(expand_wildcards).split(",")}
        if "all" in states:
            states |= {"open", "closed"}

        def state_ok(name: str) -> bool:
            closed = name in self._closed
            return ("closed" if closed else "open") in states

        if names in (None, "_all", "*", ""):
            return [s for n, s in self.indices.items() if state_ok(n)]
        out = []
        seen: set[str] = set()

        def add(svc: IndexService, concrete: bool = False):
            if concrete:
                if not metadata_op and svc.name in self._closed:
                    if ignore_unavailable:
                        return  # closed counts as unavailable
                    # a data operation naming a closed index directly is
                    # forbidden (ref: IndexClosedException, 403)
                    from .utils.errors import IndexClosedError
                    raise IndexClosedError(svc.name)
                ok = True
            else:
                ok = state_ok(svc.name)
            if svc.name not in seen and ok:
                seen.add(svc.name)
                out.append(svc)
        for n in str(names).split(","):
            n = n.strip()
            if n in self._aliases:
                for target in sorted(self._aliases[n]):
                    if target in self.indices:
                        add(self.indices[target])
            elif "*" in n:
                import fnmatch
                matched = False
                for k in sorted(self.indices):
                    if fnmatch.fnmatch(k, n):
                        add(self.indices[k])
                        matched = True
                for alias, targets in sorted(self._aliases.items()):
                    if fnmatch.fnmatch(alias, n):
                        for target in sorted(targets):
                            if target in self.indices:
                                add(self.indices[target])
                        matched = True
                _ = matched
            else:
                try:
                    add(self._index(n), concrete=True)
                except IndexNotFoundError:
                    if not ignore_unavailable:
                        raise
        return out

    def _ensure_index(self, name: str) -> IndexService:
        """Auto-create on first write (ref: TransportBulkAction auto-create).
        Aliases resolve before auto-creation (writes through a
        single-index alias land in its backing index)."""
        if name in self._aliases:
            return self._index(name)
        if name not in self.indices:
            if not self.settings.get_bool("action.auto_create_index", True):
                raise IndexNotFoundError(name)
            self.create_index(name)
        return self.indices[name]

    # -- document APIs -----------------------------------------------------
    def index_doc(self, index: str, doc_id: str | None, body,
                  version: int | None = None, routing: str | None = None,
                  refresh: bool = False, ttl: str | None = None,
                  doc_type: str | None = None,
                  version_type: str = "internal",
                  parent: str | None = None,
                  timestamp: str | None = None,
                  op_type: str = "index") -> dict:
        """One document: a run of one through the bulk write path."""
        svc = self._ensure_index(index)
        op = self._index_op(svc, self._write_context(svc), doc_id, body,
                            routing, doc_type, op_type == "create", version,
                            version_type, ttl, parent, timestamp)
        r = self._index_ops(svc, [op])[0]
        if isinstance(r, ElasticsearchTpuError):
            raise r
        if refresh:
            # per-shard refresh: a doc-level refresh only publishes the
            # WRITTEN shard (ref: TransportIndexAction refresh flag is a
            # shard-level operation; delete/50_refresh.yaml encodes it).
            # Parent folds into routing exactly as the write path did.
            svc.shard_for(op.doc_id,
                          routing if routing is not None else parent
                          ).refresh()
        return r

    @staticmethod
    def _write_context(svc) -> tuple[bool, int | None, int]:
        """What every write of a run needs of its index, read once a run
        (`_index_op` takes it): is routing required, the mapping-level
        default TTL (ref: TTLFieldMapper default), the write time."""
        mapper = svc.mappers
        return (mapper.parent_type is not None or mapper.routing_required,
                getattr(mapper.mapper, "ttl_default_ms", None),
                int(time.time() * 1000))

    def _index_op(self, svc, context: tuple, doc_id: str | None, body,
                  routing=None, doc_type: str | None = None,
                  create: bool = False, version: int | None = None,
                  version_type: str = "internal", ttl=None, parent=None,
                  timestamp=None) -> IndexOp:
        """One write of the index API or of a `_bulk` request as the
        IndexOp its index takes; raises what fails this write alone."""
        need_routing, ttl_default, ts = context
        if doc_id is None:
            import uuid
            doc_id = uuid.uuid4().hex[:20]
        if need_routing:
            self._check_routing_required(svc, doc_id, routing, parent)
        if ttl is None and ttl_default:
            ttl = int(ttl_default)
        # index timestamp: explicit millis/date param or write time
        # (ref: index/mapper/internal/TimestampFieldMapper.java)
        if timestamp is not None:
            from .index.mapping import parse_date_millis
            try:
                ts = int(timestamp)
            except (TypeError, ValueError):
                ts = parse_date_millis(timestamp)
        if ttl is not None:
            # _ttl metadata (ref: index/mapper/internal/TTLFieldMapper +
            # indices/ttl/IndicesTTLService): expiry stored as a normal
            # date column, purged by the TTL sweep. Expiry anchors on
            # the doc timestamp; an already-passed expiry rejects the
            # write (ref: AlreadyExpiredException)
            body = dict(body if isinstance(body, dict)
                        else json.loads(body))
            expiry = int(ts + parse_time_value(ttl, 0))
            if expiry <= int(time.time() * 1000):
                raise IllegalArgumentError(
                    f"AlreadyExpiredException: already expired "
                    f"[{svc.name}]/[{doc_id}]")
            body["_ttl_expiry"] = expiry
        return IndexOp(doc_id, body, version, version_type, create, routing,
                       parent, doc_type, ts)

    def _index_ops(self, svc, ops: list[IndexOp]
                   ) -> list[dict | ElasticsearchTpuError]:
        """A run of writes to one index as one batch per shard
        (IndexService.index_many). One result an op, in order: its
        response, or the error it alone failed with."""
        if self._indexing_slowlog_on(svc):
            # a threshold is on a document's own time: one at a time
            written = []
            for op in ops:
                _t0 = time.monotonic()
                written.extend(svc.index_many([op]))
                if type(written[-1]) is dict:
                    self._indexing_slowlog(
                        svc, op.doc_id, op.source,
                        (time.monotonic() - _t0) * 1000.0)
        else:
            written = svc.index_many(ops)
        self.metrics.counter("indexing.index_total").inc(
            sum(1 for r in written if type(r) is dict))
        return written

    @staticmethod
    def _slowlog(logger_name: str, settings, threshold_prefix: str,
                 took_ms: float, fmt: str, *args) -> None:
        """Shared slowlog core: resolve the warn/info/debug/trace
        thresholds under `threshold_prefix` and emit at the first level
        the duration crosses (ref: both ShardSlowLogSearchService and
        ShardSlowLogIndexingService share this shape)."""
        import logging
        logger = logging.getLogger(logger_name)
        for level, log_fn in (("warn", logger.warning),
                              ("info", logger.info),
                              ("debug", logger.debug),
                              ("trace", logger.debug)):
            thr = settings.get_str(f"{threshold_prefix}.{level}")
            if thr is None:
                continue
            try:
                thr_ms = parse_time_value(thr, default_ms=1 << 60)
            except ElasticsearchTpuError:
                continue  # a bad threshold must never fail the op
            if took_ms >= thr_ms:
                log_fn(fmt, *args)
                return

    @staticmethod
    def _indexing_slowlog_on(svc) -> bool:
        """Is any indexing slowlog threshold configured at all — the
        common (unconfigured) write path must not tax every document."""
        return any(
            svc.settings.get_str(
                f"index.indexing.slowlog.threshold.index.{lvl}") is not None
            for lvl in ("warn", "info", "debug", "trace"))

    @classmethod
    def _indexing_slowlog(cls, svc, doc_id: str, body,
                          took_ms: float) -> None:
        """Per-index indexing slowlog (ref: index/indexing/slowlog/
        ShardSlowLogIndexingService.java; source truncated per
        index.indexing.slowlog.source); the caller has seen
        `_indexing_slowlog_on`."""
        prefix = "index.indexing.slowlog.threshold.index"
        limit = svc.settings.get_int("index.indexing.slowlog.source", 1000)
        src = json.dumps(body, default=str)[:limit] \
            if not isinstance(body, (bytes, str)) else str(body)[:limit]
        cls._slowlog("index.indexing.slowlog.index", svc.settings,
                     prefix, took_ms,
                     "[%s] took[%dms], id[%s], source[%s]", svc.name,
                     int(took_ms), doc_id, src)

    @staticmethod
    def _check_routing_required(svc, doc_id: str, routing, parent) -> None:
        """Parent-mapped (or routing-required) types reject doc ops
        without routing/parent (ref: RoutingMissingException usage in
        TransportIndexAction/TransportGetAction)."""
        if routing is None and parent is None and (
                svc.mappers.parent_type is not None
                or svc.mappers.routing_required):
            from .utils.errors import RoutingMissingError
            raise RoutingMissingError(svc.name, doc_id)

    def get_doc(self, index: str, doc_id: str, routing: str | None = None,
                doc_type: str | None = None, realtime: bool = True,
                parent: str | None = None) -> dict:
        svc = self._index(index)
        self._check_routing_required(svc, doc_id, routing, parent)
        r = svc.get_doc(doc_id,
                        routing if routing is not None else parent,
                        doc_type=doc_type, realtime=realtime)
        src = r.get("_source")
        # _ttl_expiry is metadata, never surfaced; the substring probe
        # gates the parse so untouched docs skip json entirely, then the
        # top-level key alone is stripped, type preserved
        if isinstance(src, (bytes, str)) and b'"_ttl_expiry"' in (
                src if isinstance(src, bytes) else src.encode()):
            obj = json.loads(src)
            if isinstance(obj, dict) and "_ttl_expiry" in obj:
                obj.pop("_ttl_expiry", None)
                clean = json.dumps(obj, separators=(",", ":"))
                r["_source"] = clean if isinstance(src, str) else clean.encode()
        elif isinstance(src, dict) and "_ttl_expiry" in src:
            r["_source"] = {k: v for k, v in src.items()
                            if k != "_ttl_expiry"}
        return r

    def delete_doc(self, index: str, doc_id: str, version: int | None = None,
                   routing: str | None = None, refresh: bool = False,
                   doc_type: str | None = None,
                   version_type: str = "internal",
                   parent: str | None = None) -> dict:
        svc = self._index(index)
        self._check_routing_required(svc, doc_id, routing, parent)
        r = svc.delete_doc(doc_id, version,
                           routing if routing is not None else parent,
                           doc_type=doc_type, version_type=version_type)
        if refresh:
            svc.shard_for(doc_id,
                          routing if routing is not None else parent
                          ).refresh()
        return r

    def update_doc(self, index: str, doc_id: str, body: dict,
                   refresh: bool = False,
                   doc_type: str | None = None,
                   routing: str | None = None,
                   parent: str | None = None,
                   version: int | None = None,
                   fields: list[str] | None = None,
                   ttl: str | None = None,
                   timestamp: str | None = None) -> dict:
        """Partial update: doc merge, script update (ctx._source
        mutation), upsert. Ref: action/update/TransportUpdateAction.java
        + UpdateHelper.java — get, apply doc/script, re-index with the
        read version (optimistic concurrency)."""
        # update auto-creates a missing index when the request can upsert
        # (ref: TransportUpdateAction.doExecute auto-create round trip)
        if index not in self.indices and index not in self._aliases and (
                body.get("upsert") is not None
                or body.get("doc_as_upsert")
                or body.get("scripted_upsert")):
            svc = self._ensure_index(index)
        else:
            svc = self._index(index)
        self._check_routing_required(svc, doc_id, routing, parent)
        routing = routing if routing is not None else parent
        script_spec = body.get("script")
        if isinstance(script_spec, str) and (
                body.get("params") is not None
                or body.get("lang") is not None):
            # 1.x UpdateRequest shape: script/params/lang are request
            # TOP-LEVEL keys (ref: UpdateRequest.source parsing)
            script_spec = {"inline": script_spec,
                           "params": body.get("params") or {},
                           "lang": body.get("lang", "groovy")}
        if script_spec is not None and body.get("doc") is not None:
            # ref: UpdateRequest.validate — "can't provide both script and doc"
            raise IllegalArgumentError(
                "can't provide both script and doc")

        def _with_get(r: dict, new_src: dict) -> dict:
            # ?fields= echoes the post-update doc in a `get` section
            # (ref: UpdateHelper.extractGetResult)
            if fields:
                g: dict = {"found": True}
                if "_source" in fields:
                    g["_source"] = new_src
                flds = {}
                for f in fields:
                    if f == "_parent":
                        if doc_id in svc.doc_parent:
                            flds[f] = svc.doc_parent[doc_id]
                    elif f == "_routing":
                        if doc_id in svc.doc_routing:
                            flds[f] = svc.doc_routing[doc_id]
                    elif f == "_timestamp":
                        if doc_id in svc.doc_ts:
                            flds[f] = svc.doc_ts[doc_id]
                    elif f == "_ttl":
                        exp = new_src.get("_ttl_expiry")
                        if exp:
                            flds[f] = int(exp - time.time() * 1000)
                    elif f != "_source" and f in new_src:
                        v = new_src[f]
                        flds[f] = v if isinstance(v, list) else [v]
                if flds:
                    g["fields"] = flds
                r["get"] = g
            return r

        try:
            current = svc.get_doc(doc_id, routing, doc_type=doc_type)
        except ElasticsearchTpuError:
            if version is not None:
                # versioned update on a missing doc is always a conflict
                # (ref: UpdateRequest version + missing doc)
                from .utils.errors import VersionConflictError
                raise VersionConflictError(index, doc_id, -1, version)
            upsert = body.get("upsert")
            if upsert is None and script_spec is not None and \
                    body.get("scripted_upsert"):
                upsert = {}
            elif upsert is None and body.get("doc_as_upsert"):
                upsert = body.get("doc")
            if upsert is None:
                raise
            if script_spec is not None and body.get("scripted_upsert"):
                upsert = self._run_update_script(script_spec, dict(upsert),
                                                 is_upsert=True)
                if upsert is None:  # ctx.op == none/delete on upsert
                    return {"_index": index, "_id": doc_id,
                            "result": "noop"}
            r = self.index_doc(index, doc_id, upsert, routing=routing,
                               doc_type=doc_type, refresh=refresh,
                               ttl=ttl, timestamp=timestamp,
                               parent=parent)
            return _with_get(r, dict(upsert))
        if version is not None and current["_version"] != version:
            from .utils.errors import VersionConflictError
            raise VersionConflictError(index, doc_id,
                                       current["_version"], version)
        src = json.loads(current["_source"])
        if script_spec is not None:
            new_src = self._run_update_script(script_spec, src)
            if new_src is None:  # ctx.op = "none"
                return {"_index": index, "_id": doc_id,
                        "_version": current["_version"], "result": "noop"}
            if new_src == "__delete__":
                r = svc.delete_doc(doc_id, current["_version"], routing)
                if refresh:
                    svc.shard_for(doc_id, routing).refresh()
                return r
            src = new_src
        else:
            doc_part = body.get("doc")
            if doc_part is None:
                raise IllegalArgumentError(
                    "update requires [doc] or [script]")
            # ref: UpdateRequest.detectNoop — defaults FALSE in 2.0
            # (opt-in; flipped to true only in later ES)
            if body.get("detect_noop", False):
                merged = json.loads(json.dumps(src))
                _deep_merge(merged, doc_part)
                if merged == src:
                    svc.op_stats.on_noop_update()
                    return {"_index": index, "_id": doc_id,
                            "_version": current["_version"],
                            "result": "noop"}
                src = merged
            else:
                _deep_merge(src, doc_part)
        r = self.index_doc(index, doc_id, src,
                           version=current["_version"],
                           routing=routing, doc_type=doc_type,
                           ttl=ttl, timestamp=timestamp, parent=parent,
                           refresh=refresh)
        return _with_get(r, src)

    @staticmethod
    def _run_update_script(script_spec, src: dict, is_upsert: bool = False):
        """Run an update script against ctx._source; returns the new
        source, "__delete__", or None for a noop. Ref: UpdateHelper
        ctx.op handling (index/delete/none)."""
        from .script import parse_script_spec, compile_script
        source, params = parse_script_spec(script_spec)
        cs = compile_script(source)
        ctx = {"_source": src, "op": "index",
               "_now": int(time.time() * 1000)}
        cs.run(params=params, bindings={"ctx": ctx})
        op = ctx.get("op", "index")
        if op in ("none", "noop"):
            return None
        if op == "delete":
            return None if is_upsert else "__delete__"
        return ctx["_source"]

    def bulk(self, operations: list[tuple[str, dict]], refresh: bool = False) -> dict:
        """operations: [(action, payload)] where action in index/create/
        delete/update; payload carries _index/_id/doc. Ref:
        TransportBulkAction.executeBulk grouping by shard.

        Each run of consecutive index / create items for one index is
        applied as one batch per shard (`_index_ops`); a delete, an
        update or another index ends the run, so a write and a later
        delete of the same id keep their order. Items answer in request
        order, each with its own status or error."""
        started = time.monotonic()
        items = []
        errors = False
        touched: set[str] = set()
        batched: set[str] = set()

        def enter_meta_batch(idx: str) -> None:
            if idx not in batched and idx in self.indices:
                batched.add(idx)
                meta_batches.enter_context(
                    self.indices[idx].batched_meta_saves())

        def index_run(idx: str, run: list[tuple[str, dict]]) -> None:
            nonlocal errors
            results: list = [None] * len(run)
            ops, slots = [], []
            try:
                svc = self._ensure_index(idx)
                enter_meta_batch(idx)
                context = self._write_context(svc)
                for i, (action, p) in enumerate(run):
                    try:
                        ops.append(self._index_op(
                            svc, context, p.get("_id"), p["doc"],
                            p.get("_routing"), p.get("_type"),
                            action == "create"))
                        slots.append(i)
                    except ElasticsearchTpuError as e:
                        results[i] = e
                for i, r in zip(slots, self._index_ops(svc, ops)):
                    results[i] = r
            except PowerLossError:
                raise       # the process died: no item is answered
            except ElasticsearchTpuError as e:
                results = [e] * len(run)
            for (action, _p), r in zip(run, results):
                if type(r) is dict:
                    touched.add(idx)
                    r["status"] = 201 if r["created"] else 200
                    items.append({action: r})
                else:
                    errors = True
                    items.append({action: {"error": r.to_dict(),
                                           "status": r.status}})

        # one metadata write-through per index per bulk REQUEST (at the
        # stack's exit, before the response acks anything), not per item
        with contextlib.ExitStack() as meta_batches:
            at, n = 0, len(operations)
            while at < n:
                action, payload = operations[at]
                if action in ("index", "create"):
                    idx = payload["_index"]
                    end = at + 1
                    while end < n and operations[end][0] in (
                            "index", "create") \
                            and operations[end][1]["_index"] == idx:
                        end += 1
                    index_run(idx, operations[at:end])
                    at = end
                    continue
                at += 1
                try:
                    idx = payload["_index"]
                    enter_meta_batch(idx)
                    typ = payload.get("_type")
                    if action == "delete":
                        r = self.delete_doc(idx, payload["_id"], doc_type=typ,
                                            routing=payload.get("_routing"))
                        touched.add(idx)
                        items.append({"delete": {**r, "status": 200 if r.get("found")
                                                 else 404}})
                    elif action == "update":
                        r = self.update_doc(idx, payload["_id"], payload["doc"],
                                            doc_type=typ,
                                            routing=payload.get("_routing"))
                        touched.add(idx)
                        items.append({"update": {**r, "status": 200}})
                    else:
                        raise IllegalArgumentError(f"unknown bulk action [{action}]")
                except ElasticsearchTpuError as e:
                    errors = True
                    items.append({action: {"error": e.to_dict(), "status": e.status}})
        if refresh:
            for idx in touched:
                self.indices[idx].refresh()
        return {"took": int((time.monotonic() - started) * 1000),
                "errors": errors, "items": items}

    # -- search (ref: TransportSearchAction QUERY_THEN_FETCH) --------------
    def search(self, index: str | None, body: dict | None = None,
               scroll: str | None = None,
               search_type: str | None = None,
               tenant: str | None = None,
               request: "profiler.Request | None" = None) -> dict:
        """Admission control FIRST (search/traffic.py): the tenant's
        token bucket / concurrency quota sheds over-quota load with a
        structured 429 (TrafficRejectedError carries retry_after)
        BEFORE the request takes a thread-pool slot or any breaker
        hold — a shed request costs the node nothing but the
        bookkeeping. Then executes on the bounded `search` pool:
        saturation with a full queue answers 429
        EsRejectedExecutionError instead of growing unbounded host
        threads (ref: ThreadPool.java:112-127 SEARCH pool +
        EsRejectedExecutionException). Pool threads re-entering search
        (template/inner flows) run inline to stay deadlock-free and
        are NOT re-admitted — the outer request already paid.

        `request` is what the REST handler made for this search
        (utils/profiler.Request: its id on every phase span, and its
        `rest_parse` phase, out of which the search takes its time); an
        in-process caller gets its id here. The two hand-overs, to the
        pool thread and back, are the `pool_wait` timer."""
        if threading.current_thread().name.startswith("pool-search"):
            return self._search_inner(index, body, scroll, search_type)
        if request is None:
            request = profiler.Request()
        if request.parse is not None:
            request.parse.pause()
        request.hand_over()
        ticket = self.traffic.admit(tenant, "search")
        try:
            pool = self.thread_pool.executor("search")
            result = pool.submit(self._search_inner, index, body, scroll,
                                 search_type, ticket.lane,
                                 request).result()
        finally:
            ticket.release()
        request.taken()
        profiler.waited("pool_wait", request.waited_s)
        if request.parse is not None:
            request.parse.resume()
        return result

    def _search_inner(self, index: str | None, body: dict | None = None,
                      scroll: str | None = None,
                      search_type: str | None = None,
                      lane: str = "interactive",
                      request: "profiler.Request | None" = None) -> dict:
        if request is not None:
            request.taken()
        batch = self._dispatch.batch(lane=lane)
        rid = request.id if request is not None else None
        st = self._search_submit(index, body, scroll, search_type, batch,
                                 rid)
        batch.dispatch()
        result = self._search_finish(st)
        if request is not None:
            request.hand_over()
        return result

    def _search_submit(self, index: str | None, body: dict | None,
                       scroll: str | None, search_type: str | None,
                       batch, request: int | None = None) -> dict:
        """Resolve + bind + enqueue the fan-out of one search onto a
        dispatch batch (search/dispatch.py) WITHOUT collecting — so
        msearch / concurrent callers can coalesce identical plans and
        pipeline the rest before any device round trip completes.
        The `resolve` phase; `request` is the id its spans carry."""
        with profiler.phase("resolve", **profiler.request_args([request])):
            return self._search_resolve(index, body, scroll, search_type,
                                        batch, request)

    def _search_resolve(self, index: str | None, body: dict | None,
                        scroll: str | None, search_type: str | None,
                        batch, request: int | None) -> dict:
        body = body or {}
        services = self._resolve(index)
        shard_readers: list[tuple[str, ShardReader]] = []
        # shard-level containment (ISSUE 15): a FAILED (corrupt-
        # contained) shard becomes a structured `_shards.failures`
        # entry and the search reduces over the survivors — the node
        # stays up, the response says exactly which shard is dark
        prefailed: list[tuple[str, int, Exception]] = []
        for svc in services:
            for sid, eng in svc.shards.items():
                try:
                    shard_readers.append((svc.name,
                                          eng.acquire_searcher()))
                except ShardFailedError as e:
                    prefailed.append((svc.name, sid, e))
        if search_type in ("dfs_query_then_fetch", "dfs_query_and_fetch"):
            # DFS pre-phase: aggregate term statistics across shards so
            # every shard scores with GLOBAL idf (ref: search/dfs/
            # DfsPhase.java + SearchPhaseController.aggregateDfs :88)
            stats = self._aggregate_dfs(shard_readers, services, body)
            if stats:
                body = dict(body)
                body["_dfs_stats"] = stats
        scan_mode = search_type == "scan"
        if scan_mode:
            # scan: cursor-order export, no scoring (ref: search/scan/
            # ScanContext.java:47 + QueryPhase.java:115) — wrap as a
            # constant-score filter; the first response carries only the
            # cursor + total
            body = dict(body)
            body["query"] = {"constant_score": {
                "filter": body.get("query") or {"match_all": {}}}}
        started = time.monotonic()
        # per-request search deadline (ref: the body/URL `timeout` param
        # enforced per shard in QueryPhase): body timeout wins, else the
        # node-level search.default_search_timeout setting; -1 disables
        timeout = body.get("timeout")
        if timeout is None:
            timeout = self.settings.get_str("search.default_search_timeout")
        deadline = None
        if timeout not in (None, "", -1, "-1"):
            deadline = started + parse_time_value(timeout, 0) / 1000.0
        exec_st = self._submit_on_readers(shard_readers, body, batch,
                                          deadline=deadline,
                                          request=request)
        if prefailed:
            exec_st["prefailed"] = prefailed
        return {"services": services, "shard_readers": shard_readers,
                "body": body, "scan_mode": scan_mode, "scroll": scroll,
                "started": started, "exec": exec_st, "request": request}

    def _search_finish(self, st: dict) -> dict:
        result = self._finish_on_readers(st["exec"])
        with profiler.phase("finish",
                            **profiler.request_args([st["request"]])):
            return self._search_account(st, result)

    def _search_account(self, st: dict, result: dict) -> dict:
        """The rest of a search once its shards are reduced: slowlog,
        search stats, doc types, the scroll context."""
        services = st["services"]
        shard_readers = st["shard_readers"]
        body = st["body"]
        scan_mode = st["scan_mode"]
        scroll = st["scroll"]
        took_ms = (time.monotonic() - st["started"]) * 1000.0
        self._search_slowlog(services, body, took_ms)
        # query counter + per-group search stats (ref: body `stats`
        # groups → ShardSearchStats.groupStats); fetch rides the same
        # program here (query_then_fetch fused), suggest when requested
        for svc in services:
            svc.op_stats.on_search(body.get("stats"), took_ms)
            svc.op_stats.on_fetch(1e3 * st["exec"].get("fetch_s", 0.0))
            if body.get("suggest"):
                svc.op_stats.on_suggest(took_ms)
        # surface stored per-doc mapping types on hits (no-op when the
        # index only ever saw untyped writes)
        if any(svc.doc_types for svc in services):
            by_name = {svc.name: svc for svc in services}
            for hit in result.get("hits", {}).get("hits", []):
                svc = by_name.get(hit.get("_index"))
                if svc is not None and svc.doc_types:
                    hit["_type"] = svc.doc_type_of(hit["_id"])
        if scroll is not None:
            import uuid
            scroll_id = uuid.uuid4().hex
            self._reap_scrolls()
            self._scrolls[scroll_id] = {
                "readers": shard_readers, "body": dict(body),
                # scan: the first response returns no hits, the cursor
                # starts at 0; regular scroll continues after page 1
                "pos": 0 if scan_mode else
                       int(body.get("from", 0)) + int(body.get("size", 10)),
                "keepalive_ms": parse_time_value(scroll, 60_000),
                "expires_at": time.time()
                + parse_time_value(scroll, 60_000) / 1000.0,
            }
            result["_scroll_id"] = scroll_id
            if scan_mode:
                result["hits"]["hits"] = []
        return result

    def _aggregate_dfs(self, shard_readers, services, body: dict) -> dict:
        """Collect (field, term) pairs from the query and sum df/doc_count
        across every shard — the aggregateDfs merge."""
        from .search.query_dsl import QueryParser
        from .search.highlight import collect_terms
        if not services or body.get("query") is None:
            return {}
        try:
            ast = QueryParser(services[0].mappers).parse(body["query"])
        except ElasticsearchTpuError:
            return {}
        pairs = [(f, t) for f, terms in collect_terms(ast).items()
                 for t in terms]
        stats: dict[str, list] = {}
        for _, reader in shard_readers:
            for key, (df, n) in reader.term_stats(pairs).items():
                cur = stats.setdefault(key, [0, 0])
                cur[0] += df
                cur[1] += n
        return {k: v for k, v in stats.items() if v[1] > 0}

    def _search_slowlog(self, services, body: dict, took_ms: float) -> None:
        """Per-index search slowlog (ref: index/search/slowlog/
        ShardSlowLogSearchService.java)."""
        for svc in services:
            self._slowlog("index.search.slowlog.query", svc.settings,
                          "index.search.slowlog.threshold.query", took_ms,
                          "[%s] took[%dms], search[%s]", svc.name,
                          int(took_ms), json.dumps(body)[:1000])

    def scroll(self, scroll_id: str, scroll: str | None = None,
               tenant: str | None = None) -> dict:
        """Next page over the stored point-in-time readers (ref:
        TransportSearchScrollAction + SearchService keepalive). Scroll
        pages ride the `scroll` lane (tenant lane override wins) and
        pay admission like any other search — a runaway exporter is
        shed with 429s before it holds anything."""
        ticket = self.traffic.admit(tenant, "scroll")
        try:
            self._reap_scrolls()
            ctx = self._scrolls.get(scroll_id)
            if ctx is None:
                err = ElasticsearchTpuError(
                    f"No search context found for id [{scroll_id}]")
                err.status = 404
                raise err
            body = dict(ctx["body"])
            size = int(body.get("size", 10))
            body["from"] = ctx["pos"]
            ctx["pos"] += size
            if scroll is not None:
                ctx["keepalive_ms"] = parse_time_value(scroll, 60_000)
            ctx["expires_at"] = time.time() + ctx["keepalive_ms"] / 1000.0
            result = self._execute_on_readers(ctx["readers"], body,
                                              lane=ticket.lane)
            result["_scroll_id"] = scroll_id
            return result
        finally:
            ticket.release()

    def clear_scroll(self, scroll_ids: list[str] | None = None) -> dict:
        if scroll_ids is None or scroll_ids == ["_all"]:
            n = len(self._scrolls)
            self._scrolls.clear()
        else:
            n = 0
            for sid in scroll_ids:
                if self._scrolls.pop(sid, None) is not None:
                    n += 1
            if n == 0:
                # ref: RestClearScrollAction — nothing freed is a 404
                return {"succeeded": True, "num_freed": 0, "_missing": True}
        return {"succeeded": True, "num_freed": n}

    def _reap_scrolls(self) -> None:
        now = time.time()
        for sid in [s for s, c in self._scrolls.items()
                    if c["expires_at"] < now]:
            del self._scrolls[sid]

    def _execute_on_readers(self, shard_readers: list[tuple[str, ShardReader]],
                            body: dict, lane: str = "interactive") -> dict:
        batch = self._dispatch.batch(lane=lane)
        st = self._submit_on_readers(shard_readers, body, batch)
        batch.dispatch()
        return self._finish_on_readers(st)

    def _submit_on_readers(self, shard_readers: list[tuple[str, ShardReader]],
                           body: dict, batch,
                           deadline: float | None = None,
                           request: int | None = None) -> dict:
        """Enqueue the per-shard fan-out of one request onto a dispatch
        batch. Identical plans from other requests on the same batch
        coalesce into ONE batched device program; the rest dispatch
        back-to-back so dispatch round trips overlap (the scheduler in
        search/dispatch.py owns both behaviors)."""
        ap = body.get("allow_partial_search_results")
        if ap is None:
            ap = self.settings.get_bool(
                "search.default_allow_partial_results", True)
        st: dict = {"shard_readers": shard_readers, "body": body,
                    "allow_partial": bool(ap), "request": request}
        if not shard_readers:
            st["empty"] = True
            return st
        frm = int(body.get("from", 0))
        size = int(body.get("size", 10))
        # each shard computes the full from+size window (ref: sortDocs)
        shard_body = dict(body)
        shard_body["from"] = 0
        shard_body["size"] = frm + size
        # coordinator-level controls: stripped so plan signatures and
        # request-cache keys stay identical with and without them
        shard_body.pop("timeout", None)
        shard_body.pop("allow_partial_search_results", None)
        from .index.cache import cacheable, canonical_key
        from .search.bound_plans import body_key
        # made once for all shards of the search: what each reader keys
        # its kept plans by
        plan_key = body_key(shard_body)
        cache_key = None
        cache_by_index: dict[str, bool] = {}
        entries: list[tuple] = []
        for name, reader in shard_readers:
            svc = self.indices.get(name)
            use_cache = cache_by_index.get(name)
            if use_cache is None:
                use_cache = svc is not None and cacheable(
                    shard_body, svc.settings.get_bool(
                        "index.cache.query.enable", False),
                    include_hits=svc.settings.get_bool(
                        "index.cache.query.include_hits", False))
                cache_by_index[name] = use_cache
            r = None
            if use_cache:
                if cache_key is None:
                    cache_key = plan_key or canonical_key(shard_body)
                # generation-exact key (reader.generation_key inside
                # the cache): a hit is a pure host-side copy — zero
                # device dispatches/transfers/compiles — and is
                # invalidated exactly by compaction / delta-epoch
                # re-keys, never by a reader republish alone
                r = svc.request_cache.get(reader, cache_key)
                self.traffic.note_cache(hit=r is not None)
            if r is None:
                job = batch.submit(reader, shard_body, with_partials=True,
                                   deadline=deadline, request=request,
                                   key=plan_key)
                entries.append(("job", svc if use_cache else None,
                                reader, cache_key, job))
            else:
                entries.append(("hit", None, None, None, r))
        st["entries"] = entries
        return st

    def _finish_on_readers(self, st: dict) -> dict:
        """The `reduce` phase: collect the shard results of one search
        and merge them. The significant-terms sub-aggregations search
        again and so stay outside the span (spans are leaves)."""
        with profiler.phase("reduce",
                            **profiler.request_args([st.get("request")])):
            out, agg_specs = self._reduce_on_readers(st)
        if agg_specs is not None:
            self._apply_sig_subs(out, agg_specs, st["body"],
                                 st["shard_readers"])
        return out

    def _reduce_on_readers(self, st: dict) -> tuple[dict, list | None]:
        body = st["body"]
        prefailed = st.get("prefailed") or []
        if st.get("empty") and not prefailed:
            # zero shards: empty result (ref: empty SearchResponse)
            return merge_shard_results([], [], [], 0,
                                       int(body.get("size", 10))), None
        agg_specs = parse_aggs(body.get("aggs") or body.get("aggregations"))
        suggest_specs = parse_suggest(body.get("suggest"))
        frm = int(body.get("from", 0))
        size = int(body.get("size", 10))
        allow_partial = st.get("allow_partial", True)
        responses = []
        partials = []
        suggest_parts = []
        failures = []
        hard_errors = []
        timed_out = False
        # contained (corrupt-failed) shards never produced a reader:
        # they enter the reduce as structured failures up front, and
        # fail-fast requests re-raise exactly like an in-flight shard
        # error would
        for name, sid, exc in prefailed:
            if not allow_partial:
                raise exc
            hard_errors.append(exc)
            failures.append(shard_failure(sid, name, exc,
                                          node=self.name))
        for kind, svc, reader, cache_key, payload in st.get("entries", ()):
            if kind == "job":
                # per-shard failure isolation (ref: onShardFailure in
                # TransportSearchTypeAction): a failing shard becomes a
                # structured `_shards.failures` entry and the reduce
                # runs over the survivors — unless the request (or
                # search.default_allow_partial_results) asked for
                # fail-fast, which restores the old re-raise
                try:
                    r = payload.result()
                except Exception as e:  # noqa: BLE001 — any shard error
                    if isinstance(e, SearchTimeoutError):
                        timed_out = True
                    else:
                        hard_errors.append(e)
                    if not allow_partial:
                        raise
                    failures.append(shard_failure(
                        reader.shard_id, reader.index_name, e,
                        node=self.name))
                    continue
                st["fetch_s"] = st.get("fetch_s", 0.0) + payload.fetch_s
                if svc is not None:
                    svc.request_cache.put(reader, cache_key, r)
            else:
                r = payload
            partials.append(r.pop("_agg_partials", {}))
            if "suggest" in r:
                suggest_parts.append(r.pop("suggest"))
            responses.append(r)
        if not responses and hard_errors:
            # ALL shards failed hard (ref: SearchPhaseExecutionException
            # "all shards failed"): a partial response needs at least one
            # survivor; a query that is broken everywhere — parse error,
            # every copy dead — stays an error. All-shards-TIMED-OUT is
            # different: the reference answers that with an (empty)
            # `timed_out: true` response, so pure-timeout exits fall
            # through to the partial reduce below.
            raise hard_errors[0]
        sort = body.get("sort")
        score_sort = sort in (None, [], "_score") or (
            isinstance(sort, list) and sort and sort[0] == "_score")
        descending = True
        multi_orders = None
        if isinstance(sort, list) and len(sort) > 1:
            multi_orders = []
            for e in sort:
                if isinstance(e, str):
                    multi_orders.append(False)
                else:
                    spec = next(iter(e.values()))
                    order = (spec.get("order", "asc")
                             if isinstance(spec, dict) else str(spec))
                    multi_orders.append(str(order).lower() == "desc")
            score_sort = False
        elif not score_sort:
            entry = sort[0] if isinstance(sort, list) else sort
            if isinstance(entry, dict):
                spec = next(iter(entry.values()))
                order = (spec.get("order", "asc") if isinstance(spec, dict)
                         else str(spec))
                descending = order.lower() == "desc"
            else:
                descending = False
        self.metrics.counter("search.query_total").inc()
        if timed_out:
            self.metrics.counter("search.timed_out_total").inc()
        if failures:
            self.metrics.counter("search.shard_failures_total").inc(
                len(failures))
        # the coordinator's merge proper, apart from the rest of
        # `reduce` (the jobs' results, the request-cache puts): an
        # always-on timer beside `phases` and a span for the viewer
        stats = self._dispatch.stats
        stats.record_merge(responses)
        with profiler.enclosing("request:merge", timer=stats.merge,
                                **profiler.request_args(
                                    [st.get("request")]),
                                shards=len(responses)):
            out = merge_shard_results(
                responses, agg_specs, partials, frm=frm, size=size,
                descending=descending, score_sort=score_sort,
                multi_orders=multi_orders,
                total_shards=len(st.get("entries", ())) + len(prefailed),
                failures=failures, timed_out=timed_out)
        if suggest_specs:
            out["suggest"] = merge_suggests(suggest_parts, suggest_specs)
        return out, agg_specs

    def _apply_sig_subs(self, out: dict, agg_specs, body: dict,
                        shard_readers) -> None:
        """significant_terms nested under a terms agg, fanned over the
        SAME shard set and JLH-scored at the coordinator (see
        aggregations.apply_sig_subs). The enclosing-query foreground
        scope is honored via a capped (10k) matching-id set."""
        if not any(getattr(spec, "sig_subs", None) for spec in agg_specs):
            return
        from .search.aggregations import apply_sig_subs

        def search_ids(query: dict) -> set:
            r = self._execute_on_readers(
                shard_readers, {"query": query, "size": 10_000,
                                "_source": False})
            return {h["_id"] for h in r["hits"]["hits"]}

        apply_sig_subs(agg_specs, out.get("aggregations", {}),
                       [reader for _, reader in shard_readers],
                       raw_query=body.get("query"),
                       search_ids=search_ids)
    def msearch(self, requests: list[tuple],
                tenant: str | None = None) -> dict:
        """Multi-search through the dispatch scheduler: every item's
        fan-out is SUBMITTED before anything is collected, so items
        whose plans finalize identically coalesce into one batched
        device dispatch and the rest pipeline their dispatch round trips
        (vs the serial self.search loop this replaces). Items are
        (index, body) or (index, body, search_type) tuples.

        Admission is PER ITEM (search/traffic.py): the tenant's token
        bucket grants the longest admissible prefix, the rejected tail
        answers structured per-item 429s with `retry_after` — an
        over-quota bulk tenant degrades to partial progress, it is
        never errored wholesale, and no shed item ever touches a
        thread-pool slot or breaker hold.

        Per-request failure isolation: one bad search (e.g. missing
        index) yields an error entry, not a failed batch; every item
        carries its own `took` and `status` (ref:
        TransportMultiSearchAction item responses)."""
        if threading.current_thread().name.startswith("pool-search"):
            return self._msearch_inner(requests)
        from .utils.errors import TrafficRejectedError
        items = self.traffic.admit_items(tenant, "msearch",
                                         len(requests))
        try:
            admitted = requests[:items.granted]
            responses: list[dict] = []
            if admitted:
                pool = self.thread_pool.executor("search")
                try:
                    responses = pool.submit(
                        self._msearch_inner, admitted,
                        items.lane).result()["responses"]
                except ElasticsearchTpuError as e:
                    if e.status != 429:
                        raise
                    # pool saturation: keep the old serial loop's
                    # per-item isolation — every admitted item answers
                    # 429, the batch shape holds
                    responses = [
                        {"error": _legacy_error_string(e),
                         "status": e.status}
                        for _ in admitted]
            if items.granted < len(requests):
                shed = TrafficRejectedError(
                    items.tenant, "rate limit exceeded",
                    retry_after_s=items.retry_after_s)
                responses.extend(
                    {"error": _legacy_error_string(shed),
                     "status": shed.status,
                     "retry_after": shed.info["retry_after"]}
                    for _ in range(len(requests) - items.granted))
            return {"responses": responses}
        finally:
            items.release()

    def _msearch_inner(self, requests: list[tuple],
                       lane: str = "msearch") -> dict:
        batch = self._dispatch.batch(lane=lane)
        prepared: list[tuple] = []
        for item in requests:
            i, b = item[0], item[1]
            search_type = item[2] if len(item) > 2 else None
            t0 = time.monotonic()
            try:
                # an id of its own for each item: its spans name it, and
                # the batch counts its searches by their ids
                st = self._search_submit(i, b, None, search_type, batch,
                                         profiler.next_request_id())
                prepared.append((t0, None, st))
            except ElasticsearchTpuError as e:
                prepared.append((t0, e, None))
        batch.dispatch()
        out = []
        for t0, err, st in prepared:
            if err is None:
                try:
                    r = self._search_finish(st)
                    r["took"] = int((time.monotonic() - t0) * 1000)
                    r["status"] = 200
                    out.append(r)
                    continue
                except ElasticsearchTpuError as e:
                    err = e
            out.append({"error": _legacy_error_string(err),
                        "status": err.status})
        return {"responses": out}

    def count(self, index: str | None, body: dict | None = None) -> dict:
        r = self.search(index, {"query": (body or {}).get("query"), "size": 0})
        return {"count": r["hits"]["total"], "_shards": r["_shards"]}

    # -- admin -------------------------------------------------------------
    def _broadcast_per_index(self, svcs, op) -> dict:
        """Run a per-index maintenance op with real shard accounting:
        an index whose op raises contributes structured failures for its
        shards instead of fabricating `failed: 0` (the same
        shards_header the search reduce uses)."""
        total = successful = 0
        failures: list[dict] = []
        for svc in svcs:
            n = len(svc.shards)
            total += n
            try:
                op(svc)
                successful += n
            except Exception as e:  # noqa: BLE001 — per-index isolation
                failures.extend(
                    shard_failure(sid, svc.name, e, node=self.name)
                    for sid in svc.shards)
        return {"_shards": shards_header(total, successful, failures)}

    def refresh(self, index: str | None = None) -> dict:
        svcs = self._resolve(index)

        def op(svc):
            svc.refresh()
            if getattr(svc, "warmers", None):
                self._run_warmers(svc)

        return self._broadcast_per_index(svcs, op)

    def flush(self, index: str | None = None) -> dict:
        return self._broadcast_per_index(self._resolve(index),
                                         lambda svc: svc.flush())

    def force_merge(self, index: str | None = None,
                    max_num_segments: int = 1) -> dict:
        for svc in self._resolve(index):
            svc.force_merge(max_num_segments)
        return {"acknowledged": True}

    def put_mapping(self, index: str | None, mapping: dict,
                    doc_type: str | None = None) -> dict:
        if mapping and "properties" not in mapping and "dynamic" not in mapping:
            tname, first = next(iter(mapping.items()), (None, None))
            if isinstance(first, dict) and ("properties" in first
                                            or "dynamic" in first
                                            or not first):
                doc_type = doc_type or tname
                mapping = first
        for svc in self._resolve(index, metadata_op=True):
            if doc_type and doc_type not in ("_all", "*", "_doc"):
                svc.mapping_types.add(doc_type)
                svc.mappers.put_type_mapping(doc_type, mapping or {})
            else:
                svc.mappers.merge_mapping(mapping or {})
            self._persist_svc_meta(svc)
        return {"acknowledged": True}

    def get_mapping(self, index: str | None = None,
                    doc_type: str | None = None,
                    expand_wildcards: str = "open") -> dict:
        """GET _mapping[/{type}] — per-type rendering with type-name
        filtering; indices with no matching type are omitted (ref:
        RestGetMappingAction + GetMappingsResponse)."""
        import fnmatch
        pats = None
        if doc_type not in (None, "", "_all", "*"):
            pats = [p.strip() for p in str(doc_type).split(",")]
        out = {}
        for svc in self._resolve(index, expand_wildcards,
                                 metadata_op=True):
            types = sorted(svc.mapping_types)
            if not types and svc.mappers.mapping_dict().get("properties"):
                # untyped (modern-style) mapping renders under _doc
                types = ["_doc"]
            sel = {t: (svc.mappers.type_mapping_dict(t) if t != "_doc"
                       else svc.mappers.mapping_dict())
                   for t in types
                   if pats is None
                   or any(fnmatch.fnmatch(t, p) for p in pats)}
            if pats is None or sel:
                out[svc.name] = {"mappings": sel}
        return out

    def get_field_mapping(self, index: str | None, fields: str,
                          doc_type: str | None = None,
                          include_defaults: bool = False) -> dict:
        """GET _mapping[/{type}]/field/{fields} (ref: action/admin/
        indices/mapping/get/TransportGetFieldMappingsAction.java) —
        {index: {mappings: {type: {field: {full_name, mapping}}}}}."""
        import fnmatch
        fpats = [p.strip() for p in str(fields).split(",")]
        tpats = None
        if doc_type not in (None, "", "_all", "*"):
            tpats = [p.strip() for p in str(doc_type).split(",")]
        out: dict = {}
        type_seen = False
        for svc in self._resolve(index, metadata_op=True):
            types = sorted(svc.mapping_types) or ["_doc"]
            tsel: dict = {}
            for t in types:
                if tpats is not None and not any(
                        fnmatch.fnmatch(t, p) for p in tpats):
                    continue
                type_seen = True
                view = (svc.mappers.types.get(t)
                        if t != "_doc" else None) or svc.mappers.mapper
                fsel: dict = {}
                added: set[str] = set()

                def emit(key: str, fname: str, fm) -> None:
                    if key in fsel or fname in added:
                        return
                    spec = fm.to_dict()
                    if include_defaults and fm.type == "text":
                        spec.setdefault("analyzer", "default")
                    fsel[key] = {"full_name": fname,
                                 "mapping": {fname.rsplit(".", 1)[-1]:
                                             spec}}
                    added.add(fname)

                # two resolve rounds with full-name preference (ref:
                # TransportGetFieldMappingsAction full name > short name)
                for pat in fpats:
                    for fname, fm in sorted(view._fields.items()):
                        if fnmatch.fnmatch(fname, pat):
                            emit(fname, fname, fm)
                    for fname, fm in sorted(view._fields.items()):
                        short = fname.rsplit(".", 1)[-1]
                        if fnmatch.fnmatch(short, pat):
                            emit(short, fname, fm)
                if fsel:
                    tsel[t] = fsel
            if tsel:
                out[svc.name] = {"mappings": tsel}
        if tpats is not None and not type_seen and not any(
                "*" in p or "?" in p for p in tpats):
            from .utils.errors import TypeMissingError
            raise TypeMissingError(doc_type)  # ref: TypeMissingException
        return out

    def get_settings(self, index: str | None = None,
                     flat: bool = False,
                     name: str | None = None,
                     expand_wildcards: str = "open") -> dict:
        """GET _settings[/{name}]: nested string-valued tree by default,
        flat dotted keys with ?flat_settings=true, optional setting-name
        filter incl. wildcards (ref: RestGetSettingsAction +
        Settings.toXContent)."""
        import fnmatch
        pats = None
        if name not in (None, "", "_all", "*"):
            pats = [p.strip() for p in str(name).split(",")]
        out = {}
        for svc in self._resolve(index, expand_wildcards,
                                 metadata_op=True):
            entries = {"index.number_of_shards": str(svc.num_shards),
                       "index.number_of_replicas": str(svc.num_replicas),
                       "index.uuid": svc.name,
                       "index.version.created": "2000099"}
            for k, v in svc.settings.as_dict().items():
                if k.startswith("index."):
                    entries[k] = str(v)
            if pats is not None:
                entries = {k: v for k, v in entries.items()
                           if any(fnmatch.fnmatch(k, p) for p in pats)}
            if flat:
                out[svc.name] = {"settings": dict(entries)}
            else:
                nested: dict = {}
                for k, v in entries.items():
                    cur = nested
                    parts = k.split(".")
                    for part in parts[:-1]:
                        nxt = cur.setdefault(part, {})
                        if not isinstance(nxt, dict):
                            nxt = cur[part] = {}
                        cur = nxt
                    cur[parts[-1]] = v
                out[svc.name] = {"settings": nested}
        return out

    def update_index_settings(self, index: str | None, body: dict,
                              ignore_unavailable: bool = False) -> dict:
        """PUT _settings (ref: MetaDataUpdateSettingsService — dynamic
        per-index settings; number_of_replicas is the canonical one)."""
        flat: dict = {}

        def flatten(prefix, obj):
            for k, v in (obj or {}).items():
                key = f"{prefix}{k}"
                if isinstance(v, dict):
                    flatten(key + ".", v)
                else:
                    flat[key] = v
        body = body or {}
        flatten("", body.get("settings", body))
        norm = {}
        for k, v in flat.items():
            if not k.startswith("index."):
                k = "index." + k
            norm[k] = v
        for svc in self._resolve(index,
                                 ignore_unavailable=ignore_unavailable):
            if "index.number_of_replicas" in norm:
                svc.num_replicas = int(norm["index.number_of_replicas"])
            svc.settings = svc.settings.merged_with(norm)
        return {"acknowledged": True}

    def cluster_health(self, level: str | None = None,
                       index: str | None = None) -> dict:
        svcs = self._resolve(index) if index else list(self.indices.values())
        shards = sum(len(s.shards) for s in svcs)
        out = {
            "cluster_name": self.cluster_name,
            "status": "green",
            "timed_out": False,
            "number_of_nodes": 1,
            "number_of_data_nodes": 1,
            "active_primary_shards": shards,
            "active_shards": shards,
            "relocating_shards": 0,
            "initializing_shards": 0,
            "unassigned_shards": 0,
            "delayed_unassigned_shards": 0,
            "number_of_pending_tasks": 0,
            "number_of_in_flight_fetch": 0,
            "task_max_waiting_in_queue_millis": 0,
            "active_shards_percent_as_number": 100.0,
        }
        if level in ("indices", "shards"):
            out["indices"] = {}
            for svc in svcs:
                entry = {
                    "status": "green",
                    "number_of_shards": svc.num_shards,
                    "number_of_replicas": svc.num_replicas,
                    "active_primary_shards": svc.num_shards,
                    "active_shards": svc.num_shards,
                    "relocating_shards": 0,
                    "initializing_shards": 0,
                    "unassigned_shards": 0,
                }
                if level == "shards":
                    entry["shards"] = {
                        str(sid): {"status": "green", "primary_active": True,
                                   "active_shards": 1,
                                   "relocating_shards": 0,
                                   "initializing_shards": 0,
                                   "unassigned_shards": 0}
                        for sid in svc.shards}
                out["indices"][svc.name] = entry
        return out

    def stats(self) -> dict:
        return {
            "cluster_name": self.cluster_name,
            "indices": {name: svc.stats() for name, svc in self.indices.items()},
            "metrics": self.metrics.snapshot(),
        }

    def cat_indices(self) -> list[dict]:
        out = []
        for name, svc in sorted(self.indices.items()):
            size = sum(e.segment_stats()["memory_in_bytes"]
                       for e in svc.shards.values())
            out.append({"health": "green",
                        "status": ("close" if name in self._closed
                                   else "open"),
                        "index": name,
                        "pri": svc.num_shards, "rep": svc.num_replicas,
                        "docs.count": svc.doc_count(),
                        "docs.deleted": 0,
                        "store.size": size, "pri.store.size": size})
        return out

    # -- aliases (ref: MetaDataIndexAliasesService, rest/action/admin/
    # indices/alias/) ------------------------------------------------------
    def update_aliases(self, actions: list[dict]) -> dict:
        import fnmatch
        for entry in actions:
            op, spec = next(iter(entry.items()))
            # index/indices and alias/aliases forms both accepted
            # (ref: IndicesAliasesRequest AliasActions)
            idx_expr = spec.get("index", spec.get("indices"))
            if isinstance(idx_expr, list):
                idx_expr = ",".join(idx_expr)
            aliases = spec.get("aliases", spec.get("alias"))
            if not aliases:
                raise IllegalArgumentError("[aliases] requires [alias]")
            alias_list = (aliases if isinstance(aliases, list)
                          else [aliases])
            if idx_expr is None:
                # ref: IndicesAliasesRequest.validate
                raise IllegalArgumentError(
                    f"[aliases] action [{op}] requires an [index]")
            if op == "add":
                svcs = self._resolve(idx_expr, metadata_op=True)
                if not svcs and idx_expr is not None \
                        and "*" not in str(idx_expr):
                    raise IndexNotFoundError(idx_expr)
                meta: dict = {}
                if spec.get("filter") is not None:
                    meta["filter"] = spec["filter"]
                routing = spec.get("routing")
                ir = spec.get("index_routing",
                              spec.get("index-routing", routing))
                sr = spec.get("search_routing",
                              spec.get("search-routing", routing))
                if ir is not None:
                    meta["index_routing"] = str(ir)
                if sr is not None:
                    meta["search_routing"] = str(sr)
                for alias in alias_list:
                    for svc in svcs:
                        self._aliases.setdefault(alias, set()).add(svc.name)
                        # alias metadata: filter + routing split (ref:
                        # cluster/metadata/AliasMetaData.java — `routing`
                        # sets both index_ and search_routing)
                        self._alias_meta[(alias, svc.name)] = dict(meta)
            elif op == "remove":
                removed = False
                index_names = [s.name for s in
                               self._resolve(idx_expr, metadata_op=True)]
                alias_list = ["*" if p == "_all" else p
                              for p in alias_list]
                for pat in alias_list:
                    for a in list(self._aliases):
                        if not fnmatch.fnmatch(a, pat):
                            continue
                        targets = self._aliases[a]
                        for iname in index_names:
                            if iname in targets:
                                targets.discard(iname)
                                self._alias_meta.pop((a, iname), None)
                                removed = True
                        if not targets:
                            del self._aliases[a]
                if not removed:
                    from .utils.errors import AliasesMissingError
                    raise AliasesMissingError(alias_list)
            else:
                raise IllegalArgumentError(f"unknown alias action [{op}]")
        return {"acknowledged": True}

    def put_alias(self, index: str | None, alias: str,
                  body: dict | None = None) -> dict:
        spec = {"index": index, "alias": alias, **(body or {})}
        return self.update_aliases([{"add": spec}])

    def delete_alias(self, index: str, alias: str) -> dict:
        return self.update_aliases([{"remove": {"index": index,
                                                "alias": alias}}])

    def alias_meta(self, alias: str, index: str) -> dict:
        return self._alias_meta.get((alias, index), {})

    def get_aliases(self, index: str | None = None,
                    name: str | None = None,
                    include_empty: bool = False) -> dict:
        """`include_empty` distinguishes the /_aliases rendering (every
        resolved index appears, possibly with an empty aliases map) from
        /_alias (indices with no matching alias are omitted). Ref:
        RestGetAliasesAction vs RestGetIndicesAliasesAction."""
        import fnmatch
        pats = None
        if name not in (None, "", "_all", "*"):
            pats = [p.strip() for p in str(name).split(",")]
        out: dict = {}
        for svc in self._resolve(index, metadata_op=True):
            aliases = {}
            for a, targets in self._aliases.items():
                if svc.name not in targets:
                    continue
                if pats is not None and not any(
                        fnmatch.fnmatch(a, p) for p in pats):
                    continue
                aliases[a] = self.alias_meta(a, svc.name)
            if pats is None or aliases or include_empty:
                out[svc.name] = {"aliases": aliases}
        return out

    # -- templates (ref: MetaDataIndexTemplateService) ---------------------
    @staticmethod
    def _alias_spec_meta(spec) -> dict:
        """Normalize an alias spec to AliasMetaData rendering (routing
        splits into index_routing/search_routing)."""
        meta: dict = {}
        spec = spec if isinstance(spec, dict) else {}
        if spec.get("filter") is not None:
            meta["filter"] = spec["filter"]
        routing = spec.get("routing")
        ir = spec.get("index_routing", routing)
        sr = spec.get("search_routing", routing)
        if ir is not None:
            meta["index_routing"] = str(ir)
        if sr is not None:
            meta["search_routing"] = str(sr)
        return meta

    def put_template(self, name: str, body: dict,
                     create: bool = False) -> dict:
        if create and name in self._templates:
            raise IllegalArgumentError(
                f"index_template [{name}] already exists")
        patterns = body.get("index_patterns") or body.get("template")
        if patterns is None:
            raise IllegalArgumentError(
                "index template requires [index_patterns]")
        if isinstance(patterns, str):
            patterns = [patterns]
        mappings = body.get("mappings") or {}
        if mappings and "properties" not in mappings:
            first = next(iter(mappings.values()), None)
            if isinstance(first, dict) and "properties" in first:
                mappings = first
        # settings normalize to flat "index."-prefixed string values
        # (ref: IndexTemplateMetaData settings rendering)
        flat = Settings(body.get("settings") or {}).as_dict()
        settings = {(k if k.startswith("index.") else f"index.{k}"):
                    str(v) for k, v in flat.items()}
        self._templates[name] = {
            "patterns": list(patterns),
            "order": int(body.get("order", 0)),
            "settings": settings,
            "mappings": dict(mappings),
            "aliases": dict(body.get("aliases") or {}),
        }
        return {"acknowledged": True}

    def get_templates(self, name: str | None = None,
                      flat: bool = False) -> dict:
        """GET _template[/{name}] in the 2.0 shape: single `template`
        pattern, string-valued settings (nested unless flat_settings),
        AliasMetaData-shaped aliases. A concrete missing name is a 404
        (ref: RestGetIndexTemplateAction)."""
        import fnmatch
        out = {}
        for tname, t in sorted(self._templates.items()):
            if name in (None, "*") or fnmatch.fnmatch(tname, name):
                settings: dict = dict(t["settings"])
                if not flat:
                    nested: dict = {}
                    for k, v in settings.items():
                        cur = nested
                        parts = k.split(".")
                        for part in parts[:-1]:
                            nxt = cur.setdefault(part, {})
                            if not isinstance(nxt, dict):
                                nxt = cur[part] = {}
                            cur = nxt
                        cur[parts[-1]] = v
                    settings = nested
                out[tname] = {"template": t["patterns"][0],
                              "index_patterns": t["patterns"],
                              "order": t["order"],
                              "settings": settings,
                              "mappings": t["mappings"],
                              "aliases": {a: self._alias_spec_meta(sp)
                                          for a, sp in
                                          t["aliases"].items()}}
        if not out and name is not None and "*" not in name:
            raise IndexNotFoundError(f"index_template [{name}]")
        return out

    def delete_template(self, name: str) -> dict:
        if name not in self._templates:
            raise IndexNotFoundError(f"index_template [{name}] missing")
        del self._templates[name]
        return {"acknowledged": True}

    # -- open/close (ref: MetaDataIndexStateService) -----------------------
    def close_index(self, name: str) -> dict:
        for svc in self._resolve(name, expand_wildcards="open",
                                 metadata_op=True):
            self._closed.add(svc.name)
        return {"acknowledged": True}

    def open_index(self, name: str) -> dict:
        for svc in self._resolve(name, expand_wildcards="open,closed",
                                 metadata_op=True):
            self._closed.discard(svc.name)
        return {"acknowledged": True}

    # -- validate / explain ------------------------------------------------
    def validate_query(self, index: str | None, body: dict | None,
                       explain: bool = False) -> dict:
        """Ref: action/admin/indices/validate/query/."""
        from .search.query_dsl import QueryParser
        services = self._resolve(index)
        mapper = services[0].mappers if services else None
        try:
            if mapper is None:
                from .index.mapping import MapperService
                mapper = MapperService()
            q = QueryParser(mapper).parse((body or {}).get("query"))
            out = {"valid": True,
                   "_shards": {"total": 1, "successful": 1, "failed": 0}}
            if explain:
                from .search.query_dsl import lucene_str
                out["explanations"] = [
                    {"index": svc.name, "valid": True,
                     "explanation": lucene_str(q)} for svc in services]
            return out
        except ElasticsearchTpuError as e:
            return {"valid": False,
                    "_shards": {"total": 1, "successful": 1, "failed": 0},
                    "error": str(e)}

    def explain_doc(self, index: str, doc_id: str, body: dict | None) -> dict:
        """Ref: action/explain/TransportExplainAction — score breakdown of
        one doc against a query (matched + value; the per-term Lucene
        explanation tree maps to the eager-impact summary here)."""
        svc = self._index(index)  # resolves aliases; 404 when missing
        query = (body or {}).get("query") or {"match_all": {}}
        restricted = {"bool": {"must": [query],
                               "filter": [{"ids": {"values": [doc_id]}}]}}
        r = self.search(svc.name, {"query": restricted, "size": 1})
        matched = r["hits"]["total"] > 0
        out = {"_index": svc.name, "_type": svc.doc_type_of(doc_id),
               "_id": doc_id, "matched": matched}
        if matched:
            hit = r["hits"]["hits"][0]
            out["explanation"] = {
                "value": hit.get("_score") or 0.0,
                "description": "sum of eager-impact BM25 term scores "
                               "(device batch scorer)",
                "details": []}
        src_spec = (body or {}).get("_source")
        if src_spec is not None:
            # ?_source=... adds a get section with the filtered source
            # (ref: TransportExplainAction fetchSourceContext)
            from .search.shard_searcher import filter_source
            g: dict = {"found": True}
            try:
                doc = self.get_doc(index, doc_id)
                obj = doc.get("_source")
                obj = (json.loads(obj)
                       if isinstance(obj, (bytes, str)) else obj)
                filtered = filter_source(obj or {}, src_spec)
                if filtered is not None:
                    g["_source"] = filtered
            except ElasticsearchTpuError:
                g["found"] = False
            out["get"] = g
        return out

    # -- percolator (ref: percolator/PercolatorService.java; REST 2.0
    # shape: queries registered under the .percolator type, executed via
    # /{index}/_percolate) ------------------------------------------------
    def register_percolator(self, index: str, query_id: str,
                            body: dict | None) -> dict:
        svc = self._ensure_index(index)
        r = svc.percolator.register(query_id, body or {})
        return {"_index": svc.name, "_type": ".percolator", "_id": query_id,
                "created": r["created"], "_version": 1}

    def unregister_percolator(self, index: str, query_id: str) -> dict:
        svc = self._index(index)
        found = svc.percolator.unregister(query_id)
        return {"_index": svc.name, "_type": ".percolator", "_id": query_id,
                "found": found}

    def get_percolator(self, index: str, query_id: str) -> dict:
        svc = self._index(index)
        q = svc.percolator.get(query_id)
        out = {"_index": svc.name, "_type": ".percolator", "_id": query_id,
               "found": q is not None}
        if q is not None:
            out["_source"] = q
        return out

    def percolate(self, index: str, body: dict | None,
                  count_only: bool = False) -> dict:
        body = body or {}
        doc = body.get("doc")
        if doc is None:
            raise IllegalArgumentError("percolate request requires [doc]")
        svc = self._index(index)
        from .index.stats import timed
        with timed() as t:
            res = svc.percolate(doc, body.get("filter"), body.get("size"))
        svc.op_stats.on_percolate(t.ms)
        out = {"took": 0, "_shards": {"total": svc.num_shards,
                                      "successful": svc.num_shards,
                                      "failed": 0},
               "total": res["total"]}
        if not count_only:
            out["matches"] = res["matches"]
        return out

    def mpercolate(self, payload: list[dict]) -> dict:
        """_mpercolate: alternating {percolate: {...}} header / doc lines
        (ref: action/percolate/TransportMultiPercolateAction)."""
        responses = []
        i = 0
        while i + 1 < len(payload) or (i < len(payload) and
                                       "percolate" in payload[i]):
            header = payload[i].get("percolate") or {}
            body = payload[i + 1] if i + 1 < len(payload) else {}
            i += 2
            try:
                responses.append(self.percolate(header.get("index"), body))
            except ElasticsearchTpuError as e:
                responses.append({"error": _legacy_error_string(e)})
        return {"responses": responses}

    def segments(self, index: str | None = None,
                 ignore_unavailable: bool = False,
                 allow_no_indices: bool = True) -> dict:
        """GET _segments (ref: action/admin/indices/segments/
        IndicesSegmentsAction — per-shard copy rows with routing +
        named Lucene-style segment entries)."""
        svcs = self._resolve(index, ignore_unavailable=ignore_unavailable)
        if not svcs and not allow_no_indices:
            raise IndexNotFoundError(index if index else "_all")
        out = {}
        n_shards = 0
        for svc in svcs:
            shards = {}
            for sid, eng in svc.shards.items():
                n_shards += 1
                segs = {}
                for i, seg in enumerate(eng.segments):
                    live = eng.live.get(seg.seg_id)
                    num_live = (int(live.sum()) if live is not None
                                else seg.num_docs)
                    segs[f"_{i}"] = {
                        "generation": i,
                        "num_docs": num_live,
                        "deleted_docs": seg.num_docs - num_live,
                        "size_in_bytes": seg.nbytes(),
                        "memory_in_bytes": seg.nbytes(),
                        "committed": True, "search": True,
                        "version": "tpu-columnar", "compound": False,
                    }
                shards[str(sid)] = [{
                    "routing": {"state": "STARTED", "primary": True,
                                "node": self.name},
                    "num_committed_segments": len(segs),
                    "num_search_segments": len(segs),
                    "segments": segs,
                }]
            out[svc.name] = {"shards": shards}
        return {"_shards": {"total": n_shards, "successful": n_shards,
                            "failed": 0},
                "indices": out}

    # -- cluster settings (ref: ClusterUpdateSettingsAction) ---------------
    def get_cluster_settings(self) -> dict:
        return {"persistent": dict(getattr(self, "_persistent_settings", {})),
                "transient": dict(getattr(self, "_transient_settings", {}))}

    def put_cluster_settings(self, body: dict) -> dict:
        pers = dict(getattr(self, "_persistent_settings", {}))
        trans = dict(getattr(self, "_transient_settings", {}))
        pers.update(body.get("persistent") or {})
        trans.update(body.get("transient") or {})
        self._persistent_settings = pers
        self._transient_settings = trans
        # traffic quotas are DYNAMIC: republish the effective
        # `search.traffic.*` group (node settings layered under
        # persistent under transient) into the controller — counters
        # and in-flight accounting survive, limits change immediately
        merged = self.settings.merged_with(Settings(pers)) \
                     .merged_with(Settings(trans))
        self.traffic.reconfigure(
            merged.by_prefix("search.traffic.").as_dict())
        return {"acknowledged": True, "persistent": pers,
                "transient": trans}

    def cluster_state(self, metrics: str | None = None,
                      index: str | None = None,
                      expand_wildcards: str = "open",
                      ignore_unavailable: bool = False,
                      allow_no_indices: bool = True) -> dict:
        """Full state, or sections selected by the `metrics` path part
        (ref: RestClusterStateAction metric filtering)."""
        if index:
            svcs = self._resolve(index, expand_wildcards,
                                 ignore_unavailable=ignore_unavailable,
                                 metadata_op=True)
            if not svcs and not allow_no_indices:
                raise IndexNotFoundError(index)
            names = [s.name for s in svcs]
        else:
            names = list(self.indices)
        # index-level blocks from index.blocks.* settings (ref:
        # cluster/block/ClusterBlocks + IndexMetaData block settings)
        blocks_idx: dict = {}
        _block_ids = {"read_only": "5", "read": "7", "write": "8",
                      "metadata": "9"}
        for name, svc in self.indices.items():
            entry = {}
            for kind, bid in _block_ids.items():
                if svc.settings.get_bool(f"index.blocks.{kind}", False):
                    entry[bid] = {
                        "description": f"index {kind} (api)",
                        "retryable": False,
                        "levels": ["write"] if kind != "read"
                        else ["read"]}
            if entry:
                blocks_idx[name] = entry
        full = {
            "cluster_name": self.cluster_name,
            "version": 1,
            "master_node": self.name,
            "blocks": ({"indices": blocks_idx} if blocks_idx else {}),
            "nodes": {self.name: {"name": self.name}},
            "routing_table": {"indices": {
                name: {"shards": {}} for name in names}},
            "routing_nodes": {"unassigned": [], "nodes": {self.name: []}},
            "metadata": {"indices": {
                name: {"state": ("close" if name in self._closed
                                 else "open"),
                       "settings": {"index": {
                           "number_of_shards": svc.num_shards,
                           "number_of_replicas": svc.num_replicas}},
                       "mappings": {"_doc": svc.mappers.mapping_dict()},
                       "aliases": [a for a, t in self._aliases.items()
                                   if name in t]}
                for name, svc in self.indices.items() if name in names}},
        }
        if metrics in (None, "_all"):
            return full
        keep = {m.strip() for m in metrics.split(",")}
        out = {"cluster_name": full["cluster_name"]}
        for key in ("version", "master_node", "blocks", "nodes",
                    "routing_table", "routing_nodes", "metadata"):
            if key in keep:
                out[key] = full[key]
        return out

    def cat_shards(self, index: str | None = None) -> list[dict]:
        """One row per shard COPY: primaries STARTED on this node,
        replicas UNASSIGNED (single-node cluster has nowhere to place
        them) — ref: RestShardsAction row shape."""
        out = []
        wanted = ({s.name for s in self._resolve(index)}
                  if index is not None else None)
        for name, svc in sorted(self.indices.items()):
            if wanted is not None and name not in wanted:
                continue
            for sid, eng in svc.shards.items():
                size = eng.segment_stats()["memory_in_bytes"]
                out.append({"index": name, "shard": sid, "prirep": "p",
                            "state": "STARTED", "docs": eng.doc_count(),
                            "store": size, "ip": "127.0.0.1",
                            "node": self.name})
                shadow = svc.settings.get_bool(
                    "index.shadow_replicas", False)
                for _r in range(svc.num_replicas):
                    out.append({"index": name, "shard": sid,
                                "prirep": "s" if shadow else "r",
                                "state": "UNASSIGNED"})
        return out

    def cat_count(self, index: str | None = None) -> list[dict]:
        import datetime
        now = datetime.datetime.now(datetime.timezone.utc)
        total = sum(svc.doc_count() for svc in self._resolve(index))
        return [{"epoch": int(now.timestamp()),
                 "timestamp": now.strftime("%H:%M:%S"), "count": total}]

    # -- persistence of index metadata (gateway analog) --------------------
    def _persist_index_meta(self, svc: IndexService, settings: dict) -> None:
        meta = {"settings": settings,
                "mappings": svc.mappers.mapping_dict(),
                "types": {t: svc.mappers.type_mapping_dict(t)
                          for t in svc.mapping_types},
                "warmers": dict(getattr(svc, "warmers", {}))}
        path = os.path.join(self.data_path, svc.name, "_meta.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, path)

    def _load_existing_indices(self) -> None:
        for name in sorted(os.listdir(self.data_path)):
            meta_path = os.path.join(self.data_path, name, "_meta.json")
            if os.path.exists(meta_path):
                with open(meta_path) as f:
                    meta = json.load(f)
                svc = IndexService(name, self.settings.merged_with(
                    meta.get("settings") or {}), meta.get("mappings"),
                    data_path=self.data_path,
                    type_mappings=meta.get("types") or None)
                svc.mapping_types = set(meta.get("types") or ())
                if meta.get("warmers"):
                    svc.warmers = dict(meta["warmers"])
                self.indices[name] = svc

    # -- query-driven writes (ref: action/deletebyquery/ in 2.0;
    # update-by-query landed upstream later but completes the surface) ---
    _QUERY_WRITE_PAGE = 1000

    def delete_by_query(self, index: str | None, body: dict | None) -> dict:
        """Per-ENGINE sweep (matches the reference's per-shard
        TransportDeleteByQueryAction): deleting through the owning engine
        sidesteps doc-id re-routing (custom-routed docs delete correctly)
        and gives a natural progress guarantee per shard."""
        query = (body or {}).get("query") or {"match_all": {}}
        deleted = 0
        failures: list[dict] = []
        for svc in self._resolve(index):
            for eng in svc.shards.values():
                while True:
                    reader = eng.acquire_searcher()
                    r = reader.search({"query": query,
                                       "size": self._QUERY_WRITE_PAGE,
                                       "_source": False})
                    ids = [h["_id"] for h in r["hits"]["hits"]]
                    if not ids:
                        break
                    progress = False
                    for did in ids:
                        try:
                            res = eng.delete(did)
                            if res.get("found", True):
                                deleted += 1
                                progress = True
                        except ElasticsearchTpuError as e:
                            failures.append({"index": svc.name, "id": did,
                                             "cause": str(e)})
                    eng.refresh()
                    if not progress:
                        break
        return {"deleted": deleted, "failures": failures,
                "_indices": {"_all": {"deleted": deleted}}}

    def update_by_query(self, index: str | None, body: dict | None) -> dict:
        """Per-engine script update sweep; a seen-set per engine prevents
        both re-updating and window starvation across shards."""
        body = body or {}
        query = body.get("query") or {"match_all": {}}
        script = body.get("script")
        updated = 0
        failures: list[dict] = []
        for svc in self._resolve(index):
            for eng in svc.shards.values():
                seen: set[str] = set()
                while True:
                    reader = eng.acquire_searcher()
                    r = reader.search({"query": query,
                                       "size": self._QUERY_WRITE_PAGE,
                                       "_source": True})
                    fresh = [h for h in r["hits"]["hits"]
                             if h["_id"] not in seen]
                    if not fresh:
                        break
                    for h in fresh:
                        seen.add(h["_id"])
                        try:
                            src = h.get("_source") or {}
                            if script is not None:
                                src = self._run_update_script(script, src)
                            if src is None:
                                continue           # ctx.op = none
                            if src == "__delete__":
                                eng.delete(h["_id"])
                                continue
                            eng.index(h["_id"], src)
                            updated += 1
                        except ElasticsearchTpuError as e:
                            failures.append({"index": svc.name,
                                             "id": h["_id"],
                                             "cause": str(e)})
                    eng.refresh()
        return {"updated": updated, "failures": failures}

    # -- TTL sweep (ref: indices/ttl/IndicesTTLService.java) ---------------
    def purge_expired(self) -> int:
        """Delete docs whose _ttl_expiry has passed. Returns count."""
        now = int(time.time() * 1000)
        total = 0
        for name, svc in list(self.indices.items()):
            if svc.mappers.field("_ttl_expiry") is None:
                continue
            r = self.delete_by_query(name, {"query": {
                "range": {"_ttl_expiry": {"lte": now}}}})
            total += r["deleted"]
        return total

    # -- warmers (ref: indices/IndicesWarmer.java + search/warmer/ —
    # registered searches run after refresh; here they additionally
    # pre-compile the XLA programs the real traffic will hit) -------------
    @staticmethod
    def _warmer_pats(name: str | None) -> list[str] | None:
        if name in (None, ""):
            return None
        return ["*" if p.strip() == "_all" else p.strip()
                for p in str(name).split(",")]

    def _persist_svc_meta(self, svc) -> None:
        if self.data_path:
            self._persist_index_meta(svc, {
                k: v for k, v in svc.settings.as_dict().items()
                if k.startswith("index.")})

    def put_warmer(self, index: str | None, name: str,
                   body: dict | None) -> dict:
        src = body or {"query": {"match_all": {}}}
        for svc in self._resolve(index, metadata_op=True):
            if not hasattr(svc, "warmers"):
                svc.warmers = {}
            svc.warmers[name] = src
            self._persist_svc_meta(svc)
        return {"acknowledged": True}

    def get_warmers(self, index: str | None = None,
                    name: str | None = None) -> dict:
        """Response shape {index: {warmers: {name: {types, source}}}};
        with a name filter, indices with no match are omitted entirely
        (ref: RestGetWarmerAction + GetWarmersResponse rendering)."""
        import fnmatch
        pats = self._warmer_pats(name)
        out: dict = {}
        for svc in self._resolve(index):
            warmers = {
                n: {"types": [], "source": b}
                for n, b in sorted(getattr(svc, "warmers", {}).items())
                if pats is None
                or any(fnmatch.fnmatch(n, p) for p in pats)}
            if pats is None or warmers:
                out[svc.name] = {"warmers": warmers}
        return out

    def delete_warmer(self, index: str, name: str | None = None) -> dict:
        import fnmatch
        from .utils.errors import WarmerMissingError
        pats = self._warmer_pats(name) or ["*"]
        found = False
        for svc in self._resolve(index, metadata_op=True):
            warmers = getattr(svc, "warmers", {})
            changed = False
            for n in [n for n in warmers
                      if any(fnmatch.fnmatch(n, p) for p in pats)]:
                warmers.pop(n)
                found = changed = True
            if changed:
                self._persist_svc_meta(svc)
        if not found:
            # ref: IndexWarmerMissingException -> 404
            raise WarmerMissingError(name if name is not None else "_all")
        return {"acknowledged": True}

    def _run_warmers(self, svc) -> None:
        for wbody in getattr(svc, "warmers", {}).values():
            try:
                self.search(svc.name, dict(wbody))
            except ElasticsearchTpuError:
                pass  # a broken warmer must not fail the refresh

    # -- cache clear (ref: indices/cache/ + RestClearIndicesCacheAction) ---
    def clear_cache(self, index: str | None = None) -> dict:
        n = 0
        for svc in self._resolve(index):
            svc.request_cache.clear()
            for eng in svc.shards.values():
                if eng.failed is not None:
                    continue  # contained shard: nothing resident
                reader = eng.acquire_searcher()
                reader._global_ords.clear()
                for seg in reader.segments:
                    # drop HBM-resident columns + cached live uploads +
                    # pinned resident executables (Segment.drop_device)
                    seg.drop_device()
                n += 1
        return {"_shards": {"total": n, "successful": n, "failed": 0}}

    def recovery_status(self, index: str | None = None) -> dict:
        """Ref: action/admin/indices/recovery/ — per-shard recovery info
        (single-node: every shard recovered from local store/translog)."""
        out = {}
        for svc in self._resolve(index):
            shards = []
            for sid, eng in svc.shards.items():
                if eng.failed is not None:
                    # contained shard: the failure reason and the
                    # on-disk corruption marker are the recovery story
                    # (ref: a corruption-marked store refusing to open)
                    shards.append({
                        "id": sid,
                        "type": "GATEWAY", "stage": "FAILED",
                        "primary": True,
                        "failure": {
                            "reason": eng.failed["reason"],
                            "during": eng.failed["during"],
                            "corruption_marker": eng.failed["marker"],
                        },
                    })
                    continue
                size = eng.segment_stats()["memory_in_bytes"]
                shards.append({
                    "id": sid,
                    # a locally-restored primary is a GATEWAY recovery
                    # in 2.0 terms (RecoveryState.Type.GATEWAY)
                    "type": "GATEWAY", "stage": "DONE",
                    "primary": True,
                    "source": {"name": self.name, "ip": "127.0.0.1",
                               "host": "127.0.0.1"},
                    "target": {"name": self.name, "ip": "127.0.0.1",
                               "host": "127.0.0.1"},
                    "index": {
                        "size": {"total_in_bytes": size,
                                 "reused_in_bytes": size,
                                 "recovered_in_bytes": 0,
                                 "percent": "100.0%"},
                        "files": {"total": len(eng.segments),
                                  "reused": len(eng.segments),
                                  "recovered": 0,
                                  "percent": "100.0%"},
                        "source_throttle_time_in_millis": 0,
                        "target_throttle_time_in_millis": 0,
                        "total_time_in_millis": 0},
                    "translog": {"recovered": 0, "total": -1,
                                 "total_on_start": 0,
                                 "total_time_in_millis": 0},
                    "start": {"check_index_time_in_millis": 0,
                              "total_time_in_millis": 0},
                })
            out[svc.name] = {"shards": shards}
        return out

    def verify_integrity(self, index: str | None = None) -> dict:
        """Per-shard store audit (the `index.shard.check_on_startup`
        pass, callable on demand): commit readability, per-segment
        checksums, corruption markers, live translog tail sanity.
        Pure reads — serving state is untouched. The kill -9 soak's
        post-restart gate: `clean` must hold after ANY crash."""
        out: dict = {"clean": True, "indices": {}}
        for svc in self._resolve(index):
            shards = {}
            for sid, eng in svc.shards.items():
                if eng.store is None:
                    continue
                rep = eng.store.verify_integrity()
                if eng.failed is not None:
                    rep["failed"] = dict(eng.failed)
                    rep["clean"] = False
                shards[str(sid)] = rep
                out["clean"] &= rep["clean"]
            if shards:
                out["indices"][svc.name] = {"shards": shards}
        return out

    # -- monitoring (ref: monitor/MonitorService.java, _nodes APIs) --------
    def nodes_info(self) -> dict:
        import platform
        return {"cluster_name": self.cluster_name, "nodes": {self.name: {
            "name": self.name,
            "version": "0.1.0",
            "build_flavor": "tpu-native",
            "roles": ["master", "data", "ingest"],
            "os": {"name": platform.system(),
                   "arch": platform.machine(),
                   "available_processors": os.cpu_count() or 1},
            "process": {"id": os.getpid()},
            "plugins": self.plugins.info(),
            "thread_pool": {n: {"threads": p.size,
                                "queue_size": p.queue_size}
                            for n, p in self.thread_pool.pools.items()},
            "transport": {"profiles": {},
                          "bound_address": ["local"],
                          "publish_address": "local"},
            "http": {"bound_address": ["127.0.0.1:9200"],
                     "publish_address": "127.0.0.1:9200"},
            "settings": self.settings.as_dict(),
        }}}

    def nodes_stats(self) -> dict:
        from .utils import monitor
        from .search.executor import fused_scoring_stats
        from .index import devbuild
        return {"cluster_name": self.cluster_name, "nodes": {self.name: {
            "name": self.name,
            # per-index stats + the process-wide durability counter
            # block (index/durability.py): salvage/containment events
            # a chaos run asserts on — and a clean recovery asserts
            # are ZERO (the "durability" key shadows a same-named
            # index here; accepted, the stats API still serves it).
            # "indexing", likewise node-wide: writes, and how many of
            # them reached their shard in a batch of two or more
            "indices": {**{name: svc.stats()
                           for name, svc in self.indices.items()},
                        "durability": _durability_snapshot(),
                        "indexing": {
                            key: sum(getattr(svc.op_stats, key)
                                     for svc in self.indices.values())
                            for key in ("index_total", "bulk_batches",
                                        "bulk_batch_docs")}},
            "os": monitor.os_stats(),
            "process": monitor.process_stats(),
            "jvm": monitor.runtime_stats(),   # python runtime, jvm-shaped
            "fs": monitor.fs_stats([self.data_path] if self.data_path
                                   else []),
            "accelerator": monitor.device_stats(),
            "thread_pool": self.thread_pool.stats(),
            "breakers": _breaker_stats(),
            # fused score+top-k autotuner choices + block-prune counters
            # (process-wide: the executor serves every index on the node)
            "fused_scoring": fused_scoring_stats(),
            # dispatch scheduler: cross-request coalescing + pipelining
            # counters (search/dispatch.py)
            "dispatch": self._dispatch.stats.snapshot(),
            # deterministic fault injection (utils/faults.py): active
            # rules + per-rule firing counts, so chaos runs are auditable
            "fault_injection": _fault_snapshot(),
            # device-parallel pack builder (index/devbuild.py):
            # device/fallback/skip counters + derived ingest docs/sec
            # (process-wide — the builder serves every index on the node)
            "indexing": {"device_build": devbuild.stats()},
            "metrics": self.metrics.snapshot(),
        }}}

    # ref: action/admin/indices/stats/ (CommonStats sections, metric
    # selection in RestIndicesStatsAction, level in IndicesStatsResponse)
    _STATS_METRIC_MAP = {
        "docs": "docs", "store": "store", "indexing": "indexing",
        "get": "get", "search": "search", "merge": "merges",
        "refresh": "refresh", "flush": "flush", "warmer": "warmer",
        "filter_cache": "filter_cache", "id_cache": "id_cache",
        "fielddata": "fielddata", "percolate": "percolate",
        "completion": "completion", "segments": "segments",
        "translog": "translog", "suggest": "suggest",
        "recovery": "recovery", "query_cache": "query_cache",
    }

    def indices_stats(self, index: str | None = None,
                      metric: str | None = None,
                      level: str = "indices",
                      types: list[str] | None = None,
                      groups: list[str] | None = None,
                      fields: list[str] | None = None,
                      fielddata_fields: list[str] | None = None,
                      completion_fields: list[str] | None = None) -> dict:
        import fnmatch
        from .index.stats import merge_type_counters, merge_group_counters
        svcs = self._resolve(None if index in ("_all", "*") else index)

        def _match(name: str, pats: list[str]) -> bool:
            return any(fnmatch.fnmatch(name, p) for p in pats)

        def _field_sizes(svc_list) -> tuple[dict, dict]:
            """Per-field fielddata + completion sizes. Columns are loaded
            at segment birth here (columnar-at-refresh design), so every
            mapped column reports its resident bytes — the analog of
            fielddata memory (ref: FieldDataStats / CompletionStats)."""
            fd: dict[str, int] = {}
            comp: dict[str, int] = {}
            for svc in svc_list:
                for eng in svc.shards.values():
                    for seg in eng.segments:
                        cols = [*seg.keywords.values(),
                                *seg.numerics.values(),
                                *seg.vectors.values(),
                                *seg.geos.values()]
                        for col in cols:
                            fd[col.name] = fd.get(col.name, 0) + col.nbytes()
                        for pf in seg.text.values():
                            fd[pf.name] = fd.get(pf.name, 0) + pf.nbytes()
                        for cc in seg.completions.values():
                            comp[cc.name] = (comp.get(cc.name, 0)
                                             + cc.nbytes())
            return fd, comp

        def build(svc_list) -> dict:
            seg = [e.segment_stats() for svc in svc_list
                   for e in svc.shards.values()]
            ops = [svc.op_stats for svc in svc_list]
            fd_sizes, comp_sizes = _field_sizes(svc_list)
            tl_ops = tl_bytes = 0
            for svc in svc_list:
                for eng in svc.shards.values():
                    if eng.translog is not None:
                        # properties, not methods — calling them was a
                        # TypeError on every path-backed _stats call
                        tl_ops += eng.translog.num_ops
                        tl_bytes += eng.translog.size_in_bytes
            # pack-build wall time + docs (refresh rebuilds and
            # compaction folds) so indexing throughput is observable
            build_ms = sum(o.build_time_ms for o in ops)
            build_docs = sum(o.build_docs for o in ops)
            full: dict = {
                "docs": {"count": sum(s.doc_count() for s in svc_list),
                         "deleted": 0},
                "store": {"size_in_bytes":
                          sum(s["memory_in_bytes"] for s in seg),
                          "throttle_time_in_millis": 0},
                "indexing": {
                    "index_total": sum(o.index_total for o in ops),
                    "index_time_in_millis":
                        sum(o.index_time_ms for o in ops),
                    "index_current": 0,
                    "delete_total": sum(o.delete_total for o in ops),
                    "delete_time_in_millis":
                        sum(o.delete_time_ms for o in ops),
                    "delete_current": 0,
                    "noop_update_total":
                        sum(o.noop_update_total for o in ops),
                    "bulk_batches": sum(o.bulk_batches for o in ops),
                    "bulk_batch_docs":
                        sum(o.bulk_batch_docs for o in ops),
                    "build_total": sum(o.build_total for o in ops),
                    "build_time_in_millis": build_ms,
                    "build_docs": build_docs,
                    "build_docs_per_s":
                        (build_docs / (build_ms / 1000.0)
                         if build_ms > 0 else 0.0),
                    "device_build_total":
                        sum(o.build_device_total for o in ops),
                    "is_throttled": False,
                    "throttle_time_in_millis": 0},
                "get": {"total": sum(o.get_total for o in ops),
                        "time_in_millis": sum(o.get_time_ms for o in ops),
                        "exists_total": sum(o.get_exists for o in ops),
                        "exists_time_in_millis": 0,
                        "missing_total": sum(o.get_missing for o in ops),
                        "missing_time_in_millis": 0, "current": 0},
                "search": {"open_contexts": len(self._scrolls),
                           "query_total": sum(o.query_total for o in ops),
                           "query_time_in_millis":
                               sum(o.query_time_ms for o in ops),
                           "query_current": 0,
                           "fetch_total": sum(o.fetch_total for o in ops),
                           "fetch_time_in_millis":
                               int(sum(o.fetch_time_ms for o in ops)),
                           "fetch_current": 0},
                "merges": {"current": 0, "current_docs": 0,
                           "current_size_in_bytes": 0,
                           "total": sum(o.merge_total for o in ops),
                           "total_time_in_millis":
                               sum(o.merge_time_ms for o in ops),
                           "total_docs": 0, "total_size_in_bytes": 0},
                "refresh": {"total": sum(o.refresh_total for o in ops),
                            "total_time_in_millis":
                                sum(o.refresh_time_ms for o in ops)},
                "flush": {"total": sum(o.flush_total for o in ops),
                          "total_time_in_millis":
                              sum(o.flush_time_ms for o in ops)},
                "warmer": {"current": 0,
                           "total": sum(o.warmer_total for o in ops),
                           "total_time_in_millis":
                               sum(o.warmer_time_ms for o in ops)},
                "filter_cache": {"memory_size_in_bytes": 0, "evictions": 0},
                "query_cache": {
                    "memory_size_in_bytes":
                        sum(s.request_cache.memory_size_in_bytes()
                            for s in svc_list),
                    "evictions": sum(s.request_cache.evictions
                                     for s in svc_list),
                    "hit_count": sum(s.request_cache.hit_count
                                     for s in svc_list),
                    "miss_count": sum(s.request_cache.miss_count
                                      for s in svc_list)},
                "id_cache": {"memory_size_in_bytes": 0},
                "fielddata": {"memory_size_in_bytes":
                              sum(fd_sizes.values()),
                              "evictions": 0},
                "percolate": {"total":
                              sum(o.percolate_total for o in ops),
                              "time_in_millis":
                              sum(o.percolate_time_ms for o in ops),
                              "current": 0, "memory_size_in_bytes": -1,
                              "memory_size": "-1b",
                              "queries": sum(svc.percolator.count()
                                             for svc in svc_list)},
                "completion": {"size_in_bytes":
                               sum(comp_sizes.values())},
                "segments": {"count": sum(s["count"] for s in seg),
                             "memory_in_bytes":
                             sum(s["memory_in_bytes"] for s in seg),
                             "index_writer_memory_in_bytes": 0,
                             "version_map_memory_in_bytes": 0,
                             "fixed_bit_set_memory_in_bytes": 0},
                "translog": {"operations": tl_ops,
                             "size_in_bytes": tl_bytes},
                "suggest": {"total": sum(o.suggest_total for o in ops),
                            "time_in_millis":
                                sum(o.suggest_time_ms for o in ops),
                            "current": 0},
                "recovery": {"current_as_source": 0,
                             "current_as_target": 0,
                             "throttle_time_in_millis": 0},
            }
            # per-field sections, selected by fields/…_fields patterns
            # (ref: CommonStatsFlags fieldDataFields/completionDataFields)
            fd_pats = list(fielddata_fields or []) + list(fields or [])
            if fd_pats:
                sel = {f: {"memory_size_in_bytes": sz}
                       for f, sz in fd_sizes.items() if _match(f, fd_pats)}
                if sel:
                    full["fielddata"]["fields"] = sel
            comp_pats = list(completion_fields or []) + list(fields or [])
            if comp_pats:
                sel = {f: {"size_in_bytes": sz}
                       for f, sz in comp_sizes.items()
                       if _match(f, comp_pats)}
                if sel:
                    full["completion"]["fields"] = sel
            if types:
                matched_types = {
                    t: row for t, row in merge_type_counters(
                        [o.types for o in ops]).items()
                    if _match(t, types)}
                if matched_types:
                    full["indexing"]["types"] = matched_types
            if groups:
                matched = {g: row for g, row in merge_group_counters(
                    [o.groups for o in ops]).items()
                    if _match(g, groups)}
                if matched:
                    full["search"]["groups"] = matched
            if metric in (None, "_all"):
                return full
            keep = {self._STATS_METRIC_MAP.get(m.strip())
                    for m in str(metric).split(",")}
            return {k: v for k, v in full.items() if k in keep}

        total = sum(s.num_shards * (1 + s.num_replicas) for s in svcs)
        ok = sum(s.num_shards for s in svcs)
        all_stats = build(svcs)
        out: dict = {
            "_shards": {"total": total, "successful": ok, "failed": 0},
            "_all": {"primaries": all_stats, "total": all_stats},
        }
        if level in ("indices", "shards"):
            out["indices"] = {}
            for svc in svcs:
                st = build([svc])
                entry = {"primaries": st, "total": st}
                if level == "shards":
                    entry["shards"] = {
                        str(sid): [build([svc])]
                        for sid in svc.shards}
                out["indices"][svc.name] = entry
        return out

    def hot_threads(self, threads: int = 3, interval_ms: int = 500) -> str:
        from .utils import monitor
        return (f"::: [{self.name}]\n"
                + monitor.hot_threads(threads, interval_ms))

    # -- term vectors (ref: action/termvectors/) ---------------------------
    def term_vectors(self, index: str, doc_id: str,
                     body: dict | None = None,
                     fields: list[str] | None = None) -> dict:
        from .search.termvectors import term_vectors as tv
        body = body or {}
        fields = fields or body.get("fields")
        svc = self._index(index)
        out = {"_index": svc.name, "_type": "_doc", "_id": doc_id,
               "found": False}
        for attempt in (0, 1):
            for eng in svc.shards.values():
                reader = eng.acquire_searcher()
                result = tv(reader.segments, reader.live, doc_id,
                            fields=fields,
                            term_statistics=bool(
                                body.get("term_statistics", False)),
                            field_statistics=bool(
                                body.get("field_statistics", True)),
                            positions=bool(body.get("positions", True)),
                            offsets=bool(body.get("offsets", True)),
                            analyzer_for=(
                                lambda f: svc.mappers.analysis.analyzer(
                                    getattr(svc.mappers.field(f),
                                            "analyzer", "standard")
                                    if svc.mappers.field(f) is not None
                                    else "standard")))
                if result is not None:
                    out["found"] = True
                    out["term_vectors"] = result
                    return out
            # realtime semantics: un-refreshed docs become visible after a
            # refresh (ref: ShardTermVectorsService realtime get)
            if attempt == 0 and body.get("realtime", True) is not False:
                try:
                    if svc.get_doc(doc_id).get("found"):
                        svc.refresh()
                        continue
                except ElasticsearchTpuError:
                    pass
            break
        return out

    def mtermvectors(self, index: str | None, body: dict | None) -> dict:
        docs = (body or {}).get("docs") or []
        out = []
        for spec in docs:
            idx = spec.get("_index") or index
            did = spec.get("_id")
            try:
                out.append(self.term_vectors(idx, did, spec,
                                             spec.get("fields")))
            except ElasticsearchTpuError as e:
                out.append({"_index": idx, "_id": did, "error": str(e)})
        return {"docs": out}

    # -- search templates (ref: RestSearchTemplateAction + the Mustache
    # script engine) -------------------------------------------------------
    def search_template(self, index: str | None, body: dict | None) -> dict:
        rendered = self.render_template(body)["template_output"]
        return self.search(index, rendered)

    def render_template(self, body: dict | None) -> dict:
        from .search.templates import render_template
        body = body or {}
        template = body.get("inline") or body.get("template")
        tid = body.get("id")
        # {"template": {"id": "1"}} indirection (ref:
        # TemplateQueryParser stored-template reference)
        if isinstance(template, dict) and template.get("id") \
                and set(template) <= {"id", "params"}:
            tid = template["id"]
            template = None
        if isinstance(template, str) and not template.lstrip(
                ).startswith("{"):
            # a bare name is a disk/indexed script reference (ref:
            # ScriptService file-script lookup error)
            tid, template = template, None
        if template is None and tid is not None:
            from .script import ScriptService
            stored = ScriptService.instance().stored
            template = stored.get(f"__template__{tid}",
                                  stored.get(str(tid)))
            if template is None:
                raise IllegalArgumentError(
                    f"Unable to find on disk script {tid}")
        if template is None:
            raise IllegalArgumentError(
                "search template requires [inline], [template] or [id]")
        return {"template_output": render_template(template,
                                                   body.get("params") or {})}

    def close(self) -> None:
        self._ttl_stop.set()
        if getattr(self, "_process_stats", None) is not None:
            # reset the process-wide failover/eviction counters this
            # node installed — unless a later node installed its own,
            # in which case theirs stands (fault-registry convention)
            from .search import dispatch as _dispatch_mod
            _dispatch_mod.reset_process_stats(
                if_owner=self._process_stats)
            self._process_stats = None
        if getattr(self, "_durability_stats", None) is not None:
            from .index import durability as _durability_mod
            _durability_mod.reset_process_stats(
                if_owner=self._durability_stats)
            self._durability_stats = None
        if getattr(self, "_eviction_cfg", None) is not None:
            # restore eviction defaults only while the installed config
            # is still this node's (a later node's settings stand)
            from .parallel import repack as _repack
            _repack.reset_config(if_current=self._eviction_cfg)
            self._eviction_cfg = None
        if getattr(self, "_tiering_cfg", None) is not None:
            # tiered-residency config + paged tiles: reset only while
            # the installed config is still THIS node's (a later
            # node's settings — and its paged tiles — stand)
            from .index import tiering as _tiering
            _tiering.reset(if_current=self._tiering_cfg)
            self._tiering_cfg = None
        if getattr(self, "_ann_cfg", None) is not None:
            # IVF config: reset only while the installed config is
            # still THIS node's (a later node's settings stand)
            from .index import ann as _ann
            _ann.reset(if_current=self._ann_cfg)
            self._ann_cfg = None
        if getattr(self, "_fault_registry", None) is not None:
            # tear down the fault registry this node installed — unless
            # someone re-configured since, in which case theirs stands
            from .utils import faults
            if faults.active() is self._fault_registry:
                faults.clear()
        self.resource_watcher.close()
        w = getattr(self, "_script_watcher", None)
        if w is not None:
            self.resource_watcher.remove(w)
            self._script_watcher = None
        # persist mappings learned dynamically, then close engines
        for svc in self.indices.values():
            if self.data_path:
                self._persist_index_meta(svc, {
                    "index.number_of_shards": svc.num_shards})
            svc.close()
        self.thread_pool.shutdown()
        if getattr(self, "_autotune_store", None):
            # stop writing autotuner choices into this node's data dir
            # once the node (and its lock) are gone — but only if THIS
            # node owns the process-global store
            from .search.executor import configure_autotune_persistence
            configure_autotune_persistence(None,
                                           if_owner=self._autotune_store)
        if self._node_lock_fh is not None:
            import fcntl
            try:
                fcntl.flock(self._node_lock_fh, fcntl.LOCK_UN)
            finally:
                self._node_lock_fh.close()
                self._node_lock_fh = None


def _breaker_stats() -> dict:
    """Node-stats breakers section (ref: CircuitBreakerStats). The
    fielddata entry additionally splits its estimate into the tiered-
    residency components: permanently-resident tile summaries (part of
    the ordinary column upload hold) vs paged tile bytes (per-tile LRU
    holds, index/tiering.py)."""
    from .utils.breaker import breaker_service
    out = breaker_service().stats()
    from .index import tiering as _tiering
    fd = out.get("fielddata")
    if fd is not None:
        fd["tiering"] = _tiering.breaker_split()
    return out


def _fault_snapshot() -> dict:
    from .utils import faults
    return faults.snapshot()


def _durability_snapshot() -> dict:
    from .index import durability
    return durability.snapshot()


def _legacy_error_string(e: ElasticsearchTpuError) -> str:
    """ES 2.0 wire format for embedded error strings:
    `IndexMissingException[[idx] missing]` (ref: ElasticsearchException
    toString rendering used in multi-item responses)."""
    if isinstance(e, IndexNotFoundError):
        return f"IndexMissingException[[{e.index}] missing]"
    return f"{type(e).__name__}[{e}]"


def _deep_merge(dst: dict, src: dict) -> None:
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _deep_merge(dst[k], v)
        else:
            dst[k] = v
