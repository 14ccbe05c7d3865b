"""Typed error hierarchy.

Reference analog: org.elasticsearch.ElasticsearchException and subclasses
(e.g. index/engine/VersionConflictEngineException.java,
indices/IndexMissingException.java). Each error carries an HTTP status so
the REST layer can render it the way rest/BytesRestResponse.java does.
"""

from __future__ import annotations


class ElasticsearchTpuError(Exception):
    """Base error. `status` is the HTTP status the REST layer returns."""

    status = 500

    def __init__(self, message: str = "", **kwargs):
        super().__init__(message)
        self.message = message
        self.info = kwargs

    def to_dict(self) -> dict:
        return {
            "type": type(self).__name__,
            "reason": self.message,
            **{k: v for k, v in self.info.items() if v is not None},
        }


class IllegalArgumentError(ElasticsearchTpuError):
    status = 400


class IndexNotFoundError(ElasticsearchTpuError):
    """Ref: indices/IndexMissingException.java (404)."""

    status = 404

    def __init__(self, index: str):
        super().__init__(f"no such index [{index}]", index=index)
        self.index = index


class IndexClosedError(ElasticsearchTpuError):
    """Ref: indices/IndexClosedException.java (403 FORBIDDEN)."""

    status = 403

    def __init__(self, index: str):
        super().__init__(f"closed", index=index)
        self.index = index


class AliasesMissingError(ElasticsearchTpuError):
    """Ref: rest/action/admin/indices/alias/delete/
    AliasesMissingException (404)."""

    status = 404

    def __init__(self, names):
        super().__init__(f"aliases {list(names)} missing")


class TypeMissingError(ElasticsearchTpuError):
    """Ref: indices/TypeMissingException.java (404)."""

    status = 404

    def __init__(self, type_name: str):
        super().__init__(f"type [{type_name}] missing")


class WarmerMissingError(ElasticsearchTpuError):
    """Ref: search/warmer/IndexWarmerMissingException.java (404)."""

    status = 404

    def __init__(self, name: str):
        super().__init__(f"index_warmer [{name}] missing")


class IndexAlreadyExistsError(ElasticsearchTpuError):
    """Ref: indices/IndexAlreadyExistsException.java (400)."""

    status = 400

    def __init__(self, index: str):
        super().__init__(f"index [{index}] already exists", index=index)
        self.index = index


class RoutingMissingError(ElasticsearchTpuError):
    """Ref: action/RoutingMissingException.java (400): a doc op on a
    parent-mapped (or routing-required) type without routing/parent."""

    status = 400

    def __init__(self, index: str, doc_id: str):
        super().__init__(
            f"routing is required for [{index}]/[{doc_id}]",
            index=index, id=doc_id)


class ShardNotFoundError(ElasticsearchTpuError):
    status = 404

    def __init__(self, index: str, shard: int):
        super().__init__(f"no such shard [{index}][{shard}]", index=index, shard=shard)


class DocumentMissingError(ElasticsearchTpuError):
    """Ref: index/engine/DocumentMissingException.java (404)."""

    status = 404

    def __init__(self, index: str, doc_id: str):
        super().__init__(f"document [{doc_id}] missing", index=index, id=doc_id)


class VersionConflictError(ElasticsearchTpuError):
    """Optimistic-concurrency failure.

    Ref: index/engine/VersionConflictEngineException.java; raised by the
    version check in index/engine/InternalEngine.java:253-274.
    """

    status = 409

    def __init__(self, index: str, doc_id: str, current: int, provided: int):
        super().__init__(
            f"version conflict for [{doc_id}]: current [{current}], provided [{provided}]",
            index=index,
            id=doc_id,
            current_version=current,
            provided_version=provided,
        )
        self.current_version = current


class MapperParsingError(ElasticsearchTpuError):
    """Ref: index/mapper/MapperParsingException.java (400)."""

    status = 400


class QueryParsingError(ElasticsearchTpuError):
    """Ref: index/query/QueryParsingException.java (400)."""

    status = 400


class SearchParseError(ElasticsearchTpuError):
    """Ref: search/SearchParseException.java (400)."""

    status = 400


class ScriptException(ElasticsearchTpuError):
    """Script compile/runtime failure.

    Ref: the GeneralScriptException / expression-compile errors thrown out
    of script/ScriptService.java compile (400 — bad script in request).
    """

    status = 400


class ScriptMissingError(ElasticsearchTpuError):
    """Stored script not found (404, like a missing doc in `.scripts`)."""

    status = 404

    def __init__(self, script_id: str):
        super().__init__(f"unable to find script [{script_id}]",
                         script_id=script_id)


class CircuitBreakingError(ElasticsearchTpuError):
    """Memory budget exceeded before an allocation would blow HBM/host RAM.

    Ref: common/breaker/CircuitBreakingException.java; thrown by
    common/breaker/MemoryCircuitBreaker.java when the estimate crosses the
    limit.
    """

    status = 429

    def __init__(self, breaker: str, wanted: int, limit: int):
        super().__init__(
            f"[{breaker}] data too large: wanted [{wanted}b] would exceed limit [{limit}b]",
            breaker=breaker,
            bytes_wanted=wanted,
            bytes_limit=limit,
        )


class TrafficRejectedError(ElasticsearchTpuError):
    """Admission-control shed (search/traffic.py): the tenant's rate or
    concurrency quota said no BEFORE the request took a thread-pool
    slot or breaker hold. 429 like the reference's
    EsRejectedExecutionException, but structured: `retry_after_s`
    prices when the token bucket will admit again (the REST layer
    renders it as a Retry-After header)."""

    status = 429

    def __init__(self, tenant: str, reason: str,
                 retry_after_s: float = 1.0):
        # a rate-0 (fully blocked) tenant prices to infinity; clamp so
        # the JSON body and Retry-After header stay finite and valid
        if not (retry_after_s == retry_after_s
                and retry_after_s < float("inf")):
            retry_after_s = 3600.0
        super().__init__(
            f"traffic admission rejected for tenant [{tenant}]: "
            f"{reason}", tenant=tenant,
            retry_after=round(retry_after_s, 3))
        self.tenant = tenant
        self.retry_after_s = retry_after_s


class SearchTimeoutError(ElasticsearchTpuError):
    """A shard missed the search deadline (per-request `timeout` /
    `search.default_search_timeout`).

    Ref: the per-shard QueryPhase timeout that surfaces as
    `timed_out: true` + a failed shard in SearchPhaseController — only
    fatal to the request when partial results are disallowed (504).
    """

    status = 504

    def __init__(self, index: str | None = None, shard: int | None = None,
                 timeout_ms: int | None = None):
        where = (f"[{index}][{shard}]" if index is not None
                 else "search")
        msg = f"{where} exceeded the search deadline"
        if timeout_ms is not None:
            msg += f" of [{timeout_ms}ms]"
        super().__init__(msg, index=index, shard=shard,
                         timeout_ms=timeout_ms)


class HostDownError(ElasticsearchTpuError):
    """A mesh host is evicted (failed its heartbeat/exec contract) and
    its shards cannot be re-sourced from a surviving replica — the
    shard-level entry a degraded multihost response carries in
    `_shards.failures` (parallel/multihost.py).

    Ref: NoShardAvailableActionException rendered per shard when a
    node leaves and no started copy remains (503: retryable — the
    host's rejoin restores coverage)."""

    status = 503

    def __init__(self, host: str, shard: int | None = None):
        where = f"[{shard}]" if shard is not None else ""
        super().__init__(
            f"shard{where} lives on evicted mesh host [{host}]",
            host=host, shard=shard)
        self.host = host


class StaleEpochError(ElasticsearchTpuError):
    """A mesh control-plane message carries a membership epoch that no
    longer matches the receiver's — the seq-fencing guard that keeps a
    rejoined (or slow) host from replaying a turn minted against an
    older mesh shape (parallel/multihost.py). Drivers retry against
    the current epoch; the message itself is never served.

    Ref: the master-fencing term checks zen2 puts on cluster-state
    publishes (Coordinator.publish rejects stale terms with 409)."""

    status = 409

    def __init__(self, msg: str, epoch: int | None = None,
                 current: int | None = None):
        super().__init__(msg, epoch=epoch, current=current)


class LeaseFencedError(ElasticsearchTpuError):
    """An exec turn was minted under a coordinator-lease term the
    receiver (or the current holder) no longer honors — the fencing
    that replaces the single-driver-at-a-time convention: a concurrent
    driver gets a 409-and-retry instead of a seq collision
    (parallel/membership.py / parallel/multihost.py). The driver
    re-acquires (or hands off) the lease and retries; nothing is
    served under the stale term.

    Ref: zen2's master term fencing — a publish under an old term is
    rejected so two masters can never both commit."""

    status = 409
    # class-level defaults: a wire-rebuilt instance (tcp_transport
    # restores the base contract without subclass __init__) still
    # answers .term/.holder
    term: int | None = None
    holder: str | None = None

    def __init__(self, msg: str, term: int | None = None,
                 holder: str | None = None):
        super().__init__(msg, term=term, holder=holder)
        self.term = term
        self.holder = holder


class FaultInjectedError(ElasticsearchTpuError):
    """A deterministic injected fault (utils/faults.py) standing in for
    a real device/shard failure — OOM, preemption, runtime drop."""

    status = 500


class PowerLossError(FaultInjectedError):
    """An injected crash point fired (utils/faults.py `crash_point`):
    the process "died" exactly at a named storage write site, leaving
    whatever partial on-disk state the real crash would have left. A
    test catches this where the OS would have reaped the process —
    NOTHING in the storage stack may catch it (a crashed process does
    not run exception handlers); recovery happens on the next open."""

    status = 500


class ShardFailedError(ElasticsearchTpuError):
    """A shard is in a FAILED (contained) state — typically corruption
    detected during recovery/load (index/store.py corruption marker).
    The NODE stays up: searches over the shard answer with structured
    `_shards.failures` entries, writes answer 503 so clients retry
    against a promoted copy.

    Ref: index/shard/IndexShard failing the shard with
    `corrupted_<uuid>` markers (store corruption handling) while the
    node keeps serving its healthy shards."""

    status = 503

    def __init__(self, index: str, shard: int, reason: str = ""):
        super().__init__(
            f"[{index}][{shard}] shard is failed"
            + (f": {reason}" if reason else ""),
            index=index, shard=shard)
        self.index = index
        self.shard = shard
        self.reason = reason


class ClusterBlockError(ElasticsearchTpuError):
    """An operation hit a cluster-level or index-level block.

    Ref: cluster/block/ClusterBlockException.java (503 when retryable) —
    raised by the action layer's checkGlobalBlock/checkRequestBlock before
    executing (e.g. writes while no master is elected or state is not
    recovered).
    """

    status = 503

    def __init__(self, descriptions):
        super().__init__(f"blocked by: {descriptions}")
