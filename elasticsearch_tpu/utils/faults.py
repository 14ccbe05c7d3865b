"""Deterministic fault injection for the search read path.

Reference analog: the reference exercises its partial-failure semantics
(`_shards.failures`, `timed_out`, replica retry in
TransportSearchTypeAction.onFirstPhaseResult) with MockTransportService
disruptions and ESIntegTestCase's random shard failures. A device-mesh
stack has no wire to cut, so this registry injects the equivalent
failure classes AT the dispatch boundary — the reader/executor seam a
real device error (OOM, preemption, runtime drop) would surface through:

  * ``shard_error``  — dispatch raises FaultInjectedError (a dead shard)
  * ``shard_delay``  — dispatch sleeps (a straggler shard; deadline food)
  * ``breaker_trip`` — a real add_estimate past the named breaker's
    limit, so the CircuitBreakingError AND the trip counter come from
    the production breaker, not a stand-in
  * ``device_dead``  — PERMANENT device death: fires at EVERY phase,
    deterministically (no ``rate=`` decay — a dead chip does not flake
    back to life between dispatches). The injectable the mesh eviction
    threshold (parallel/repack.py) keys on, distinct from transient
    ``shard_error`` which must NOT evict while under-threshold; the
    re-expansion probe consults ``device_dead_matches`` so removing the
    rule is how a "repaired" device comes back

Host-level CONTROL-PLANE kinds (the multihost mesh's failure classes,
hooked at every transport boundary parallel/multihost.py crosses —
ping, clock, exec broadcast, fetch):

  * ``host_dead``   — PERMANENT machine death: every control-plane
    message to OR from the host fails, deterministically (no ``rate=``,
    same reasoning as ``device_dead``). The injectable the host
    eviction threshold keys on; the rejoin probe consults
    ``host_dead_matches`` so removing the rule is how a repaired
    machine comes back
  * ``ctrl_drop``   — a TRANSIENT dropped control-plane message (the
    wire analog of ``shard_error``): the send raises; retry/backoff is
    what recovers it
  * ``ctrl_delay``  — a slow control-plane link: the boundary sleeps
    ``ms=`` before proceeding
  * ``net_partition`` — BIDIRECTIONAL group severing: every
    control-plane message whose two ends straddle the named host set
    (``hosts=a+b``, ``+``-separated) fails, deterministically —
    within-group and outside-group traffic proceeds, so both partition
    halves stay internally live (the split-brain the membership quorum
    must fence). ``heal=`` names hosts subtracted back out of the
    severed set (``heal=all`` disables the rule), and the runtime
    ``heal_partition()`` helper edits the live rule without reseeding —
    partition→heal arcs replay byte-for-byte under one seed. The probe
    helper ``net_partition_matches`` never consumes. Composes with
    ``ctrl_drop``/``ctrl_delay`` rules in the same spec

STORAGE kinds (the durability path's failure classes, hooked at every
``index/store.py`` and ``index/translog.py`` write/read boundary —
the adversary the crash-recovery matrix drives):

  * ``crash_point`` — the process "dies" at a named write site:
    ``site=store`` phases ``seg_npz|seg_meta|commit|cleanup``,
    ``site=translog`` phases ``append|fsync|rotate``. Fires AT MOST
    ONCE per installed registry (a process crashes once), first
    leaving the torn on-disk state the real crash would leave (a
    half-written translog record at ``append``; with
    ``unsynced=drop``, OS-buffered-but-unfsynced translog bytes are
    dropped too — the POWER-LOSS simulation the durability-mode
    guarantee tests need). Then raises ``PowerLossError`` — or, with
    ``kill=1``, SIGKILLs the process (the kill -9 soak's injectable:
    death lands exactly at the write site, no handler runs)
  * ``disk_corrupt`` — post-hoc corruption of the file a READ is
    about to touch (``mode=flip`` one seeded byte, ``mode=truncate``
    the tail quarter), at read phases ``load_npz|load_meta|
    read_commit`` (store) / ``read`` (translog); the read proceeds
    and the production checksum/crc path does the detecting
  * ``io_error``   — the read raises ``OSError(EIO)`` (a dying disk),
    same read phases

Spec grammar (env ``ES_TPU_FAULT_INJECT`` or node setting
``search.fault_injection``; comma-separated rules)::

    shard_error:shard=1:rate=1.0
    shard_delay:ms=200:rate=0.3:seed=7
    breaker_trip:breaker=request:index=logs
    shard_error:shard=1:replica=0          # mesh: fail one replica row
    device_dead:replica=0:site=mesh        # mesh: one row PERMANENTLY dead
    host_dead:host=host-1                  # multihost: machine death
    ctrl_drop:action=exec:rate=0.5:seed=3  # flaky exec broadcast
    ctrl_delay:ms=50:host=host-2:action=fetch
    net_partition:hosts=host-1+host-2        # sever {1,2} from the rest
    net_partition:hosts=host-1+host-2:heal=host-2  # host-2 healed back
    crash_point:site=store:phase=commit    # die mid-flush, commit torn
    crash_point:site=translog:phase=append:rate=0.02:seed=9:kill=1
    crash_point:site=translog:phase=fsync:unsynced=drop  # power loss
    disk_corrupt:site=store:phase=load_npz:mode=flip
    io_error:site=store:phase=load_meta:index=logs:shard=0

Rule selectors ``site`` (reader|mesh), ``index``, ``shard``, ``replica``
restrict where a rule fires; omitted selectors match everything.
``phase`` picks the boundary: ``submit`` (program enqueue — where a
dead shard errors out) or ``collect`` (result sync — where a straggler
burns wall-clock). Defaults: errors/breaker trips fire at submit,
delays at collect, matching how the real failure classes present.
Control-plane kinds take ``host=`` (the REMOTE end of the message —
matching both directions is what makes an injected dead host
unreachable, not merely unresponsive) and ``action=`` (the action
name's trailing segment: ``action=ping`` matches
``internal:mesh/ping`` — the grammar splits rules on ``:``, so the
tail is the addressable form for namespaced actions); they never fire
at data-plane dispatch boundaries and data-plane kinds never fire at
control-plane ones.
``rate`` draws from ONE seeded RNG (``seed=`` on any rule reseeds the
registry), so a given spec+seed yields the same firing sequence every
run — chaos tests stay reproducible without real hardware failures.
"""

from __future__ import annotations

import os
import random
import threading
import time

from .errors import FaultInjectedError, PowerLossError

DISPATCH_KINDS = ("shard_error", "shard_delay", "breaker_trip",
                  "device_dead")
CTRL_KINDS = ("host_dead", "ctrl_drop", "ctrl_delay", "net_partition")
STORAGE_KINDS = ("crash_point", "disk_corrupt", "io_error")
KINDS = DISPATCH_KINDS + CTRL_KINDS + STORAGE_KINDS

# the write sites a crash_point may name and the read sites a
# disk_corrupt/io_error may name, per storage subsystem — validated at
# parse time so a typo'd phase fails the spec instead of silently
# never firing
STORAGE_WRITE_PHASES = {
    "store": ("seg_npz", "seg_meta", "commit", "cleanup"),
    "translog": ("append", "fsync", "rotate"),
}
STORAGE_READ_PHASES = {
    "store": ("load_npz", "load_meta", "read_commit"),
    "translog": ("read",),
}


class FaultRule:
    """One parsed rule: a fault kind plus match selectors."""

    __slots__ = ("kind", "site", "index", "shard", "replica", "phase",
                 "rate", "ms", "breaker", "host", "action", "mode",
                 "kill", "unsynced", "hosts", "heal", "fired")

    def __init__(self, kind: str, site: str | None = None,
                 index: str | None = None, shard: int | None = None,
                 replica: int | None = None, phase: str | None = None,
                 rate: float = 1.0, ms: float = 0.0,
                 breaker: str = "request", host: str | None = None,
                 action: str | None = None, mode: str = "flip",
                 kill: int = 0, unsynced: str | None = None,
                 hosts: frozenset | None = None,
                 heal: frozenset | None = None):
        if kind not in KINDS:
            raise ValueError(f"unknown fault kind [{kind}] "
                             f"(expected one of {KINDS})")
        self.kind = kind
        self.mode = mode
        self.kill = bool(kill)
        self.unsynced = unsynced
        if kind != "net_partition" and (hosts is not None
                                        or heal is not None):
            raise ValueError(
                f"[hosts=]/[heal=] apply only to net_partition, "
                f"not [{kind}] (use host= for single-host selectors)")
        self.hosts = frozenset(hosts) if hosts is not None else None
        self.heal = frozenset(heal) if heal is not None else frozenset()
        if kind not in STORAGE_KINDS:
            if mode != "flip" or kill or unsynced is not None:
                raise ValueError(
                    f"[mode=]/[kill=]/[unsynced=] apply only to storage "
                    f"kinds {STORAGE_KINDS}, not [{kind}]")
        if kind in STORAGE_KINDS:
            # storage rules select on (site, phase, index, shard); a
            # file has no replica/host identity and no dispatch phase
            for sel, val in (("replica", replica), ("host", host),
                             ("action", action)):
                if val is not None:
                    raise ValueError(
                        f"{kind} is a storage fault; [{sel}=] does not "
                        "apply (use site=/phase=/index=/shard=)")
            if site is not None and site not in STORAGE_WRITE_PHASES:
                raise ValueError(
                    f"{kind} site must be one of "
                    f"{tuple(STORAGE_WRITE_PHASES)}, got [{site}]")
            valid = (STORAGE_WRITE_PHASES if kind == "crash_point"
                     else STORAGE_READ_PHASES)
            if phase is not None:
                sites = (site,) if site is not None else tuple(valid)
                if not any(phase in valid[s] for s in sites):
                    raise ValueError(
                        f"{kind} phase [{phase}] is not a valid "
                        f"{'write' if kind == 'crash_point' else 'read'}"
                        f" site for {sites} (expected "
                        f"{ {s: valid[s] for s in sites} })")
            if kind != "crash_point" and (kill or unsynced is not None):
                raise ValueError(
                    f"[kill=]/[unsynced=] apply only to crash_point")
            if kind != "disk_corrupt" and mode != "flip":
                raise ValueError("[mode=] applies only to disk_corrupt")
            if mode not in ("flip", "truncate"):
                raise ValueError(
                    f"disk_corrupt mode must be flip|truncate, "
                    f"got [{mode}]")
            if unsynced not in (None, "drop"):
                raise ValueError(
                    f"crash_point unsynced must be [drop] when given, "
                    f"got [{unsynced}]")
            self.site = site
            self.index = index
            self.shard = shard
            self.replica = None
            self.host = None
            self.action = None
            self.phase = phase
            self.rate = rate
            self.ms = ms
            self.breaker = breaker
            self.fired = 0
            return
        if kind in CTRL_KINDS:
            # control-plane rules select on (host, action) only — a
            # machine-level fault has no shard/replica/phase identity
            for sel, val in (("site", site), ("index", index),
                             ("shard", shard), ("replica", replica),
                             ("phase", phase)):
                if val is not None:
                    raise ValueError(
                        f"{kind} is a control-plane fault; [{sel}=] "
                        "does not apply (use host=/action=)")
            if kind == "host_dead" and rate != 1.0:
                raise ValueError(
                    "host_dead is persistent; [rate=] decay is not "
                    "allowed (use ctrl_drop for transient faults)")
            if kind == "ctrl_delay" and ms <= 0.0:
                raise ValueError("ctrl_delay needs [ms=]")
            if kind == "net_partition":
                if not self.hosts:
                    raise ValueError(
                        "net_partition needs [hosts=] (the severed "
                        "group, +-separated: hosts=h-1+h-2)")
                if host is not None or action is not None:
                    raise ValueError(
                        "net_partition severs whole links; [host=]/"
                        "[action=] do not apply (use hosts=/heal=, and "
                        "compose ctrl_drop/ctrl_delay rules for "
                        "action-scoped faults)")
                if rate != 1.0:
                    raise ValueError(
                        "net_partition is persistent while installed; "
                        "[rate=] decay is not allowed (use ctrl_drop "
                        "for flaky links)")
                unknown = self.heal - self.hosts - {"all"}
                if unknown:
                    raise ValueError(
                        f"net_partition heal names hosts outside the "
                        f"partition set: {sorted(unknown)}")
        elif host is not None or action is not None:
            raise ValueError(
                f"{kind} fires at data-plane dispatch boundaries; "
                "[host=]/[action=] apply only to "
                f"control-plane kinds {CTRL_KINDS}")
        self.site = site
        self.index = index
        self.shard = shard
        self.replica = replica
        self.host = host
        self.action = action
        # a dead shard presents at enqueue; a straggler presents while
        # the caller waits on results — the phase defaults encode that.
        # A dead DEVICE presents everywhere: device_dead matches any
        # phase (and may not specify one).
        if kind == "device_dead":
            if phase is not None:
                raise ValueError(
                    "device_dead fires at every phase; drop [phase=]")
            if rate != 1.0:
                raise ValueError(
                    "device_dead is persistent; [rate=] decay is not "
                    "allowed (use shard_error for transient faults)")
            self.phase = None
        elif kind in CTRL_KINDS:
            self.phase = None
        else:
            self.phase = phase or ("collect" if kind == "shard_delay"
                                   else "submit")
        self.rate = rate
        self.ms = ms
        self.breaker = breaker
        self.fired = 0

    def matches(self, site: str, index: str | None, shard: int | None,
                replica: int | None, phase: str) -> bool:
        if self.kind in CTRL_KINDS or self.kind in STORAGE_KINDS:
            return False
        if self.phase is not None and self.phase != phase:
            return False
        if self.site is not None and site != self.site:
            return False
        if self.index is not None and index != self.index:
            return False
        if self.shard is not None and shard != self.shard:
            return False
        if self.replica is not None and replica != self.replica:
            return False
        return True

    def severed_hosts(self) -> frozenset:
        """net_partition's EFFECTIVE severed set: hosts minus heals
        (heal=all empties it — the rule stays installed but cuts
        nothing, so a spec can pin the full arc deterministically)."""
        if self.kind != "net_partition" or self.hosts is None:
            return frozenset()
        if "all" in self.heal:
            return frozenset()
        return self.hosts - self.heal

    def matches_ctrl(self, action: str, host: str | None,
                     me: str | None = None) -> bool:
        """Control-plane boundary match. `host` is the REMOTE end of
        the message (target on send, source on receive) so a
        host-pinned fault severs both directions; `action=` accepts the
        full name or its trailing segment (`ping` ~ internal:mesh/ping).
        `me` is the LOCAL end — net_partition fires when exactly one
        end is inside the severed group (links WITHIN the group and
        links wholly outside it stay up: both halves remain internally
        live, which is the split-brain shape quorum fencing exists
        for). A caller that omits `me` is treated as outside the set."""
        if self.kind not in CTRL_KINDS:
            return False
        if self.kind == "net_partition":
            cut = self.severed_hosts()
            return (host in cut) != (me in cut)
        if self.host is not None and host != self.host:
            return False
        if self.action is not None and action != self.action \
                and action.rsplit("/", 1)[-1] != self.action:
            return False
        return True

    def matches_storage(self, site: str, phase: str,
                        index: str | None, shard: int | None) -> bool:
        """Storage boundary match: (site, phase) name the write/read
        site; index/shard scope the rule to one shard's files when the
        caller knows them (Store/Translog carry their owner's ids)."""
        if self.kind not in STORAGE_KINDS:
            return False
        if self.site is not None and site != self.site:
            return False
        if self.phase is not None and phase != self.phase:
            return False
        if self.index is not None and index != self.index:
            return False
        if self.shard is not None and shard != self.shard:
            return False
        return True

    def describe(self) -> dict:
        sel = {k: getattr(self, k)
               for k in ("site", "index", "shard", "replica", "host",
                         "action")
               if getattr(self, k) is not None}
        out = {"kind": self.kind, "phase": self.phase or "any",
               "rate": self.rate, "fired": self.fired, **sel}
        if self.kind in ("shard_delay", "ctrl_delay"):
            out["ms"] = self.ms
        if self.kind == "breaker_trip":
            out["breaker"] = self.breaker
        if self.kind == "disk_corrupt":
            out["mode"] = self.mode
        if self.kind == "crash_point":
            if self.kill:
                out["kill"] = True
            if self.unsynced is not None:
                out["unsynced"] = self.unsynced
        if self.kind == "net_partition":
            out["hosts"] = sorted(self.hosts or ())
            if self.heal:
                out["heal"] = sorted(self.heal)
        return out


class FaultRegistry:
    """A parsed fault spec + one seeded RNG shared by every rate draw."""

    def __init__(self, rules: list[FaultRule], seed: int = 0):
        self.rules = rules
        self.seed = seed
        self._rng = random.Random(seed)
        self._mx = threading.Lock()

    @classmethod
    def parse(cls, spec: str | None) -> "FaultRegistry":
        rules: list[FaultRule] = []
        seed = 0
        for part in (spec or "").split(","):
            part = part.strip()
            if not part:
                continue
            fields = part.split(":")
            kw: dict = {}
            for f in fields[1:]:
                key, _, val = f.partition("=")
                key = key.strip()
                val = val.strip()
                if key in ("shard", "replica", "kill"):
                    kw[key] = int(val)
                elif key in ("rate", "ms"):
                    kw[key] = float(val)
                elif key == "seed":
                    seed = int(val)
                elif key in ("site", "index", "breaker", "phase",
                             "host", "action", "mode", "unsynced"):
                    kw[key] = val
                elif key in ("hosts", "heal"):
                    # host GROUPS are +-separated (the rule grammar
                    # already claims , and :)
                    kw[key] = frozenset(
                        h for h in val.split("+") if h)
                else:
                    raise ValueError(
                        f"unknown fault selector [{key}] in [{part}]")
            rules.append(FaultRule(fields[0].strip(), **kw))
        return cls(rules, seed)

    def on_dispatch(self, site: str, index: str | None = None,
                    shard: int | None = None,
                    replica: int | None = None,
                    phase: str = "submit",
                    skip_delay: bool = False) -> None:
        """Evaluate every matching rule at a dispatch boundary; raises
        (shard_error / breaker_trip) or sleeps (shard_delay).
        skip_delay=True skips shard_delay rules — the caller already
        injected the straggler delay elsewhere (a resident stepped
        dispatch meters it inside device execution via StepBudget) and
        must not sleep it a second time at the collect boundary."""
        for rule in self.rules:
            if skip_delay and rule.kind == "shard_delay":
                continue
            if not rule.matches(site, index, shard, replica, phase):
                continue
            with self._mx:
                if rule.rate < 1.0 and self._rng.random() >= rule.rate:
                    continue
                rule.fired += 1
            if rule.kind == "shard_delay":
                time.sleep(rule.ms / 1000.0)
            elif rule.kind == "shard_error":
                raise FaultInjectedError(
                    f"injected shard_error at {site} dispatch",
                    index=index, shard=shard)
            elif rule.kind == "device_dead":
                raise FaultInjectedError(
                    f"injected device_dead at {site} dispatch "
                    f"(permanent)", index=index, shard=shard)
            elif rule.kind == "breaker_trip":
                from .breaker import breaker_service
                b = breaker_service().breaker(rule.breaker)
                # a REAL over-limit estimate: the trip counter, error
                # shape, and (non-)retention all come from the
                # production breaker path
                wanted = (b.limit + 1) if b.limit > 0 else (1 << 62)
                # un-tripped (e.g. unlimited breaker): the Hold's scoped
                # exit gives the bytes straight back, no leak
                with b.hold(wanted):
                    pass

    def on_ctrl(self, action: str, host: str | None = None,
                me: str | None = None) -> None:
        """Evaluate control-plane rules at a transport boundary
        (parallel/multihost.py hooks every send AND every handler
        entry); raises (host_dead / ctrl_drop / net_partition) or
        sleeps (ctrl_delay). `host` is the remote end of the message,
        `me` the local end (net_partition needs both to decide whether
        the link straddles the severed group)."""
        for rule in self.rules:
            if not rule.matches_ctrl(action, host, me=me):
                continue
            with self._mx:
                if rule.rate < 1.0 and self._rng.random() >= rule.rate:
                    continue
                rule.fired += 1
            if rule.kind == "ctrl_delay":
                time.sleep(rule.ms / 1000.0)
            elif rule.kind == "host_dead":
                raise FaultInjectedError(
                    f"injected host_dead: [{host}] is unreachable "
                    f"for [{action}] (permanent)")
            elif rule.kind == "net_partition":
                raise FaultInjectedError(
                    f"injected net_partition: link [{me}]<->[{host}] "
                    f"severed for [{action}]")
            else:  # ctrl_drop
                raise FaultInjectedError(
                    f"injected ctrl_drop: [{action}] to/from [{host}] "
                    "lost on the wire")

    def on_storage_write(self, site: str, phase: str,
                         index: str | None = None,
                         shard: int | None = None,
                         partial=None, unsynced_drop=None) -> None:
        """Evaluate crash_point rules at a storage WRITE boundary
        (index/store.py save/commit/cleanup sites, index/translog.py
        append/fsync/rotate). A firing rule first runs `partial` (the
        caller's torn-state writer — e.g. half a translog record) and,
        under ``unsynced=drop``, `unsynced_drop` (the caller's
        page-cache-loss simulation: truncate back to the last fsynced
        offset) — then dies: SIGKILL with ``kill=1``, else
        PowerLossError. One-shot: a process crashes once, so a fired
        crash_point never fires again under the same registry."""
        for rule in self.rules:
            if rule.kind != "crash_point" or rule.fired:
                continue
            if not rule.matches_storage(site, phase, index, shard):
                continue
            with self._mx:
                if rule.fired:
                    continue
                if rule.rate < 1.0 and self._rng.random() >= rule.rate:
                    continue
                rule.fired += 1
            if partial is not None:
                partial()
            if rule.unsynced == "drop" and unsynced_drop is not None:
                unsynced_drop()
            if rule.kill:
                import signal
                os.kill(os.getpid(), signal.SIGKILL)
            raise PowerLossError(
                f"injected crash_point at {site}:{phase}"
                + (f" [{index}][{shard}]" if index is not None else ""))

    def on_storage_read(self, site: str, phase: str, path: str,
                        index: str | None = None,
                        shard: int | None = None) -> None:
        """Evaluate disk_corrupt/io_error rules at a storage READ
        boundary, BEFORE the caller opens `path`: disk_corrupt mutates
        the file on disk (seeded flip / tail truncate) and lets the
        read proceed — detection stays the production checksum/crc
        path's job; io_error raises OSError(EIO) like a dying disk."""
        import errno
        for rule in self.rules:
            if rule.kind not in ("disk_corrupt", "io_error"):
                continue
            if not rule.matches_storage(site, phase, index, shard):
                continue
            with self._mx:
                if rule.rate < 1.0 and self._rng.random() >= rule.rate:
                    continue
                rule.fired += 1
                if rule.kind == "disk_corrupt":
                    _corrupt_file(path, rule.mode, self._rng)
                    continue
            raise OSError(errno.EIO,
                          f"injected io_error at {site}:{phase}", path)

    def step_delay_ms(self, site: str, index: str | None = None,
                      shard: int | None = None,
                      replica: int | None = None) -> float:
        """Total shard_delay milliseconds matching this dispatch at the
        collect boundary, CONSUMED here (rate draws + fired counts) so
        the resident step loop can meter the straggler inside device
        execution instead of sleeping it at collect. One call per
        dispatch (StepBudget enforces the once)."""
        total = 0.0
        for rule in self.rules:
            if rule.kind != "shard_delay":
                continue
            if not rule.matches(site, index, shard, replica, "collect"):
                continue
            with self._mx:
                if rule.rate < 1.0 and self._rng.random() >= rule.rate:
                    continue
                rule.fired += 1
            total += rule.ms
        return total

    def snapshot(self) -> dict:
        return {"enabled": bool(self.rules), "seed": self.seed,
                "rules": [r.describe() for r in self.rules]}


_mx = threading.Lock()
_registry: FaultRegistry | None = None


def active() -> FaultRegistry:
    """The process-wide registry; first use parses ES_TPU_FAULT_INJECT."""
    global _registry
    if _registry is None:
        with _mx:
            if _registry is None:
                _registry = FaultRegistry.parse(
                    os.environ.get("ES_TPU_FAULT_INJECT", ""))
    return _registry


def configure(spec: str | None, seed: int | None = None) -> FaultRegistry:
    """Install a new registry from a spec string (None/"" disables)."""
    global _registry
    with _mx:
        reg = FaultRegistry.parse(spec)
        if seed is not None:
            reg.seed = seed
            reg._rng = random.Random(seed)
        _registry = reg
        return reg


def clear() -> None:
    configure("")


def enabled() -> bool:
    return bool(active().rules)


def on_dispatch(site: str, index: str | None = None,
                shard: int | None = None,
                replica: int | None = None,
                phase: str = "submit",
                skip_delay: bool = False) -> None:
    """Hook call at a dispatch boundary — no-op (one attribute check)
    when no rules are installed."""
    reg = active()
    if reg.rules:
        reg.on_dispatch(site, index=index, shard=shard, replica=replica,
                        phase=phase, skip_delay=skip_delay)


def on_ctrl(action: str, host: str | None = None,
            me: str | None = None) -> None:
    """Control-plane boundary hook — no-op (one attribute check) when
    no rules are installed."""
    reg = active()
    if reg.rules:
        reg.on_ctrl(action, host=host, me=me)


def on_storage_write(site: str, phase: str, index: str | None = None,
                     shard: int | None = None,
                     partial=None, unsynced_drop=None) -> None:
    """Storage write-boundary hook (crash_point) — no-op (one
    attribute check) when no rules are installed."""
    reg = active()
    if reg.rules:
        reg.on_storage_write(site, phase, index=index, shard=shard,
                             partial=partial,
                             unsynced_drop=unsynced_drop)


def on_storage_read(site: str, phase: str, path: str,
                    index: str | None = None,
                    shard: int | None = None) -> None:
    """Storage read-boundary hook (disk_corrupt / io_error) — no-op
    (one attribute check) when no rules are installed."""
    reg = active()
    if reg.rules:
        reg.on_storage_read(site, phase, path, index=index, shard=shard)


def _corrupt_file(path: str, mode: str, rng: random.Random) -> None:
    """The disk_corrupt mutator: one seeded byte-flip mid-file or a
    tail-quarter truncation — the two corruption shapes a real torn
    write / bad sector presents. Missing/empty files are left alone
    (nothing to corrupt; the read will fail on its own terms)."""
    try:
        size = os.path.getsize(path)
    except OSError:
        return
    if size <= 0:
        return
    if mode == "truncate":
        keep = size - max(size // 4, 1)
        with open(path, "r+b") as f:
            f.truncate(max(keep, 0))
        return
    pos = rng.randrange(size)
    with open(path, "r+b") as f:
        f.seek(pos)
        b = f.read(1)
        f.seek(pos)
        f.write(bytes([b[0] ^ 0xFF]))


def host_dead_matches(host: str) -> bool:
    """Does a persistent host_dead rule still cover this host? The
    rejoin probe (parallel/multihost.py) asks this BEFORE pinging:
    while the rule stands, the injected machine is still dead;
    removing it (faults.configure/clear) is the deterministic analog
    of the machine coming back. Does NOT consume a firing — probes are
    not messages."""
    for rule in active().rules:
        if rule.kind == "host_dead" and rule.matches_ctrl("probe", host):
            return True
    return False


def net_partition_matches(a: str, b: str | None) -> bool:
    """Does an installed net_partition rule sever the a<->b link? The
    membership/rejoin probes (parallel/multihost.py) ask this BEFORE
    pinging: while the link straddles a severed group the partition
    stands; healing it (heal_partition / configure with heal=) is the
    deterministic analog of the network coming back. Does NOT consume
    a firing — probes are not messages."""
    for rule in active().rules:
        if rule.kind != "net_partition":
            continue
        cut = rule.severed_hosts()
        if (a in cut) != (b in cut):
            return True
    return False


def heal_partition(hosts=None) -> None:
    """Runtime heal counterpart of net_partition: fold the named hosts
    (iterable; None = every partitioned host) back into the connected
    component by adding them to each rule's heal set. Edits the LIVE
    rules under the registry lock — no reconfigure, no reseed, so the
    one RNG's draw sequence (and every other rule's determinism) is
    preserved across the partition→heal arc."""
    reg = active()
    with reg._mx:
        for rule in reg.rules:
            if rule.kind != "net_partition":
                continue
            if hosts is None:
                rule.heal = rule.heal | {"all"}
            else:
                rule.heal = rule.heal | (frozenset(hosts) & rule.hosts)


def device_dead_matches(site: str, index: str | None = None,
                        shard: int | None = None,
                        replica: int | None = None) -> bool:
    """Does a persistent device_dead rule still cover this placement?
    The re-expansion probe (parallel/repack.py) asks this BEFORE
    touching real hardware: while the rule stands, the injected device
    is still dead; removing it (faults.configure/clear) is the
    deterministic analog of the chip coming back. Does NOT consume a
    firing — probes are not dispatches."""
    for rule in active().rules:
        if rule.kind == "device_dead" and rule.matches(
                site, index, shard, replica, "probe"):
            return True
    return False


class StepBudget:
    """One-shot straggler budget for a device-stepped dispatch (the
    resident query loop): the FIRST take() consumes the matching
    collect-phase shard_delay rules and hands their total to the step
    loop, which sleeps it per tile chunk inside device execution;
    `taken` then tells the collect boundary to skip delay rules so the
    straggler is not charged twice. Cold dispatches never call take(),
    leaving PR 4's collect-boundary behavior untouched."""

    __slots__ = ("site", "index", "shard", "replica", "taken")

    def __init__(self, site: str, index: str | None = None,
                 shard: int | None = None, replica: int | None = None):
        self.site = site
        self.index = index
        self.shard = shard
        self.replica = replica
        self.taken = False

    def take(self) -> float:
        if self.taken:
            return 0.0
        self.taken = True
        reg = active()
        if not reg.rules:
            return 0.0
        return reg.step_delay_ms(self.site, index=self.index,
                                 shard=self.shard, replica=self.replica)


def snapshot() -> dict:
    return active().snapshot()
