"""True elastic pod membership, fast tier: the quorum/lease state
machines in isolation (fake clocks, fake transport), the net_partition
fault kind, the explicit ABANDON fast-release, and the in-process
scoped-session pod — a replacement process joining LIVE survivors,
graceful drain vs crash, and the partition arc where the minority
side refuses to fork and the healed side syncs forward.

Ref: zen2 coordination (cluster/coordination/Coordinator.java — quorum
publication, term-fenced leadership, master rejoin) mapped onto the
pod control plane in parallel/membership.py + parallel/multihost.py.
The real-OS-process legs live in test_membership_procs.py (-m slow);
everything here is one process, deterministic, seconds-fast.
"""

import json
import threading
import time
from concurrent.futures import Future

import pytest

from elasticsearch_tpu.cluster.transport import LocalHub
from elasticsearch_tpu.index.mapping import MapperService
from elasticsearch_tpu.index.segment import SegmentBuilder
from elasticsearch_tpu.parallel.membership import (CoordinatorLease,
                                                   NoQuorumError,
                                                   PodCoordinator,
                                                   PodLedger, has_quorum,
                                                   quorum_size)
from elasticsearch_tpu.parallel.multihost import MultiHostIndex
from elasticsearch_tpu.search import dispatch
from elasticsearch_tpu.utils import faults
from elasticsearch_tpu.utils.errors import (LeaseFencedError,
                                            StaleEpochError)
from elasticsearch_tpu.utils.settings import Settings

# ---------------------------------------------------------------------------
# quorum math + ledger (pure, no transport)
# ---------------------------------------------------------------------------


class TestQuorumMath:
    def test_majority_sizes(self):
        assert quorum_size(1) == 1
        assert quorum_size(2) == 2  # 2-host pods cannot lose a member
        assert quorum_size(3) == 2
        assert quorum_size(4) == 3
        assert quorum_size(5) == 3

    def test_disjoint_sets_cannot_both_win(self):
        # the split-brain invariant: for any n, two DISJOINT ack sets
        # cannot both reach quorum
        for n in range(1, 12):
            q = quorum_size(n)
            assert q + q > n

    def test_has_quorum_and_validation(self):
        assert has_quorum(2, 3) and not has_quorum(1, 3)
        with pytest.raises(ValueError):
            quorum_size(0)


class TestPodLedger:
    def test_promise_epoch_gates(self):
        led = PodLedger(5, ("a", "b", "c"))
        assert led.promise(5, "a") == (False, 5)   # not ahead
        assert led.promise(6, "a") == (True, 5)
        assert led.promise(6, "a") == (True, 5)    # idempotent retry
        assert led.promise(6, "b") == (False, 5)   # one promise/epoch
        assert led.promise(7, "b") == (True, 5)    # higher supersedes

    def test_commit_monotonic(self):
        led = PodLedger(0, ("a", "b"))
        assert led.commit(2, ("a",))
        assert not led.commit(2, ("a", "b"))  # equal: stale duplicate
        assert not led.commit(1, ("a", "b"))  # older: never regresses
        assert led.committed().members == ("a",)
        assert led.commit(3, ("a", "b"))
        assert led.committed().epoch == 3
        # commit lifts the promise floor too
        assert led.promise(3, "x") == (False, 3)


# ---------------------------------------------------------------------------
# coordinator lease (fake clock — expiry without sleeping)
# ---------------------------------------------------------------------------


class TestCoordinatorLease:
    def mk(self, me="v", ttl=10.0):
        now = {"t": 100.0}
        return CoordinatorLease(me, ttl, clock=lambda: now["t"]), now

    def test_one_vote_per_term(self):
        lz, _ = self.mk()
        ok, _ = lz.vote("a", 1, 0, 0)
        assert ok
        ok, info = lz.vote("b", 1, 0, 0)  # same term, other candidate
        assert not ok and info["holder"] == "a"

    def test_stale_epoch_candidate_refused(self):
        lz, _ = self.mk()
        ok, _ = lz.vote("a", 1, candidate_epoch=3, my_epoch=5)
        assert not ok  # failover lands on a highest-epoch survivor

    def test_held_lease_refused_until_expiry(self):
        lz, now = self.mk(ttl=10.0)
        assert lz.vote("a", 1, 0, 0)[0]
        assert not lz.vote("b", 2, 0, 0)[0]   # a holds, unexpired
        now["t"] += 11.0
        assert lz.vote("b", 3, 0, 0)[0]       # expired: free

    def test_handoff_consent_bypasses_expiry(self):
        lz, _ = self.mk()
        assert lz.vote("a", 1, 0, 0)[0]
        assert lz.vote("b", 2, 0, 0, handoff_from="a")[0]
        assert not lz.vote("c", 3, 0, 0, handoff_from="zz")[0]

    def test_fence_stale_term_409(self):
        lz, _ = self.mk()
        lz.adopt("a", 5)
        with pytest.raises(LeaseFencedError) as ei:
            lz.fence("old-driver", 4)
        assert ei.value.status == 409
        assert ei.value.term == 5 and ei.value.holder == "a"
        lz.fence("a", 5)     # current term passes (and renews)
        lz.fence("b", 6)     # newer term adopted, not fenced
        assert lz.holder() == ("b", 6)

    def test_adopt_forward_only(self):
        lz, _ = self.mk()
        lz.adopt("a", 5)
        assert not lz.adopt("b", 4)
        assert not lz.adopt("b", 5)  # equal term, different holder
        assert lz.adopt("a", 5)      # equal term, same holder: renewal
        assert lz.adopt("b", 6)

    def test_release_and_i_hold(self):
        lz, now = self.mk(me="a")
        assert lz.vote("a", 1, 0, 0)[0]
        assert lz.i_hold()
        lz.release()
        assert not lz.i_hold()
        assert lz.vote("b", 2, 0, 0)[0]  # freed without waiting TTL
        lz.release()                      # non-holder: no-op
        assert lz.holder() == ("b", 2)
        now["t"] += 99.0
        assert not lz.i_hold()


# ---------------------------------------------------------------------------
# round orchestration over a fake wire
# ---------------------------------------------------------------------------


class _FakePod:
    """N in-memory members wired directly: submit() routes a round leg
    to the target's state machines synchronously. Hosts in `down` fail
    their legs (the dead-voter nack path)."""

    def __init__(self, hosts, epoch=0):
        self.hosts = list(hosts)
        self.down: set[str] = set()
        self.ledgers = {h: PodLedger(epoch, hosts) for h in hosts}
        self.leases = {h: CoordinatorLease(h, 10.0) for h in hosts}
        self.peer_errors: list[tuple[str, str]] = []
        self.coords = {
            h: PodCoordinator(
                h, self.ledgers[h], self.leases[h],
                submit=lambda t, kind, p, me=h: self._route(me, t, kind, p),
                peers=lambda: tuple(self.hosts),
                round_timeout_s=1.0,
                on_peer_error=lambda t, e, me=h:
                    self.peer_errors.append((me, t)))
            for h in hosts}

    def _route(self, src, target, kind, payload) -> Future:
        fut: Future = Future()
        if target in self.down:
            fut.set_exception(ConnectionError(f"{target} is down"))
            return fut
        if kind == "lease_vote":
            granted, info = self.leases[target].vote(
                payload["candidate"], payload["term"], payload["epoch"],
                self.ledgers[target].committed().epoch,
                handoff_from=payload.get("handoff_from"))
            fut.set_result({"granted": granted, "lease": info})
        elif kind == "lease_release":
            self.leases[target].release()
            fut.set_result({"granted": True})
        elif kind == "propose":
            granted, cur = self.ledgers[target].promise(
                payload["epoch"], payload["proposer"])
            fut.set_result({"promised": granted, "epoch": cur})
        elif kind == "commit":
            self.ledgers[target].commit(payload["epoch"],
                                        payload["members"],
                                        payload.get("host_shards"))
            fut.set_result({"ok": True})
        else:  # pragma: no cover
            fut.set_exception(ValueError(kind))
        return fut


class TestPodCoordinator:
    def test_lease_election_full_pod(self):
        pod = _FakePod(["a", "b", "c"])
        term = pod.coords["a"].acquire_lease(0)
        assert term == 1 and pod.leases["a"].i_hold()
        # every voter recorded a as holder
        assert all(pod.leases[h].holder() == ("a", 1) for h in "abc")

    def test_minority_cannot_win_lease(self):
        pod = _FakePod(["a", "b", "c"])
        pod.down |= {"b", "c"}
        with pytest.raises(LeaseFencedError):
            pod.coords["a"].acquire_lease(0)
        # failed legs hit the health observer (dead voters must feed
        # eviction, or the election starves detection forever)
        assert ("a", "b") in pod.peer_errors
        assert ("a", "c") in pod.peer_errors

    def test_second_driver_fenced_then_handoff(self):
        pod = _FakePod(["a", "b", "c"])
        pod.coords["a"].acquire_lease(0)
        with pytest.raises(LeaseFencedError):
            pod.coords["b"].acquire_lease(0)  # a holds, unexpired
        assert pod.coords["b"].request_handoff("a")
        term = pod.coords["b"].acquire_lease(0, handoff_from="a")
        assert term > 1 and pod.leases["b"].i_hold()
        assert not pod.leases["a"].i_hold()

    def test_evicted_holder_vacates_lease(self):
        pod = _FakePod(["a", "b", "c"])
        pod.coords["a"].acquire_lease(0)
        # the quorum commits a's eviction; survivors' electorate shrinks
        for h in ("b", "c"):
            pod.ledgers[h].commit(1, ("b", "c"))
        pod.down.add("a")
        # b re-elects WITHOUT waiting the TTL out: the committed
        # eviction is the holder's consent
        term = pod.coords["b"].acquire_lease(1)
        assert pod.leases["b"].i_hold() and term == 2

    def test_transition_commits_with_quorum(self):
        pod = _FakePod(["a", "b", "c"])
        pod.down.add("c")  # one dead member: 2/3 still a majority
        epoch = pod.coords["a"].propose_transition(
            ("a", "b"), None, reason="evict c")
        assert epoch == 1
        assert pod.ledgers["a"].committed().members == ("a", "b")
        assert pod.ledgers["b"].committed().members == ("a", "b")
        # c never saw the commit; its record is stale, not diverged
        assert pod.ledgers["c"].committed().epoch == 0

    def test_minority_side_cannot_commit(self):
        pod = _FakePod(["a", "b", "c"])
        pod.down |= {"b", "c"}   # a is the 1/3 minority side
        with pytest.raises(NoQuorumError) as ei:
            pod.coords["a"].propose_transition(("a",), None,
                                               reason="partition")
        assert ei.value.acks == 1 and ei.value.needed == 2
        # the refused transition left NOTHING committed
        assert pod.ledgers["a"].committed().epoch == 0

    def test_quorum_judged_against_last_known_set(self):
        # electing yourself into a majority of the NEW set is the
        # classic split-brain bug — the electorate is the OLD set
        pod = _FakePod(["a", "b", "c", "d", "e"])
        pod.down |= {"c", "d", "e"}
        with pytest.raises(NoQuorumError):
            # 2 acks of the old 5 (needs 3) — even though ("a","b")
            # would self-approve as 2/2 of the proposed set
            pod.coords["a"].propose_transition(("a", "b"), None,
                                               reason="partition")


# ---------------------------------------------------------------------------
# net_partition fault kind
# ---------------------------------------------------------------------------


class TestNetPartitionFault:
    @pytest.fixture(autouse=True)
    def _clean(self):
        faults.clear()
        yield
        faults.clear()

    def test_bidirectional_group_severing(self):
        faults.configure("net_partition:hosts=h1+h2")
        # severed: exactly one endpoint inside the group
        assert faults.net_partition_matches("h0", "h1")
        assert faults.net_partition_matches("h1", "h0")
        assert faults.net_partition_matches("h3", "h2")
        # intact: both inside, or both outside (XOR semantics)
        assert not faults.net_partition_matches("h1", "h2")
        assert not faults.net_partition_matches("h0", "h3")

    def test_probe_never_consumes(self):
        faults.configure("net_partition:hosts=h1")
        for _ in range(50):
            assert faults.net_partition_matches("h0", "h1")
        assert faults.net_partition_matches("h0", "h1")

    def test_ctrl_raises_on_severed_link_only(self):
        faults.configure("net_partition:hosts=h1")
        with pytest.raises(Exception, match="net_partition"):
            faults.on_ctrl("internal:mesh/ping", host="h1", me="h0")
        # same side of the partition: the call passes
        faults.on_ctrl("internal:mesh/ping", host="h2", me="h0")

    def test_heal_clause_and_runtime_heal(self):
        faults.configure("net_partition:hosts=h1+h2:heal=h2")
        assert faults.net_partition_matches("h0", "h1")
        assert not faults.net_partition_matches("h0", "h2")
        faults.heal_partition(["h1"])
        assert not faults.net_partition_matches("h0", "h1")
        faults.configure("net_partition:hosts=h3")
        assert faults.net_partition_matches("h0", "h3")
        faults.heal_partition()  # no args: heal everything
        assert not faults.net_partition_matches("h0", "h3")

    def test_validation(self):
        with pytest.raises(ValueError, match=r"hosts="):
            faults.configure("net_partition")
        with pytest.raises(ValueError, match="whole links"):
            faults.configure("net_partition:hosts=h1:action=exec")
        with pytest.raises(ValueError, match="persistent"):
            faults.configure("net_partition:hosts=h1:rate=0.5")
        with pytest.raises(ValueError, match="outside"):
            faults.configure("net_partition:hosts=h1:heal=h9")
        with pytest.raises(ValueError, match="net_partition"):
            faults.configure("host_dead:hosts=h1")


# ---------------------------------------------------------------------------
# in-process pods (scoped sessions over a LocalHub)
# ---------------------------------------------------------------------------

MAPPING = {"properties": {
    "color": {"type": "keyword"},
    "msg": {"type": "text"},
    "n": {"type": "long"}}}
COLORS = ["red", "green", "blue", "teal", "plum"]
N_DOCS = 60
HOSTS = ["a", "b", "c"]

FD_SETTINGS = Settings({
    "mesh.ping_interval": "-1",
    "mesh.ping_timeout": "5s",
    "mesh.ping_retries": 3,
    "mesh.exec_backoff": "10ms",
})


def _doc(i: int) -> dict:
    return {"color": COLORS[i % len(COLORS)], "msg": "alpha", "n": i}


def _segments(svc, sids, n_shards):
    segs = []
    for sid in sids:
        b = SegmentBuilder()
        for i in range(N_DOCS):
            if i % n_shards == sid:
                b.add(svc.parse(str(i), _doc(i)))
        segs.append(b.build(f"s{sid}"))
    return segs


def _build_pod(layout: str, membership: str = "quorum"):
    """Three scoped-session MultiHostIndex 'hosts' over a LocalHub —
    per-host device runtimes, host-side merge, quorum membership."""
    svc = MapperService(mapping=MAPPING)
    hub = LocalHub()
    tr = {h: hub.create_transport(h, n_threads=6) for h in HOSTS}
    out, errs = {}, {}
    n_shards = 4 if layout == "replica" else 6
    spans = {"a": [0, 1], "b": [2, 3], "c": [4, 5]}

    def mk(me):
        try:
            sids = (range(n_shards) if layout == "replica"
                    else spans[me])
            per_host = (n_shards if layout == "replica" else 2)
            out[me] = MultiHostIndex(
                tr[me], me, HOSTS, _segments(svc, sids, n_shards), svc,
                {h: per_host for h in HOSTS}, settings=FD_SETTINGS,
                layout=layout, session="scoped", membership=membership)
        except Exception as e:  # pragma: no cover — surfaced below
            errs[me] = e

    ts = [threading.Thread(target=mk, args=(h,)) for h in HOSTS[1:]]
    [t.start() for t in ts]
    mk("a")
    [t.join(timeout=120) for t in ts]
    assert not errs, errs
    return out, tr, svc, hub


def _close_all(indices, transports):
    faults.clear()
    for idx in indices:
        idx.close()
    for t in transports.values():
        t.close()


def _canon(resp: dict) -> str:
    return json.dumps(resp, sort_keys=True)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


BODY = {"query": {"term": {"color": "teal"}}, "size": 30,
        "aggs": {"k": {"terms": {"field": "color", "size": 10}}}}


def test_scoped_replica_replacement_joins_live_pod():
    """The tentpole acceptance arc, in-process: kill a member of a
    scoped replica pod, quorum-evict it, then a REPLACEMENT process
    joins the live pod — survivors never rebuild their device
    runtimes — and serving is byte-identical throughout."""
    out, tr, svc, hub = _build_pod("replica")
    a, b, c = out["a"], out["b"], out["c"]
    try:
        base = a.search(BODY)
        want = sum(1 for i in range(N_DOCS)
                   if _doc(i)["color"] == "teal")
        assert base["hits"]["total"] == want
        assert base["_shards"]["failed"] == 0
        # any member can drive: the lease hands off, bytes identical
        assert _canon(b.search(BODY)) == _canon(base)
        assert a.stats()["session"] == "scoped"
        assert a.stats()["membership"] == "quorum"

        # ---- kill c; the survivors' 2/3 quorum commits the eviction
        faults.configure("host_dead:host=c")
        for _ in range(4):
            a.heartbeat_now()
        assert a.await_settled(60), a.decisions
        assert a.members == ("a", "b")
        assert a.ledger.committed().members == ("a", "b")
        assert _canon(a.search(BODY)) == _canon(base)  # replica: full
        for _ in range(4):
            b.heartbeat_now()
        assert b.await_settled(60), b.decisions
        assert _canon(b.search(BODY)) == _canon(base)

        # ---- replacement process for seat c joins the LIVE pod ----
        faults.clear()
        before = dispatch.membership_stats.replacements.count
        epochs = (a.epoch, b.epoch)
        c.close()
        tr["c"].close()
        tr["c"] = hub.create_transport("c", n_threads=6)
        c2 = MultiHostIndex(
            tr["c"], "c", HOSTS, _segments(svc, range(4), 4), svc,
            {h: 4 for h in HOSTS}, settings=FD_SETTINGS,
            layout="replica", session="scoped", membership="quorum",
            join=True)
        out["c"] = c2
        assert a.await_settled(60) and b.await_settled(60)
        assert a.members == ("a", "b", "c")
        assert b.members == ("a", "b", "c")
        assert c2.members == ("a", "b", "c")
        # the joiner's epoch is AHEAD of the pre-join epochs — a new
        # committed generation, not a replay
        assert c2.epoch > max(epochs)
        assert dispatch.membership_stats.replacements.count == before + 1
        assert any(d["decision"] == "host_replaced"
                   for d in a.decisions + b.decisions)
        # byte identity through the whole arc, every driver
        assert _canon(a.search(BODY)) == _canon(base)
        assert _canon(b.search(BODY)) == _canon(base)
        assert _canon(c2.search(BODY)) == _canon(base)
        # the replacement learned the pod's clock table transitively
        assert c2.clock_table.get("a") is not None
        assert c2.clock_table.get("b") is not None
    finally:
        _close_all(out.values(), tr)


def test_scoped_shard_merge_and_leg_degradation():
    """Scoped shard layout: the host-side merge is byte-identical
    across drivers, and a member whose exec leg fails degrades to
    structured _shards.failures for its span INSIDE the response —
    no collective to wedge, no eviction required to answer."""
    out, tr, _svc, _hub = _build_pod("shard")
    a, b, c = out["a"], out["b"], out["c"]
    try:
        want_ids = {str(i) for i in range(N_DOCS)
                    if _doc(i)["color"] == "teal"}
        base = a.search(BODY)
        assert {h["_id"] for h in base["hits"]["hits"]} == want_ids
        assert base["_shards"] == {"total": 6, "successful": 6,
                                   "failed": 0}
        assert _canon(b.search(BODY)) == _canon(base)
        assert _canon(c.search(BODY)) == _canon(base)

        # c's span fails per-response while c is down-but-not-evicted
        faults.configure("host_dead:host=c")
        deg = a.search(BODY)
        c_ids = {i for i in want_ids if int(i) % 6 in (4, 5)}
        assert {h["_id"] for h in deg["hits"]["hits"]} == \
            want_ids - c_ids
        assert deg["_shards"]["successful"] == 4
        assert {f["shard"] for f in deg["_shards"]["failures"]} == \
            {4, 5}
        assert all(f["node"] == "c"
                   for f in deg["_shards"]["failures"])
        # the dead host held the lease (it drove last) — the failed
        # election legs feed the health tracker, so the survivors
        # quorum-evict it rather than starving failure detection
        for _ in range(4):
            a.heartbeat_now()
        assert a.await_settled(60), a.decisions
        assert a.members == ("a", "b")
        # revive: a majority member re-adds c on ping proof, c syncs
        # forward, and the merge is byte-identical to the baseline
        faults.clear()
        a.probe_now()
        assert a.await_settled(60), a.decisions
        assert a.members == ("a", "b", "c")
        for _ in range(4):
            c.heartbeat_now()
        assert c.await_settled(60), c.decisions
        assert _canon(a.search(BODY)) == _canon(base)
        assert _canon(c.search(BODY)) == _canon(base)
    finally:
        _close_all(out.values(), tr)


def test_partition_minority_refuses_majority_serves_then_heals():
    """The split-brain acceptance arc: partition {a,b} | {c}. The
    majority commits c's eviction and serves degraded; the minority's
    transition is REFUSED (it cannot reach a quorum of the last-known
    set) so it never forks — and on heal it syncs forward onto the
    majority's higher committed epoch, byte-identical."""
    out, tr, _svc, _hub = _build_pod("shard")
    a, b, c = out["a"], out["b"], out["c"]
    try:
        want_ids = {str(i) for i in range(N_DOCS)
                    if _doc(i)["color"] == "teal"}
        c_ids = {i for i in want_ids if int(i) % 6 in (4, 5)}
        base = a.search(BODY)
        before_ps = dispatch.membership_stats.partitions_survived.count
        faults.configure("net_partition:hosts=c")
        for _ in range(4):
            a.heartbeat_now()
            b.heartbeat_now()
            c.heartbeat_now()
        assert a.await_settled(60), a.decisions
        assert a.members == ("a", "b")
        maj = a.search(BODY)
        assert {h["_id"] for h in maj["hits"]["hits"]} == \
            want_ids - c_ids
        assert maj["_shards"]["failed"] == 2

        # minority: refused, still on the last committed membership
        assert not c.await_settled(3)
        assert c.members == ("a", "b", "c")
        assert c.ledger.committed().epoch < a.ledger.committed().epoch
        assert dispatch.membership_stats.partitions_survived.count > before_ps
        assert any(d["decision"] == "transition_refused_no_quorum"
                   for d in c.decisions), c.decisions

        # ---- heal: the majority re-adds c with live proof ----
        faults.heal_partition()
        a.probe_now()
        assert a.await_settled(60), a.decisions
        assert a.members == ("a", "b", "c")
        for _ in range(4):
            c.heartbeat_now()
        assert c.await_settled(60), c.decisions
        assert c.members == ("a", "b", "c")
        assert c.epoch == a.epoch
        assert _canon(a.search(BODY)) == _canon(base)
        assert _canon(c.search(BODY)) == _canon(base)
    finally:
        _close_all(out.values(), tr)


def test_drain_is_graceful_pod_state_not_a_crash():
    """drain_host: administrative decommission — logged distinctly
    from eviction, counted in membership counters, propagated as POD
    state (no other member re-proposes the drained seat back in), and
    reverted by undrain_host."""
    out, tr, _svc, _hub = _build_pod("replica")
    a, b, c = out["a"], out["b"], out["c"]
    try:
        base = a.search(BODY)
        before = dispatch.membership_stats.drains.count
        assert a.drain_host("b")
        assert not a.drain_host("b")  # idempotent refuse
        assert a.await_settled(60), a.decisions
        assert a.members == ("a", "c")
        assert dispatch.membership_stats.drains.count == before + 1
        drain = [d for d in a.decisions
                 if d["decision"] == "drain_host"]
        assert drain and "not a failure" in drain[0]["reason"]
        assert not any(d["decision"] == "evict_host"
                       for d in a.decisions)
        assert a.stats()["drained_hosts"] == ["b"]
        # the OTHER members fold the drain instead of re-adding b:
        # heartbeats on c must not restore it
        for _ in range(3):
            c.heartbeat_now()
        time.sleep(0.2)
        assert a.members == ("a", "c")
        assert _canon(a.search(BODY)) == _canon(base)  # replica: full
        # drained seat is out of members but its process serves on
        assert b.health is not None

        assert a.undrain_host("b")
        assert not a.undrain_host("b")
        assert a.await_settled(60), a.decisions
        assert a.members == ("a", "b", "c")
        assert a.stats()["drained_hosts"] == []
        assert _canon(b.search(BODY)) == _canon(base)
    finally:
        _close_all(out.values(), tr)


def test_lease_fences_concurrent_driver_and_counts():
    """Two hosts driving: the loser is fenced 409 and retries through
    a handoff — fenced_drivers counts every fence, and both drivers'
    results stay byte-identical (no mismatched-program window)."""
    out, tr, _svc, _hub = _build_pod("replica")
    a, b, _c = out["a"], out["b"], out["c"]
    try:
        base = a.search(BODY)
        assert a.lease.i_hold()
        # b fencing: direct exec under a STALE term must 409
        with pytest.raises(LeaseFencedError):
            b.lease.fence("zombie", b.lease.term() - 1)
        # concurrent drivers hammering: every response identical
        results, errs = [], []

        def drive(idx):
            try:
                for _ in range(3):
                    results.append(_canon(idx.search(BODY)))
            except Exception as e:  # pragma: no cover
                errs.append(e)

        t = threading.Thread(target=drive, args=(b,))
        t.start()
        drive(a)
        t.join(timeout=120)
        assert not errs, errs
        assert len(results) == 6
        assert all(r == _canon(base) for r in results)
        st = a.stats()
        assert st["lease"]["term"] >= 1
        assert st["ledger"]["epoch"] == a.epoch
    finally:
        _close_all(out.values(), tr)


def test_abandon_releases_accepted_seq_promptly():
    """The PR 13 residual, closed: a peer that ACCEPTED a broadcast
    whose driver then bails releases the seq on the explicit ABANDON
    instead of riding out the exec budget."""
    svc = MapperService(mapping=MAPPING)
    hub = LocalHub()
    tr = {"h0": hub.create_transport("h0", n_threads=4)}
    idx = MultiHostIndex(tr["h0"], "h0", ["h0"],
                         _segments(svc, range(2), 2), svc, {"h0": 2},
                         settings=FD_SETTINGS, layout="shard")
    try:
        view = idx._snapshot()
        release = threading.Event()

        def slow_msearch(bodies, deadline=None, allow_stepped=None):
            release.wait(timeout=30)
            return [None] * len(bodies)

        real = view.searcher.raw_msearch
        view.searcher.raw_msearch = slow_msearch
        t0 = threading.Thread(
            target=lambda: idx._exec(view, 0, 0, [{}], None, None),
            daemon=True)
        t0.start()
        time.sleep(0.1)  # seq 0 now blocks inside its program
        got: list = []

        def waiter():
            try:
                # seq 1 waits its turn behind the stuck seq 0 with NO
                # deadline: without ABANDON this parks for the whole
                # exec budget
                idx._exec(view, 1, 0, [{}], None, None)
                got.append("served")
            except StaleEpochError as e:
                got.append(e)

        t1 = threading.Thread(target=waiter, daemon=True)
        t1.start()
        time.sleep(0.1)
        start = time.monotonic()
        idx._on_abandon("driver", {"epoch": view.epoch, "seq": 1})
        t1.join(timeout=10)
        waited = time.monotonic() - start
        assert got and isinstance(got[0], StaleEpochError)
        assert "abandoned" in str(got[0])
        assert waited < 5.0, waited
        # the abandoned seq advanced the turn: seq 2 is NOT stuck
        # behind a ghost once seq 0 finishes
        release.set()
        t0.join(timeout=30)
        view.searcher.raw_msearch = real
        idx._exec(view, 2, 2, [{}], None, None)
        with idx._exec_turn:
            assert idx._exec_next == 3
    finally:
        _close_all((idx,), tr)


def test_abandon_travels_the_wire():
    """The driver-side half: _abandon_seq reaches the peer's abandon
    set over the control plane (and a partitioned peer just misses it
    — ABANDON is best-effort, the floor covers the gap)."""
    out, tr, _svc, _hub = _build_pod("replica")
    a, b, c = out["a"], out["b"], out["c"]
    try:
        epoch = b.epoch
        a._abandon_seq(epoch, 7, ["b", "c"])
        with b._exec_turn:
            assert 7 in b._abandoned
        with c._exec_turn:
            assert 7 in c._abandoned
        # best-effort: a severed link swallows, never raises
        faults.configure("net_partition:hosts=b")
        a._abandon_seq(epoch, 8, ["b"])
        with b._exec_turn:
            assert 8 not in b._abandoned
    finally:
        _close_all(out.values(), tr)
