"""Runtime race sanitizer (utils/race_guard) + concurrency stress.

Three layers:

  * the guard primitives themselves: armed mutations without the
    declared lock trip the counter, locked mutations do not, and a
    disarmed process pays only a bool check (no counting);
  * seeded multi-thread hammering of the REAL hot structures — the
    TilePager's fetch/evict cycle under an over-subscribed budget and
    the TrafficController's admit/release/reconfigure cycle — under
    the `race_guarded` fixture asserting ZERO trips (the lock
    discipline the static pass verifies holds at runtime too) plus
    the structures' own invariants (byte accounting, in-flight
    counts) surviving the storm;
  * the nodes_stats surface: `race_guard_trips` appears under
    ["dispatch"] only while armed.
"""

import gc
import random
import threading
import types

import numpy as np
import pytest

from elasticsearch_tpu.utils import race_guard


class TestGuardPrimitives:
    def test_unlocked_mutation_trips_only_while_armed(self):
        mx = threading.Lock()
        d = race_guard.guarded_dict(mx, "test.site")
        lst = race_guard.guarded_list(mx, "test.list")
        od = race_guard.guarded_odict(mx, "test.od")
        d["cold"] = 1          # disarmed: no counting
        race_guard.arm()
        race_guard.reset_counters()
        try:
            d["k"] = 1
            lst.append(2)
            od["o"] = 3
            od.move_to_end("o")
            assert race_guard.trips() == 4
            assert race_guard.trips_by_site()["test.site"] == 1
            with mx:
                d["k2"] = 2
                del d["k"]
                lst.pop()
                od.pop("o")
            assert race_guard.trips() == 4
        finally:
            race_guard.disarm()
            race_guard.reset_counters()
        d["post"] = 1          # disarmed again: silent
        assert race_guard.trips() == 0

    def test_inplace_mutators_are_guarded(self):
        # sort/reverse/__iadd__/|= are mutations too — the guard list
        # is one tuple per container type, so none slip through
        mx = threading.Lock()
        lst = race_guard.guarded_list(mx, "t.l")
        lst.extend([3, 1, 2])
        d = race_guard.guarded_dict(mx, "t.d")
        race_guard.arm()
        race_guard.reset_counters()
        try:
            lst.sort()
            lst.reverse()
            lst += [4]
            d |= {"k": 1}
            assert race_guard.trips() == 4
            assert list(lst) == [3, 2, 1, 4] and d["k"] == 1
        finally:
            race_guard.disarm()
            race_guard.reset_counters()

    def test_rlock_owner_check(self):
        mx = threading.RLock()
        d = race_guard.guarded_dict(mx, "test.rlock")
        race_guard.arm()
        race_guard.reset_counters()
        try:
            with mx:
                d["k"] = 1
            assert race_guard.trips() == 0
            d["k2"] = 2
            assert race_guard.trips() == 1
        finally:
            race_guard.disarm()
            race_guard.reset_counters()

    def test_containers_behave_like_builtins(self):
        mx = threading.Lock()
        d = race_guard.guarded_dict(mx, "s")
        d.update({"a": 1, "b": 2})
        assert dict(d) == {"a": 1, "b": 2} and d.setdefault("a", 9) == 1
        od = race_guard.guarded_odict(mx, "s")
        od["x"] = 1
        od["y"] = 2
        od.move_to_end("x")
        assert list(od) == ["y", "x"]
        assert od.popitem(last=False) == ("y", 2)
        lst = race_guard.guarded_list(mx, "s")
        lst.extend([3, 1, 2])
        lst.sort() if hasattr(lst, "sort") else None
        lst[:] = [9, 8]
        assert list(lst) == [9, 8]

    def test_snapshot_contract(self):
        assert race_guard.snapshot() is None
        race_guard.arm()
        try:
            assert race_guard.snapshot() == {"race_guard_trips": 0}
        finally:
            race_guard.disarm()
            race_guard.reset_counters()


class _FakeStore:
    """TileStore stand-in: the exact duck type TilePager.fetch reads
    (seg_id, tile_nbytes, tile_slices, _fwd, tile), without building a
    real segment."""

    def __init__(self, seg_id: str, n_tiles: int = 16, tile: int = 8,
                 width: int = 4):
        self.seg_id = seg_id
        self.tile = tile
        self.n_tiles = n_tiles
        self.fields = ("body",)
        tids = np.arange(n_tiles * tile * width,
                         dtype=np.int32).reshape(n_tiles * tile, width)
        imps = np.ones((n_tiles * tile, width), np.float32)
        self._fwd = {"body": (tids, imps)}
        self.tile_nbytes = {
            "body": tids[: tile].nbytes + imps[: tile].nbytes}
        self.paged_bytes = tids.nbytes + imps.nbytes
        self.summary_bytes = 0

    def tile_slices(self, field, tile_id):
        tids, imps = self._fwd[field]
        lo, hi = tile_id * self.tile, (tile_id + 1) * self.tile
        return tids[lo:hi], imps[lo:hi]


class TestTilePagerStress:
    def test_seeded_fetch_evict_hammer_zero_trips(self, race_guarded,
                                                  monkeypatch):
        """8 threads × seeded random tile sets against one pager with
        a budget ~25% of the working set: every fetch both uploads and
        evicts, two threads regularly race the same miss, and segments
        are dropped mid-flight. Zero sanitizer trips, byte accounting
        consistent, breaker back to baseline after the drop."""
        from elasticsearch_tpu.index.tiering import TilePager
        from elasticsearch_tpu.utils.breaker import breaker_service

        stores = [_FakeStore(f"rg-seg-{i}") for i in range(3)]
        tile_nb = stores[0].tile_nbytes["body"]
        # ~4 tiles resident out of 3 segments x 16 tiles
        monkeypatch.setenv("ES_TPU_TIERED_BUDGET_BYTES",
                           str(4 * tile_nb))
        pager = TilePager()
        fielddata = breaker_service().breaker("fielddata")
        baseline = fielddata.used
        errors: list[BaseException] = []

        def hammer(seed: int):
            rng = random.Random(seed)
            try:
                for _ in range(60):
                    st = stores[rng.randrange(len(stores))]
                    tiles = np.array(sorted(rng.sample(
                        range(st.n_tiles), rng.randint(1, 3))),
                        dtype=np.int64)
                    out = pager.fetch(st, st.fields, tiles)
                    assert len(out["body"][0]) == len(tiles)
                    if rng.random() < 0.1:
                        pager.drop_segment(st.seg_id)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=hammer, args=(31 + i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]
        assert race_guarded.trips() == 0, race_guarded.trips_by_site()
        # residency accounting survived the storm: the tracked byte
        # total equals the entries actually resident
        with pager._mx:
            assert pager._resident_bytes == sum(
                e.nbytes for e in pager._tiles.values())
        for st in stores:
            pager.drop_segment(st.seg_id)
        assert pager.resident_bytes == 0
        # retired holds release when the device buffers die
        gc.collect()
        assert fielddata.used <= baseline

    def test_eviction_respects_working_chunk(self, race_guarded,
                                             monkeypatch):
        """A fetch larger than the whole budget keeps ITS tiles (the
        working chunk is never evicted out from under a running
        program) — bytes may transiently exceed the budget instead."""
        from elasticsearch_tpu.index.tiering import TilePager

        st = _FakeStore("rg-big", n_tiles=8)
        monkeypatch.setenv("ES_TPU_TIERED_BUDGET_BYTES",
                           str(st.tile_nbytes["body"]))
        pager = TilePager()
        out = pager.fetch(st, st.fields, np.arange(6))
        assert len(out["body"][0]) == 6
        assert pager.resident_tiles() == 6
        assert race_guarded.trips() == 0
        pager.drop_segment(st.seg_id)


class TestTrafficControllerStress:
    def test_admit_release_reconfigure_hammer_zero_trips(
            self, race_guarded):
        """8 threads admitting/releasing across a rotating tenant set
        while a 9th republished quotas 40 times: zero trips, in-flight
        drains to zero, and every admit was either granted a ticket or
        priced a 429 (counters add up)."""
        from elasticsearch_tpu.search.traffic import TrafficController
        from elasticsearch_tpu.utils.errors import TrafficRejectedError

        tc = TrafficController({"tenant.t0.rate": 1e9,
                                "tenant.t0.burst": 1e9,
                                "tenant.t1.max_concurrent": 4})
        errors: list[BaseException] = []
        stop = threading.Event()

        def worker(seed: int):
            rng = random.Random(seed)
            try:
                for _ in range(150):
                    tenant = f"t{rng.randrange(3)}"
                    op = rng.choice(["search", "msearch", "scroll"])
                    if op == "msearch":
                        ticket = tc.admit_items(tenant, op,
                                                rng.randint(1, 4))
                        ticket.release()
                    else:
                        try:
                            ticket = tc.admit(tenant, op)
                        except TrafficRejectedError as e:
                            assert e.retry_after_s >= 0
                            continue
                        if rng.random() < 0.5:
                            tc.note_lane_depth(ticket.lane,
                                               rng.randint(0, 8))
                        ticket.release()
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        def reconfigurer():
            rng = random.Random(7)
            try:
                for i in range(40):
                    cfg = {"tenant.t0.rate": rng.choice([1e9, -1]),
                           "tenant.t1.max_concurrent":
                               rng.choice([2, 4, 8]),
                           "lane.bulk.quota": rng.choice([1, 2, 3])}
                    if i % 5 == 0:
                        cfg["tenant.t2.lane"] = "bulk"
                    tc.reconfigure(cfg)
                    tc.snapshot()
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
            finally:
                stop.set()

        threads = [threading.Thread(target=worker, args=(100 + i,))
                   for i in range(8)] + [
            threading.Thread(target=reconfigurer)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]
        assert race_guarded.trips() == 0, race_guarded.trips_by_site()
        snap = tc.snapshot()
        for tid, st in snap["tenants"].items():
            assert st["queued"] == 0, (tid, st)
            assert st["admitted"] + st["rejected"] > 0 or tid
        assert stop.is_set()

    def test_scheduler_lane_hammer_zero_trips(self, race_guarded):
        """Concurrent batches across lanes through the real scheduler
        (the guarded _pending list survives every drain round's
        in-place leftover swap)."""
        from elasticsearch_tpu.search.dispatch import DispatchScheduler
        from elasticsearch_tpu.search.traffic import TrafficController

        class _Reader:
            def msearch_submit(self, bodies, with_partials=False, **kw):
                return types.SimpleNamespace(
                    finish=lambda: [{"ok": b["q"]} for b in bodies],
                    group_sizes=[len(bodies)], dispatch_count=1)

        sched = DispatchScheduler(traffic=TrafficController({}))
        reader = _Reader()
        errors: list[BaseException] = []

        def caller(seed: int):
            rng = random.Random(seed)
            try:
                for i in range(40):
                    lane = rng.choice(["interactive", "msearch",
                                       "scroll", "bulk"])
                    batch = sched.batch(lane=lane)
                    jobs = [batch.submit(reader, {"q": (seed, i, j)})
                            for j in range(rng.randint(1, 3))]
                    batch.dispatch()
                    for j, job in enumerate(jobs):
                        assert job.result() == {"ok": (seed, i, j)}
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=caller, args=(500 + i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]
        assert race_guarded.trips() == 0, race_guarded.trips_by_site()
        assert not sched._pending


class TestMetricsConcurrency:
    def test_registry_snapshot_vs_get_hammer(self, race_guarded):
        """The satellite fix made provable: concurrent snapshot() and
        _get() used to be able to raise RuntimeError (dict changed
        size during iteration); now both hold the lock."""
        from elasticsearch_tpu.utils.metrics import MetricsRegistry

        reg = MetricsRegistry()
        errors: list[BaseException] = []

        def writer(seed: int):
            rng = random.Random(seed)
            try:
                for i in range(300):
                    reg.counter(f"c{rng.randrange(64)}").inc()
                    reg.meter(f"m{rng.randrange(16)}").mark()
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        def reader():
            try:
                for _ in range(200):
                    snap = reg.snapshot()
                    assert isinstance(snap, dict)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(4)] + [
            threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]
        assert race_guarded.trips() == 0

    def test_ewma_concurrent_update_stays_in_envelope(self):
        """EWMA.update is a locked read-modify-write: hammering it
        from 4 threads with samples in [0, 1] can never leave the
        value outside [0, 1] (the unlocked version could lose or
        double-apply deltas)."""
        from elasticsearch_tpu.utils.metrics import EWMA

        e = EWMA(alpha=0.3)
        errors: list[BaseException] = []

        def upd(seed: int):
            rng = random.Random(seed)
            try:
                for _ in range(2000):
                    e.update(rng.random())
                    assert 0.0 <= e.value <= 1.0
            except BaseException as ex:  # noqa: BLE001
                errors.append(ex)

        threads = [threading.Thread(target=upd, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[0]


class TestNodeStatsSurface:
    def test_race_guard_trips_key_only_while_armed(self, monkeypatch):
        from elasticsearch_tpu.node import Node

        n = Node({})
        try:
            stats = n.nodes_stats()["nodes"][n.name]["dispatch"]
            assert "race_guard_trips" not in stats
        finally:
            n.close()
        monkeypatch.setenv("ES_TPU_RACE_GUARD", "1")
        n = Node({})
        try:
            assert race_guard.armed()
            stats = n.nodes_stats()["nodes"][n.name]["dispatch"]
            assert stats["race_guard_trips"] == 0
        finally:
            n.close()
            race_guard.disarm()
            race_guard.reset_counters()

    def test_env_arm_counts_real_trip(self, race_guarded):
        """A deliberately slipped lock is visible at the stats key —
        the signal a bench run would report."""
        from elasticsearch_tpu.search import resident

        resident.cache._entries["bogus"] = None  # no lock: trips
        try:
            assert race_guarded.snapshot()["race_guard_trips"] == 1
        finally:
            with resident.cache._mx:
                resident.cache._entries.pop("bogus", None)
