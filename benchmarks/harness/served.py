"""The system under test, started as a user starts it: `Node` +
`RestServer` in this process (the chip belongs to it), everything else
over HTTP. A seed's first run in a checkout loads the configuration's
corpus, flushes it and closes the node, in a process of its own that
ends before this one touches JAX (`ensure_stored`, `loader.py`); every
run then opens a node on the flushed index, as a restart does. So the
window always meets the same state: a process that has done nothing
but recover an index from its last commit and warm up. (A process that
had loaded 262,144 docs itself stalled for up to 2.9 s inside the
window: PERF.md, Findings.)
"""

from __future__ import annotations

import http.client
import json
import os
import shutil
import subprocess
import sys
import time

from . import corpus as C

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA_ROOT = os.path.join(HERE, ".data")
SIDECAR = "bench_sidecar.json"


def say(*parts) -> None:
    print(*parts, flush=True)


class Http:
    def __init__(self, host: str, port: int):
        self.conn = http.client.HTTPConnection(host, port, timeout=1100)

    def call(self, method: str, path: str, body=None):
        data = None
        if body is not None:
            data = body if isinstance(body, bytes) \
                else json.dumps(body).encode()
        self.conn.request(method, path, body=data,
                          headers={"Content-Type": "application/json"})
        r = self.conn.getresponse()
        return r.status, json.loads(r.read() or b"null")

    def close(self) -> None:
        self.conn.close()


class CompileClock:
    """Seconds JAX spent in backend compiles (cache retrievals included
    — a warm persistent cache shows as a small number here) and the
    persistent cache's hit/miss events, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.cache_misses += 1

    def stop(self) -> None:
        from jax._src import monitoring as mon
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)


def data_dir(config: dict, seed: int, docs: int) -> str:
    return os.path.join(DATA_ROOT, f"{config['name']}-{seed}-{docs}")


def sidecar(config: dict, seed: int, docs: int) -> dict:
    return {"seed": seed, "docs": docs, "shards": config["number_of_shards"],
            "generator_version": C.shape(config).GENERATOR_VERSION}


def stored(config: dict, seed: int, docs: int) -> bool:
    """Whether the checkout holds the flushed index of this
    (configuration, seed, docs), whole."""
    try:
        with open(os.path.join(data_dir(config, seed, docs), SIDECAR)) as f:
            return json.load(f) == sidecar(config, seed, docs)
    except (OSError, ValueError):
        return False


def ensure_stored(config: dict, seed: int, docs: int, rehearse: bool) -> int:
    """Loads the corpus in a child process where the checkout does not
    hold it yet. Call it before this process touches JAX: the child
    holds the chip while it runs. Returns the child's exit code (2: it
    found no TPU), 0 where nothing had to be done."""
    if stored(config, seed, docs):
        return 0
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "loader.py"),
         config["path"], str(seed), str(docs), str(int(rehearse))]).returncode


class Served:
    """A context manager: stops what it started."""

    def __init__(self, config: dict, seed: int, docs: int,
                 keep_data: bool = True, log=say):
        self.config, self.seed, self.docs = config, seed, docs
        self.keep_data, self.log = keep_data, log
        self.data_dir = data_dir(config, seed, docs)
        self.node = self.server = self.http = self.corpus = None
        self.index = config.get("index", "logs")

    def _start(self) -> None:
        from elasticsearch_tpu.node import Node
        from elasticsearch_tpu.rest.server import RestServer

        self.node = Node({"node.name": "bench-0",
                          "path.data": self.data_dir,
                          "index.number_of_replicas": 0})
        self.server = RestServer(self.node, "127.0.0.1", 0).start()
        self.http = Http(self.server.host, self.server.port)

    def _stop(self) -> None:
        if self.http is not None:
            self.http.close()
        if self.server is not None:
            self.server.stop()
        if self.node is not None:
            self.node.close()
        self.node = self.server = self.http = None

    def __enter__(self) -> "Served":
        return self

    def _count(self, n: int, what: str) -> None:
        _st, r = self.http.call("GET", f"/{self.index}/_count")
        if r.get("count") != n:
            raise RuntimeError(f"count {r.get('count')} != {n} ({what})")

    def store(self, corpus: C.Corpus) -> None:
        """Create, `_bulk`, `_refresh`, `_flush`, count every
        acknowledged doc, close: what `loader.py` runs."""
        t = time.perf_counter()
        shutil.rmtree(self.data_dir, ignore_errors=True)
        os.makedirs(self.data_dir)
        self._start()
        http = self.http
        st, r = http.call("PUT", f"/{self.index}", {
            "settings": dict(self.config["index_settings"]),
            "mappings": self.config["mappings"]})
        if st != 200:
            raise RuntimeError(f"create index: {st} {r}")
        chunk = self.config["bulk_size"]
        for lo in range(0, corpus.n, chunk):
            st, r = http.call("POST", f"/{self.index}/_bulk", corpus.bulk_body(
                lo, min(lo + chunk, corpus.n)))
            if st != 200 or r.get("errors"):
                raise RuntimeError(f"bulk at {lo}: {st} "
                                   f"{json.dumps(r)[:300]}")
        t_bulk = time.perf_counter() - t
        for verb in ("_refresh", "_flush"):
            st, r = http.call("POST", f"/{self.index}/{verb}")
            if st != 200 or r["_shards"]["failed"]:
                raise RuntimeError(f"{verb}: {st} {r}")
        self._count(corpus.n, "loaded")
        self._stop()
        with open(os.path.join(self.data_dir, SIDECAR), "w") as f:
            json.dump(sidecar(self.config, self.seed, self.docs), f)
        self.log(f"load: bulk {t_bulk:.1f}s ({corpus.n / t_bulk:.0f} "
                 f"docs/s), refresh+flush+close "
                 f"{time.perf_counter() - t - t_bulk:.1f}s")

    def open(self, corpus: C.Corpus) -> None:
        """Open a node on the flushed index; every acknowledged doc has
        to be counted again."""
        t = time.perf_counter()
        if not stored(self.config, self.seed, self.docs):
            raise RuntimeError(f"no flushed index at {self.data_dir}")
        self._start()
        self.corpus = corpus
        self._count(corpus.n, "reopened")
        n_sh = len(self.node.indices[self.index].shards)
        if n_sh != self.config["number_of_shards"]:
            raise RuntimeError(f"{n_sh} shards")
        self.log(f"open: {corpus.n} docs at {self.data_dir}, commit "
                 f"recovered in {time.perf_counter() - t:.1f}s")

    def warm(self, mix: dict, clock: CompileClock, max_rounds: int = 6) -> None:
        """Every operation of the mix at every batch width the window can
        produce (requests of one operation that overlap are coalesced,
        and the executor pads a batch to a power of two), until a round
        compiles nothing. An operation is warmed by the bodies the
        corpus's shape names for it (`warm_bodies`: a fixed body itself,
        one body of each plan shape of a drawn operation). Width 1 goes
        through `_search`, the entry the window uses; a wider batch can
        only be made on purpose through `_msearch`."""
        head = json.dumps({"index": self.index})

        def send(name: str, body: dict, width: int) -> None:
            if width == 1:
                st, r = self.http.call("POST", f"/{self.index}/_search",
                                       body)
                subs = [r]
            else:
                st, r = self.http.call(
                    "POST", "/_msearch",
                    (f"{head}\n{json.dumps(body)}\n" * width).encode())
                subs = r.get("responses", [])
            if st != 200 or len(subs) != width or any(
                    "hits" not in s for s in subs):
                raise RuntimeError(f"warm-up {name} x{width}: {st} "
                                   f"{json.dumps(r)[:300]}")

        warmed = [(op["name"], body) for op in mix["operations"]
                  for body in self.corpus.warm_bodies(op)]
        for rnd in range(max_rounds):
            before = clock.compiles
            t = time.perf_counter()
            for name, body in warmed:
                for width in mix["warm_widths"]:
                    send(name, body, width)
            self.log(f"warm: round {rnd}, {len(warmed)} bodies, "
                     f"{clock.compiles - before} compiles,"
                     f" {time.perf_counter() - t:.1f}s")
            if clock.compiles == before:
                return
        self.log("warm: still compiling after the last round")

    def node_stats(self) -> dict:
        """The node's `fused_scoring` and `dispatch` counters."""
        out = {}
        for path, key in (("/_nodes/stats", "fused_scoring"),
                          ("/_nodes/stats/dispatch", "dispatch")):
            _st, stats = self.http.call("GET", path)
            out[key] = next(iter(stats["nodes"].values()))[key]
        return out

    def __exit__(self, *exc) -> None:
        self._stop()
        if not self.keep_data or not stored(self.config, self.seed,
                                            self.docs):
            shutil.rmtree(self.data_dir, ignore_errors=True)
