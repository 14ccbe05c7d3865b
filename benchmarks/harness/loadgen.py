"""The load generator: a process of its own that never imports JAX.

    python benchmarks/harness/loadgen.py <spec.json>

`spec` holds the server's address, the index, the traffic mix as read
from `benchmarks/traffic/<mix>.json` (with the cell's own overrides
already laid over it), the seed and the window's seconds. The one
general generator reads these parameters of a mix:

- `operations`: each with its `name`, the REST `body` it sends to
  `POST /{index}/_search` and its `target_throughput`, requests a
  second. An operation with a `draw` group in place of a `body` sends
  queries that differ from request to request: the shape's corpus draws
  `distinct` of them (`Corpus.draw` reads the rest of the group), and
  the operation's requests repeat them as often as rank^-`repeat_zipf`
  says, every one at least once (`plan_queries`, which `run.py` calls
  before the window; a planned operation carries `bodies`, `specs` and
  `order`, the query of each of its requests);
- `rate_scale`: every operation's rate is multiplied by it (1 is the
  source's own load);
- `clients`: the connections the schedule is sent over. The loop is
  open: a request is due on the schedule whatever the server does.

Every seed gets the same work: round(rate x seconds) requests of each
operation (one at the least) and the same set of exponential gaps (the
quantiles of the distribution, so that their sum is the window), both
in an order of the seed's own. A request is timed from when it was due
to the last byte of its response. The result, one JSON object on
standard output, holds every request with its times on the machine-wide
monotonic clock, its answer's digest (what `corpus.Reference.compare`
reads) and how late the generator ran.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import die_with_parent  # noqa: E402
from harness.corpus import apportion, digest  # noqa: E402

DRAIN_S = 60.0      # an answer may come this long after the window closes


def due_times(n: int, seconds: float, rng) -> np.ndarray:
    """Open-loop schedule: `n` arrivals whose gaps are the quantiles of
    the exponential distribution, scaled so that they fill the window
    exactly, shuffled by the seed."""
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    rng.shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]


def requests_of(op: dict, mix: dict, seconds: float) -> int:
    """round(rate x seconds) requests of an operation, one at the least."""
    rate = op["target_throughput"] * mix.get("rate_scale", 1.0)
    return max(1, int(round(rate * seconds)))


def deal_operations(mix: dict, seconds: float, rng) -> list[int]:
    """The window's requests, each the index of its operation, in an
    order drawn from the seed."""
    dealt = []
    for k, op in enumerate(mix["operations"]):
        dealt += [k] * requests_of(op, mix, seconds)
    rng.shuffle(dealt)
    return dealt


def repeats(requests: int, distinct: int, zipf: float, rng) -> list[int]:
    """Which of `distinct` queries each of `requests` requests sends:
    every query once, the rest as rank^-`zipf` says (largest remainders,
    so every seed repeats its head queries as often), in an order drawn
    from the seed."""
    have = apportion(1.0 / (np.arange(distinct) + 1.0) ** zipf,
                     requests - distinct)
    order = np.repeat(np.arange(distinct), have + 1)
    rng.shuffle(order)
    return order.tolist()


def plan_queries(mix: dict, corpus, seed: int, seconds: float) -> dict:
    """The mix with every drawn operation planned for one window: its
    `bodies` and `specs` (the queries `corpus.draw` made of its `draw`
    group) and `order` (the query of its first, second, ... request).
    Each drawn operation draws from a stream of its own, queries first,
    so a mix of fixed bodies sends what it sent before there were any,
    and an operation sends the same queries at every rate that has room
    for all of them."""
    ops = []
    for k, op in enumerate(mix["operations"]):
        if "draw" in op:
            rng = np.random.default_rng([seed, 12, k])
            requests = requests_of(op, mix, seconds)
            pairs = corpus.draw(op, min(op["draw"]["distinct"], requests),
                                rng)
            op = dict(op, bodies=[b for b, _ in pairs],
                      specs=[s for _, s in pairs],
                      order=repeats(requests, len(pairs),
                                    op["draw"]["repeat_zipf"], rng))
        ops.append(op)
    return dict(mix, operations=ops)


class Client:
    """One keep-alive connection and the requests sent over it."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.conn = http.client.HTTPConnection(
            spec["host"], spec["port"], timeout=spec["seconds"] + DRAIN_S)
        self.conn.connect()
        self.records: list[dict] = []

    def send(self, i: int, due: float, op: str, query: int | None,
             data: bytes) -> None:
        sent = time.monotonic()
        rec = {"i": i, "op": op, "due": due, "sent": sent, "status": 0}
        if query is not None:
            rec["query"] = query
        try:
            self.conn.request("POST", f"/{self.spec['index']}/_search",
                              body=data,
                              headers={"Content-Type": "application/json"})
            r = self.conn.getresponse()
            raw = r.read()
            rec["done"] = time.monotonic()
            rec["status"] = r.status
            resp = json.loads(raw) if r.status == 200 else {}
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["done"] = time.monotonic()
            rec["error"] = repr(e)[:200]
            resp = {}
            self.conn.close()
        rec["ok"] = ("hits" in resp and resp.get("timed_out") is False
                     and resp.get("_shards", {}).get("failed") == 0)
        rec["took"] = resp.get("took")
        if rec["ok"]:
            rec["digest"] = digest(resp)
        else:
            rec["said"] = json.dumps(resp)[:300]
        self.records.append(rec)


def start_time(spec: dict) -> float:
    """The window opens at `start_at` on the machine-wide monotonic
    clock, which the harness set a little ahead; at once if that has
    passed (the harness reads the true opening from the result)."""
    wait = spec.get("start_at", 0.0) - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    return time.monotonic()


def run_open(spec: dict) -> list[dict]:
    mix = spec["mix"]
    rng = np.random.default_rng([spec["seed"], 11])
    dealt = deal_operations(mix, spec["seconds"], rng)
    due = due_times(len(dealt), spec["seconds"], rng)
    clients = [Client(spec) for _ in range(mix["clients"])]
    # every request is built before the clock starts: an operation's
    # one body, or the planned query of each of its requests in turn
    fixed = [None if "order" in op else json.dumps(op["body"]).encode()
             for op in mix["operations"]]
    turn = [0] * len(fixed)
    built = []
    for k in dealt:
        op, query = mix["operations"][k], None
        if fixed[k] is None:
            query = op["order"][turn[k]]
            turn[k] += 1
        built.append((op["name"], query, fixed[k] or json.dumps(
            op["bodies"][query]).encode()))
    t0 = start_time(spec)
    lock = threading.Lock()
    nxt = [0]

    def loop(c: int) -> None:
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= len(due):
                return
            wait = t0 + due[i] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            clients[c].send(i, t0 + float(due[i]), *built[i])

    threads = [threading.Thread(target=loop, args=(c,), daemon=True)
               for c in range(len(clients))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(spec["seconds"] + DRAIN_S)
    return [r for cl in clients for r in cl.records]


def main(argv: list[str]) -> int:
    die_with_parent()
    with open(argv[1]) as f:
        spec = json.load(f)
    if spec["mix"]["loop"] != "open":
        raise SystemExit(f"loadgen: no loop [{spec['mix']['loop']}]")
    t_start = time.monotonic()
    records = sorted(run_open(spec), key=lambda r: r["i"])
    t_end = time.monotonic()
    late = [1e3 * (r["sent"] - r["due"]) for r in records]
    first = min((r["due"] for r in records), default=t_start)
    last = max((r.get("done", t_end) for r in records), default=t_end)
    json.dump({"t_first_due": first,
               "t_close": max(first + spec["seconds"], last),
               "late_ms": {"max": max(late, default=0.0),
                           "p95": float(np.percentile(late, 95))
                           if late else 0.0},
               "requests": records}, sys.stdout)
    sys.stdout.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
