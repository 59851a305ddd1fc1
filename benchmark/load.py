"""The load generator: one general generator that reads a traffic mix
from `traffic/<name>.json` and drives `Server.register_job`.

closed loop   `clients` threads; each registers one job of the cell's
              configuration, waits until every asked alloc of that job is
              visible in the state store, and registers the next.  Callers
              that wait for their deploy to land.
open loop     registrations are due on a schedule drawn from the seed
              (`rate_per_s`, `arrivals` uniform or poisson) whatever the
              server does; latency is timed from when a registration was
              DUE, and how late the generator ran is reported.

"Visible" is read from the live store by ONE watcher thread that sleeps
in the store's own blocking-query primitive (`wait_for_change`) and, on
every change of the allocs table, looks at the outstanding jobs: a
registration's clock stops at the first look that finds its asked
count.  No registration is dropped because the window closed: those
still open are waited out up to `wait_timeout_s` and keep their whole
latency; one that never reaches its count is `failed`.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

import cluster

HERE = os.path.dirname(os.path.abspath(__file__))
NAMESPACE = "default"


def load_traffic(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json"),
              encoding="utf-8") as f:
        t = json.load(f)
    if t["loop"] not in ("closed", "open"):
        raise ValueError(f"traffic {name}: loop must be closed or open")
    if t.get("fault_schedule"):
        raise ValueError(f"traffic {name}: fault_schedule is a hook no "
                         "generator implements yet; leave it empty")
    return t


class HeartbeatPump(threading.Thread):
    """What a deployment's clients do: heartbeat every node well inside
    its TTL (copy of `chip_smoke.HeartbeatPump`).  Client-less nodes
    otherwise expire (the TTL is rate-scaled: 10 s at toy size, ~200 s
    at 10,000 nodes), go down, and flood the broker with node-update
    evals."""

    def __init__(self, server, n_nodes_expected: int):
        super().__init__(daemon=True, name="heartbeat-pump")
        hb = server.heartbeater
        self.server = server
        self.node_ids: List[str] = []      # grows while nodes register
        self.sweep_s = min(20.0, max(n_nodes_expected / hb.max_rate,
                                     hb.min_ttl) / 4)
        self.stop_evt = threading.Event()
        self.sweeps = 0
        self.unknown = 0

    def run(self) -> None:
        while not self.stop_evt.is_set():
            ids = list(self.node_ids)
            chunk = max(1, len(ids) // 50)
            for i in range(0, max(len(ids), 1), chunk):
                for nid in ids[i:i + chunk]:
                    if self.server.node_heartbeat(nid) is None:
                        self.unknown += 1
                if self.stop_evt.wait(self.sweep_s / 50):
                    return
            self.sweeps += 1

    def stop(self) -> None:
        self.stop_evt.set()
        self.join(timeout=10.0)


class Registration:
    __slots__ = ("job_id", "asked", "shape", "t_due", "t_sent",
                 "t_visible", "seen", "error", "done")

    def __init__(self, job_id: str, asked: int, shape=None):
        self.job_id, self.asked = job_id, asked
        self.shape = shape            # None: the whole job template
        self.t_due = self.t_sent = 0.0
        self.t_visible: Optional[float] = None
        self.seen = 0                 # allocs seen at the last look
        self.error: Optional[str] = None
        self.done = threading.Event()


class Watcher(threading.Thread):
    """Stops each outstanding registration's clock when its asked count
    is in the store."""

    #: longest the watcher sleeps without a store change
    IDLE_S = 0.05

    def __init__(self, store):
        super().__init__(daemon=True, name="visibility-watcher")
        self.store = store
        self._lock = threading.Lock()
        self._open: Dict[str, Registration] = {}
        self._marks: List[tuple] = []      # (t, callback) one-shot
        self.stop_evt = threading.Event()
        self.looks = 0

    def track(self, reg: Registration) -> None:
        with self._lock:
            self._open[reg.job_id] = reg

    def at(self, t: float, callback) -> None:
        """Run `callback()` on the watcher thread at monotonic time t,
        right after a look (the window's close reads the store there)."""
        with self._lock:
            self._marks.append((t, callback))

    def look(self) -> None:
        with self._lock:
            regs = list(self._open.values())
        self.looks += 1
        for reg in regs:
            n = len(self.store.allocs_by_job(NAMESPACE, reg.job_id))
            reg.seen = n
            if n >= reg.asked:
                reg.t_visible = time.monotonic()
                with self._lock:
                    self._open.pop(reg.job_id, None)
                reg.done.set()

    def run(self) -> None:
        seen_allocs_ix = -1
        ix = self.store.latest_index()
        while not self.stop_evt.is_set():
            now = time.monotonic()
            with self._lock:
                due = [m for m in self._marks if m[0] <= now]
                self._marks = [m for m in self._marks if m[0] > now]
                nxt = min((m[0] for m in self._marks), default=None)
            allocs_ix = self.store.table_index("allocs")
            if allocs_ix != seen_allocs_ix or due:
                seen_allocs_ix = allocs_ix
                self.look()
            for _t, cb in due:
                cb()
            wait = self.IDLE_S if nxt is None \
                else max(0.0, min(self.IDLE_S, nxt - time.monotonic()))
            ix = self.store.wait_for_change(ix, wait)

    def stop(self) -> None:
        self.stop_evt.set()
        self.join(timeout=10.0)


class LoadGen:
    """Registers jobs of one configuration against one server."""

    def __init__(self, server, cfg: dict, seed: int, traffic: dict):
        self.server, self.cfg, self.seed = server, cfg, int(seed)
        self.traffic = traffic
        self.asked = cluster.job_count(cfg)
        self.watcher = Watcher(server.store)
        self.watcher.start()
        self._n = itertools.count()
        self.sent: List[Registration] = []     # every one, in send order
        self._sent_lock = threading.Lock()

    def close(self) -> None:
        self.watcher.stop()

    # ------------------------------------------------------------ one job
    def _register(self, t_due: Optional[float] = None,
                  shape=None) -> Registration:
        job_id = f"job-{self.seed}-{next(self._n)}"
        job = cluster.build_job(self.cfg, job_id, shape)
        asked = self.asked if shape is None \
            else cluster.job_count(self.cfg, shape)
        reg = Registration(job_id, asked, shape)
        self.watcher.track(reg)
        with self._sent_lock:
            self.sent.append(reg)
        reg.t_sent = time.monotonic()
        reg.t_due = reg.t_sent if t_due is None else t_due
        try:
            self.server.register_job(job)
        except Exception as exc:     # a refused registration is a failure
            reg.error = f"{type(exc).__name__}: {exc}"
            reg.done.set()
        return reg

    # ------------------------------------------------------------- loops
    def closed(self, clients: int, deadline: float, give_up: float,
               jobs_per_client: Optional[int] = None
               ) -> List[Registration]:
        """`clients` threads, each: register, wait for visible, again,
        until `deadline` (or `jobs_per_client` jobs).  A client stops
        waiting for a job at `give_up`."""
        mine: List[List[Registration]] = [[] for _ in range(clients)]

        def client(i: int) -> None:
            n = 0
            while time.monotonic() < deadline and (
                    jobs_per_client is None or n < jobs_per_client):
                reg = self._register()
                mine[i].append(reg)
                n += 1
                if not reg.done.wait(max(0.0, give_up - time.monotonic())):
                    return

        threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                    name=f"client-{i}")
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return [r for m in mine for r in m]

    def open(self, rate_per_s: float, arrivals: str, senders: int,
             t_start: float, seconds: float, give_up: float
             ) -> List[Registration]:
        """Registrations due on a schedule drawn from the seed; a pool of
        `senders` threads sends each when due, or as soon after as a
        sender is free (the lateness is reported)."""
        rng = np.random.default_rng([self.seed, 3])
        n = max(1, int(round(rate_per_s * seconds)))
        if arrivals == "poisson":
            gaps = rng.exponential(1.0 / rate_per_s, size=4 * n + 16)
            due = np.cumsum(gaps)
            due = due[due < seconds]
        elif arrivals == "uniform":
            due = np.arange(n) / rate_per_s
        else:
            raise ValueError(f"arrivals {arrivals!r}: uniform or poisson")
        nxt = itertools.count()
        regs: List[Optional[Registration]] = [None] * len(due)

        def sender() -> None:
            while True:
                i = next(nxt)
                if i >= len(due):
                    return
                t_due = t_start + float(due[i])
                delay = t_due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                regs[i] = self._register(t_due=t_due)

        threads = [threading.Thread(target=sender, daemon=True,
                                    name=f"sender-{i}")
                   for i in range(senders)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        out = [r for r in regs if r is not None]
        for r in out:
            r.done.wait(max(0.0, give_up - time.monotonic()))
        return out

    # ------------------------------------------------------------ phases
    def warm_up(self, timeout_s: float) -> int:
        """Bursts of the traffic mix's `warmup_bursts` sizes through the
        same loop the window uses, each waited out, so that every shape
        bucket the window can form is compiled or loaded before it.
        First, one at a time, the cut-down jobs of
        `warmup_leftover_totals`: the shapes in which an eval's undecided
        placements are retried alone."""
        n = 0
        for shape in cluster.leftover_shapes(
                self.cfg, self.traffic.get("warmup_leftover_totals", [])):
            reg = self._register(shape=shape)
            if not reg.done.wait(timeout_s) or reg.t_visible is None:
                raise RuntimeError(
                    f"warm-up job of shape {shape} never reached its "
                    f"count within {timeout_s:.0f}s ({reg.error})")
            n += 1
        for size in self.traffic["warmup_bursts"]:
            give_up = time.monotonic() + timeout_s
            regs = self.closed(int(size), give_up, give_up,
                               jobs_per_client=1)
            short = [r.job_id for r in regs if r.t_visible is None]
            if short:
                raise RuntimeError(
                    f"warm-up burst of {size}: {len(short)} jobs never "
                    f"reached their count within {timeout_s:.0f}s "
                    f"(first: {short[:3]}, errors: "
                    f"{[r.error for r in regs if r.error][:2]})")
            n += len(regs)
        return n

    def window(self, seconds: float) -> dict:
        """The measured window.  Returns the registrations sent in it and
        the allocs of theirs that were visible when it closed."""
        t = self.traffic
        wait_s = float(t["wait_timeout_s"])
        first = len(self.sent)
        at_close = {}

        def on_close() -> None:
            with self._sent_lock:
                regs = self.sent[first:]
            at_close["allocs"] = sum(
                r.asked if r.t_visible is not None else r.seen
                for r in regs)
            at_close["t"] = time.monotonic()

        t_start = time.monotonic()
        t_end = t_start + seconds
        self.watcher.at(t_end, on_close)
        if t["loop"] == "closed":
            regs = self.closed(int(t["clients"]), t_end, t_end + wait_s)
        else:
            regs = self.open(float(t["rate_per_s"]), t["arrivals"],
                             int(t["senders"]), t_start, seconds,
                             t_end + wait_s)
        while "allocs" not in at_close:      # the mark runs at t_end
            time.sleep(0.005)
        return {"t_start": t_start, "t_end": t_end, "regs": regs,
                "allocs_at_close": at_close["allocs"],
                "close_late_s": at_close["t"] - t_end}
