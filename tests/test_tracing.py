"""Flight recorder + metrics observability (ISSUE 10).

Three layers:

  * the metrics registry under concurrent writers (snapshot
    consistency, the per-namespace cardinality cap + overflow counter,
    prometheus text exposition);
  * span parentage/ordering over a REAL server: every solved eval's
    trace is a complete ordered chain create -> admit -> ... -> solve
    (device counters attached) -> plan apply, for singleton AND fused
    batches; shed evals carry a shed-cause span;
  * the mesh event log against a scripted grow/move/fail/recover
    sequence — events must match ElasticShardedResidentSolver's
    reshard counters.
"""
import json
import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.utils.metrics import MetricsRegistry, OVERFLOW_KEY
from nomad_tpu.utils.tracing import (FlightRecorder, MeshEventLog,
                                     NULL_SPAN, global_tracer)

#: lifecycle stage names in their required order (subsequence match:
#: traces may carry extra stages — nack retries, reconcile events)
LIFECYCLE = ["create", "admit", "broker.enqueue", "broker.dequeue",
             "worker.batch", "solve"]


# ------------------------------------------------------------------
# metrics registry: concurrency, cardinality cap, prometheus
# ------------------------------------------------------------------
def test_metrics_concurrent_writers_snapshot_consistency():
    reg = MetricsRegistry(max_keys_per_ns=4096)
    N_THREADS, N_OPS = 8, 500
    stop = threading.Event()
    snapshots = []

    def writer(i):
        for k in range(N_OPS):
            reg.incr_counter("t.counter")
            reg.incr_counter(f"t.counter_{i}")
            reg.set_gauge(f"t.gauge_{i}", float(k))
            reg.add_sample("t.sample", 0.001 * (k % 7))

    def reader():
        while not stop.is_set():
            snapshots.append(reg.dump())

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(N_THREADS)]
    rd = threading.Thread(target=reader)
    rd.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rd.join()

    final = reg.dump()
    assert final["counters"]["t.counter"] == N_THREADS * N_OPS
    for i in range(N_THREADS):
        assert final["counters"][f"t.counter_{i}"] == N_OPS
        assert final["gauges"][f"t.gauge_{i}"] == float(N_OPS - 1)
    s = final["samples"]["t.sample"]
    assert s["count"] == N_THREADS * N_OPS
    # every mid-flight snapshot is internally consistent: monotone
    # shared counter, sample count never exceeds the final
    last = 0.0
    for snap in snapshots:
        c = snap["counters"].get("t.counter", 0.0)
        assert c >= last
        last = c
        smp = snap["samples"].get("t.sample")
        if smp:
            assert 0 <= smp["count"] <= N_THREADS * N_OPS
            assert smp["min"] >= 0.0


def test_metrics_cardinality_cap_and_overflow():
    reg = MetricsRegistry(max_keys_per_ns=8)
    for i in range(50):
        reg.incr_counter(f"boom.key_{i}")
    d = reg.dump()
    boom = [k for k in d["counters"] if k.startswith("boom.")]
    assert len(boom) == 8
    assert d["counters"][OVERFLOW_KEY] == 42
    # existing keys keep working past the cap
    reg.incr_counter("boom.key_0")
    assert reg.dump()["counters"]["boom.key_0"] == 2
    # other namespaces are not starved by boom's explosion
    reg.set_gauge("calm.gauge", 1.0)
    assert reg.dump()["gauges"]["calm.gauge"] == 1.0
    # samples and gauges share the guard
    for i in range(20):
        reg.set_gauge(f"g.k{i}", 1.0)
        reg.add_sample(f"s.k{i}", 0.5)
    d = reg.dump()
    assert len([k for k in d["gauges"] if k.startswith("g.")]) == 8
    assert len([k for k in d["samples"] if k.startswith("s.")]) == 8


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.incr_counter("worker.dequeue_eval", 3)
    reg.set_gauge("broker.ready_count", 7.0)
    reg.add_sample("plan.evaluate", 0.25)
    reg.add_sample("plan.evaluate", 0.75)
    text = reg.prometheus()
    lines = text.splitlines()
    assert "# TYPE worker_dequeue_eval counter" in lines
    assert "worker_dequeue_eval 3" in lines
    assert "# TYPE broker_ready_count gauge" in lines
    assert "broker_ready_count 7" in lines
    assert "# TYPE plan_evaluate summary" in lines
    assert 'plan_evaluate{quantile="0.5"} 0.75' in lines
    assert any(ln.startswith('plan_evaluate{quantile="0.99"} ')
               for ln in lines)
    assert "plan_evaluate_sum 1" in lines
    assert "plan_evaluate_count 2" in lines
    # exposition charset: nothing outside [a-zA-Z0-9_:{}="., ]
    for ln in lines:
        if not ln.startswith("#"):
            name = ln.split("{")[0].split(" ")[0]
            assert all(c.isalnum() or c in "_:" for c in name), ln


# ------------------------------------------------------------------
# recorder unit behavior: ring bound, disabled path, explicit parents
# ------------------------------------------------------------------
def test_recorder_ring_bound_and_disabled_noop():
    rec = FlightRecorder(depth=2, enabled=True)
    for tid in ("a", "b", "c"):
        rec.event(tid, "create")
    assert rec.get("a") is None          # evicted whole
    assert rec.get("b") is not None and rec.get("c") is not None
    assert rec.stats()["dropped_traces"] == 1

    off = FlightRecorder(depth=2, enabled=False)
    assert off.span("t", "x") is NULL_SPAN
    off.event("t", "y")
    assert off.get("t") is None
    assert off.stats()["spans"] == 0


def test_explicit_parent_and_stage_chaining():
    rec = FlightRecorder(depth=8, enabled=True)
    root = rec.span("t1", "root")
    root.end()
    with rec.stage("t1", "second"):
        pass
    rec.event("t1", "third", parent=root.span_id)
    spans = rec.get("t1")
    by_name = {s["name"]: s for s in spans}
    assert by_name["root"]["parent_id"] == ""
    assert by_name["second"]["parent_id"] == by_name["root"]["span_id"]
    # explicit parent overrides the tail chain
    assert by_name["third"]["parent_id"] == by_name["root"]["span_id"]


def test_jsonl_sink(tmp_path):
    sink = tmp_path / "trace.jsonl"
    rec = FlightRecorder(depth=4, enabled=True, sink_path=str(sink))
    rec.event("t1", "create", job_id="j1")
    with rec.span("t1", "solve", waves=3):
        pass
    # sink writes ride the spill drainer (ISSUE 17) — flush for the read
    rec.flush()
    rows = [json.loads(ln) for ln in sink.read_text().splitlines()]
    assert [r["name"] for r in rows] == ["create", "solve"]
    assert rows[1]["attrs"]["waves"] == 3
    assert rows[0]["trace_id"] == "t1"


# ------------------------------------------------------------------
# span parentage/ordering over a real server
# ------------------------------------------------------------------
def _wait_terminal(server, eval_ids, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        evs = [server.store.eval_by_id(i) for i in eval_ids]
        if all(e is not None and e.terminal_status() for e in evs):
            return evs
        time.sleep(0.05)
    raise AssertionError(
        "evals not terminal: "
        + str([(e.id[:8], e.status) for e in evs
               if e is None or not e.terminal_status()]))


def _assert_span_chain(spans, eval_id):
    """Every solved eval has a complete ordered span chain and every
    span's parent is an earlier span of the same trace (or a root)."""
    names = [s["name"] for s in spans]
    it = iter(names)
    missing = [want for want in LIFECYCLE
               if not any(got == want for got in it)]
    assert not missing, (eval_id, "missing stages", missing, names)
    ids = set()
    for s in spans:                      # spans sorted by t_start
        assert s["parent_id"] == "" or s["parent_id"] in ids, \
            (eval_id, s["name"], "parent not an earlier span", names)
        ids.add(s["span_id"])
    # stage ordering follows the lifecycle (first occurrence)
    pos = {}
    for i, n in enumerate(names):
        pos.setdefault(n, i)
    for a, b in zip(LIFECYCLE, LIFECYCLE[1:]):
        assert pos[a] < pos[b], (eval_id, a, b, names)


@pytest.mark.parametrize("seed", [0, 1])
def test_span_chain_property_random_eval_batch(seed):
    """Property: a random batch of evals through a real server — every
    solved eval reconstructs a complete, ordered span chain; fused and
    singleton solves both carry device wave counters."""
    from nomad_tpu.server.server import Server

    rng = np.random.default_rng(seed)
    server = Server(num_workers=1)
    # pause the worker so the registered evals pool in the broker and
    # drain as ONE fused batch when unpaused (deterministic fusion)
    server.workers[0].paused.set()
    server.start()
    for i in range(8):
        n = mock.node()
        n.node_resources.cpu = 8000
        n.node_resources.memory_mb = 32768
        server.register_node(n)
    pre_ids = []
    for i in range(int(rng.integers(4, 7))):
        job = mock.job()
        job.task_groups[0].count = int(rng.integers(1, 4))
        job.task_groups[0].tasks[0].resources.networks = []
        pre_ids.append(server.register_job(job).id)
    assert server.broker.ready_count() == len(pre_ids)
    server.workers[0].paused.clear()
    _wait_terminal(server, pre_ids)
    # one more job alone in the queue: the singleton dequeue path
    solo = mock.job()
    solo.task_groups[0].tasks[0].resources.networks = []
    ev = server.register_job(solo)
    _wait_terminal(server, pre_ids + [ev.id])
    server.stop()

    fused_seen = singleton_seen = False
    for eid in pre_ids + [ev.id]:
        st = server.store.eval_by_id(eid)
        if st.status != "complete":
            continue
        spans = global_tracer.get(eid)
        assert spans is not None, f"no trace for {eid}"
        _assert_span_chain(spans, eid)
        solve = [s for s in spans if s["name"] == "solve"]
        assert solve, eid
        a = solve[0]["attrs"]
        # the device wave counters attached to the solve span
        assert a["waves"] >= 1 and a["rescore_waves"] >= 0
        assert "modeled_bytes_total" in a
        assert a["backend"] in ("host", "device")
        if a.get("fused"):
            fused_seen = True
            assert a["fused_batch"] >= 2
        else:
            singleton_seen = True
    assert singleton_seen, "no singleton solve recorded"
    assert fused_seen, "no fused-batch solve recorded"


def test_shed_eval_carries_shed_cause_span():
    """An admission-shed eval's trace records the shed cause."""
    from nomad_tpu.server.server import Server

    server = Server(num_workers=0,
                    serving_config={"max_pending": 1})
    server.start()          # no workers: the queue never drains
    ids = []
    for i in range(3):
        job = mock.job()
        job.task_groups[0].tasks[0].resources.networks = []
        ev = server.register_job(job)
        ids.append(ev.id)
    assert server.blocked_evals.shed_count() >= 1
    causes = []
    for eid in ids:
        spans = global_tracer.get(eid) or []
        for s in spans:
            if s["name"] == "admit" and not s["attrs"]["admitted"]:
                causes.append(s["attrs"]["shed_cause"])
    assert causes and all(c == "max_pending" for c in causes)
    server.stop()


def test_broker_gauges_export_without_workers():
    """The server-side metrics timer keeps broker gauges fresh while
    every worker is paused/absent (the worker loop was the only
    exporter before)."""
    from nomad_tpu.server.server import Server
    from nomad_tpu.utils.metrics import global_metrics

    server = Server(num_workers=0)
    server.start()
    job = mock.job()
    job.task_groups[0].tasks[0].resources.networks = []
    server.register_job(job)
    assert server.broker.ready_count() == 1
    # poison the gauge so only THIS server's timer can restore it
    global_metrics.set_gauge("broker.ready_count", -1.0)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        g = global_metrics.dump()["gauges"]
        if g.get("broker.ready_count") == 1.0:
            break
        time.sleep(0.1)
    assert global_metrics.dump()["gauges"]["broker.ready_count"] == 1.0
    server.stop()


# ------------------------------------------------------------------
# HTTP surface
# ------------------------------------------------------------------
def test_trace_http_endpoints():
    from nomad_tpu.api.http_server import HTTPAgentServer, HTTPError
    from nomad_tpu.server.server import Server

    server = Server(num_workers=1)
    server.start()
    n = mock.node()
    n.node_resources.cpu = 8000
    server.register_node(n)
    job = mock.job()
    job.task_groups[0].tasks[0].resources.networks = []
    ev = server.register_job(job)
    _wait_terminal(server, [ev.id])
    api = HTTPAgentServer(server)     # dispatch directly; no socket
    code, body, _ = api.dispatch("GET", f"/v1/trace/{ev.id}", None)
    assert code == 200
    assert [s["name"] for s in body["spans"]][:2] == ["create", "admit"]
    code, body, _ = api.dispatch("GET", "/v1/traces?limit=5", None)
    assert code == 200 and body["stats"]["enabled"]
    assert any(t["trace_id"] == ev.id for t in body["traces"])
    code, body, _ = api.dispatch("GET", "/v1/agent/events", None)
    assert code == 200 and isinstance(body["events"], list)
    with pytest.raises(HTTPError) as ei:
        api.dispatch("GET", "/v1/trace/no-such-trace", None)
    assert ei.value.code == 404
    server.stop()


# ------------------------------------------------------------------
# mesh event log vs a scripted grow/move/fail/recover sequence
# ------------------------------------------------------------------
def test_mesh_event_log_matches_reshard_counters():
    from nomad_tpu.parallel.sharded import (ElasticShardedResidentSolver,
                                            make_node_mesh)
    from tests.test_sharded_resident import make_ask, make_node

    log = MeshEventLog(depth=64)
    nodes = [make_node(i) for i in range(24)]
    es = ElasticShardedResidentSolver(
        nodes, [make_ask()], gp=4, kp=16, mesh=make_node_mesh(4),
        event_log=log)
    assert len(log) == 0

    grew = es.grow_tiles(1)
    lay = es._layout
    t = next(t for t in range(lay.n_tiles)
             if lay.owner[t] >= 0 and t not in grew)
    dst = next(s for s in range(lay.n_shards)
               if s != lay.owner[t] and lay.free_slots(s) > 0)
    es.move_tile(t, dst)
    shrunk = es.shrink_tiles(1)          # at least the grown tile is empty
    assert len(shrunk) == 1
    fail = next(int(lay.owner[t2]) for t2 in range(lay.n_tiles)
                if lay.owner[t2] >= 0)
    lost = es.fail_shard(fail)
    rec_bytes = es.recover()

    events = log.events()
    kinds = [e["kind"] for e in events]
    assert kinds == ["grow", "move", "shrink", "fail", "recover"]
    by_kind = {e["kind"]: e for e in events}
    rc = es.reshard_counters
    assert by_kind["grow"]["n_tiles"] == rc["tiles_grown"] == 1
    assert by_kind["grow"]["tiles"] == grew
    assert by_kind["grow"]["bytes"] > 0
    assert by_kind["move"]["tile"] == t
    assert by_kind["move"]["dst_shard"] == dst
    assert by_kind["move"]["bytes"] == rc["last_reshard_bytes"]
    assert by_kind["shrink"]["tiles"] == shrunk
    assert by_kind["fail"]["shard"] == fail
    assert by_kind["fail"]["tiles"] == lost
    assert by_kind["recover"]["bytes"] == rec_bytes \
        == rc["last_recovery_bytes"]
    assert by_kind["recover"]["duration_s"] > 0
    assert rc["recoveries"] == 1
    # events are seq-ordered and JSON-serializable (the /v1 surface)
    assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)
    json.dumps(events)

    # supervisor-plane events land in the same log
    from nomad_tpu.parallel.sharded import ElasticMeshSupervisor
    sup = ElasticMeshSupervisor(es)
    sup.register_host("host-a", fail)
    sup.on_fail("host-a")
    sup.on_join("host-a")
    kinds = [e["kind"] for e in log.events()]
    assert kinds[-4:] == ["fail", "supervisor.fail", "recover",
                          "supervisor.recover"]


def test_mesh_event_log_jsonl_sink(tmp_path):
    sink = tmp_path / "mesh.jsonl"
    log = MeshEventLog(depth=8, sink_path=str(sink))
    log.record("grow", tiles=[1], bytes=128)
    log.record("fail", shard=0)
    rows = [json.loads(ln) for ln in sink.read_text().splitlines()]
    assert [r["kind"] for r in rows] == ["grow", "fail"]
    assert rows[0]["bytes"] == 128


def test_mesh_event_log_truthiness_regression():
    """A FRESH (empty) log must still be truthy: with only __len__
    defined, `if event_log:` presence guards were False exactly until
    the first event was recorded — so the first transition of every
    solve was silently dropped.  Emptiness is spelled len(log) == 0."""
    log = MeshEventLog(depth=8)
    assert len(log) == 0
    assert bool(log)                 # empty but present
    recorded = []
    for _ in range(2):
        # the exact call-site shape the bug broke: guard, then record
        if log:
            recorded.append(log.record("grow", tiles=[1]))
    assert len(recorded) == 2        # first event NOT skipped
    assert len(log) == 2 and bool(log)
