"""Layer spans (ISSUE 25): one `global_tracer.layer()` per layer
boundary feeds the flight recorder, the metrics registry and a running
`jax.profiler` trace.

  * one eval through a real server: every span of the single-eval path
    and of the plan submit is ONE recorder row with the expected parent,
    inside its parent's interval, and a sample of the registry;
  * a fused round of two evals: the `fleet.*` spans, and one
    `fleet.replay` per replay;
  * recorder off: the samples are still written, no row is;
  * under a profiler session the spans are `nomad.*` events of the
    host plane, nested, one line per thread;
  * the collector's pauses are `gc.pause`, marked without a lock.
"""
import gc
import glob
import os
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.utils.metrics import global_metrics
from nomad_tpu.utils.tracing import global_tracer

INVOKE = "worker.invoke_scheduler"
#: span name -> (name of the parent span; None: opened with no layer
#: span around it, so chained on the trace's tail like a stage; sample
#: key where an older name is kept)
SINGLE_PATH = {
    "worker.wait_index": (None, "worker.wait_for_index"),
    INVOKE: (None, "worker.invoke_scheduler_service"),
    "sched.snapshot": (INVOKE, None),
    "sched.reconcile": (INVOKE, None),
    "sched.prepare": (INVOKE, None),
    "solve.pack": (INVOKE, None),
    "solve.dispatch": (INVOKE, None),
    "solve.fetch": (INVOKE, None),
    "solve.fixup": (INVOKE, None),
    "solve.d2h": ("solve.fixup", None),
    "solve.class_elig": ("solve.fixup", None),
    "sched.plan_build": (INVOKE, None),
    "plan.submit": (INVOKE, "worker.submit_plan"),
    "plan.result_wait": ("plan.submit", None),
    # a wait recorded by `waited`: the applier's respond -> the worker
    # running again, a stage of the trace
    "plan.resume_wait": (None, None),
    "plan.refresh": ("plan.submit", None),
    "eval.update": (INVOKE, None),
    # the applier's thread
    "plan.queue_wait": (None, None),
    "plan.snapshot": (None, None),
    "plan.evaluate": (None, "plan.evaluate"),
    "plan.raft_apply": (None, None),
    "fsm.apply": ("plan.raft_apply", None),
    "plan.respond": (None, None),
    "plan.commit_wait": ("plan.respond", "plan.apply"),
}
#: sample key -> its span: written with no recorder row, because no
#: eval id is known where they run (`broker.wait` is no span at all:
#: the age the broker computes at dequeue)
SAMPLES_ONLY = {"span.job.register": "job.register",
                "broker.wait": "broker.wait",
                "span.worker.dequeue_wait": "worker.dequeue_wait"}
FLEET = ["fleet.reconcile", "fleet.pack", "fleet.dispatch", "fleet.fetch",
         "fleet.fixup", "fleet.d2h", "fleet.class_elig", "fleet.plan_build",
         "fleet.apply"]


def _key(name):
    return SINGLE_PATH[name][1] or "span." + name


def _samples():
    return {k: (v["count"], v["sum"])
            for k, v in global_metrics.dump()["samples"].items()}


def _grew(before, after, key):
    """(count, sum) the sample grew by."""
    c0, s0 = before.get(key, (0, 0.0))
    c1, s1 = after.get(key, (0, 0.0))
    return c1 - c0, s1 - s0


def _server(n_nodes=8, paused=False):
    from nomad_tpu.server.server import Server
    server = Server(num_workers=1)
    if paused:
        # before start: a worker already inside its dequeue would take
        # the first eval alone
        server.workers[0].paused.set()
    server.start()
    for _ in range(n_nodes):
        n = mock.node()
        n.node_resources.cpu = 8000
        n.node_resources.memory_mb = 32768
        server.register_node(n)
    return server


def _job(count=2):
    job = mock.job()
    job.task_groups[0].count = count
    job.task_groups[0].tasks[0].resources.networks = []
    return job


def _wait_terminal(server, eval_ids, timeout=60.0):
    """Until every eval is terminal AND acked: the terminal status is
    written inside `worker.invoke_scheduler`, the ack follows once that
    span has ended and its row is recorded."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        evs = [server.store.eval_by_id(i) for i in eval_ids]
        if all(e is not None and e.terminal_status() for e in evs) \
                and server.broker.stats()["total_unacked"] == 0:
            return
        time.sleep(0.02)
    raise AssertionError(f"evals not terminal: {eval_ids}")


def _one_eval(server):
    ev = server.register_job(_job())
    _wait_terminal(server, [ev.id])
    assert server.store.eval_by_id(ev.id).status == "complete"
    return ev.id


@pytest.fixture(scope="module")
def single():
    """One eval on the single-eval path: its trace, and what the
    registry's samples grew by while it ran."""
    server = _server()
    try:
        before = _samples()
        eval_id = _one_eval(server)
    finally:
        server.stop()
    return {"spans": global_tracer.get(eval_id), "before": before,
            "after": _samples()}


@pytest.mark.parametrize("name", sorted(SINGLE_PATH))
def test_single_path_span_is_one_row_with_its_parent_and_a_sample(
        single, name):
    spans = single["spans"]
    rows = [s for s in spans if s["name"] == name]
    assert len(rows) == 1, (name, [s["name"] for s in spans])
    row = rows[0]
    by_id = {s["span_id"]: s for s in spans}
    want_parent = SINGLE_PATH[name][0]
    parent = by_id.get(row["parent_id"])
    if want_parent is None:
        # a stage of the trace: its parent, if any, is an earlier span
        assert parent is None or parent["t_start"] <= row["t_start"]
    else:
        assert parent is not None and parent["name"] == want_parent, \
            (name, parent and parent["name"])
        assert parent["t_start"] <= row["t_start"]
        assert row["t_end"] <= parent["t_end"]
    count, total = _grew(single["before"], single["after"], _key(name))
    assert count >= 1, _key(name)
    assert total >= row["dur_s"] - 1e-4


@pytest.mark.parametrize("key", sorted(SAMPLES_ONLY))
def test_single_path_samples_without_a_row(single, key):
    count, _total = _grew(single["before"], single["after"], key)
    assert count >= 1, key
    assert not [s for s in single["spans"]
                if s["name"] == SAMPLES_ONLY[key]]


def test_superseded_solve_attributes_are_gone(single):
    solve = [s for s in single["spans"] if s["name"] == "solve"]
    assert len(solve) == 1
    gone = {"dispatch_wall_s", "fetch_wall_s", "kernel_wall_s"}
    assert not gone & set(solve[0]["attrs"])
    assert not [k for k in solve[0]["attrs"] if k.startswith("stage_")]


def test_fused_round_spans_and_one_replay_span_per_replay(monkeypatch):
    """Two evals pooled behind a paused worker drain as one fused round;
    one of them is made to come back unfinished once, so it replays on
    the single-eval path inside the round's apply stage."""
    from nomad_tpu.scheduler.generic import GenericScheduler
    server = _server(paused=True)
    real = GenericScheduler._finalize
    sent_back = []

    def finalize(self, progress):
        done, err = real(self, progress)
        if not sent_back and err is None:
            sent_back.append(self.eval.id)
            return False, None
        return done, err

    monkeypatch.setattr(GenericScheduler, "_finalize", finalize)
    try:
        ids = [server.register_job(_job()).id for _ in range(2)]
        assert server.broker.ready_count() == 2
        before = _samples()
        replays0 = global_metrics.dump()["counters"].get(
            "coordinator.replays", 0)
        server.workers[0].paused.clear()
        _wait_terminal(server, ids)
    finally:
        server.stop()
    after = _samples()
    for name in FLEET:
        assert _grew(before, after, "span." + name)[0] == 1, name
    replays = global_metrics.dump()["counters"]["coordinator.replays"] \
        - replays0
    assert replays == 1 and len(sent_back) == 1
    assert _grew(before, after, "span.fleet.replay")[0] == replays
    # the replay is a row of the replayed eval's trace alone, and the
    # single-eval spans of the replay are its children
    traces = {i: global_tracer.get(i) for i in ids}
    rows = {i: [s for s in traces[i] if s["name"] == "fleet.replay"]
            for i in ids}
    assert sorted(len(r) for r in rows.values()) == [0, 1]
    replay = rows[sent_back[0]][0]
    children = [s["name"] for s in traces[sent_back[0]]
                if s["parent_id"] == replay["span_id"]]
    assert children == ["worker.wait_index", INVOKE]
    # every member has the fused solve span and its own reconcile rows
    for i in ids:
        names = [s["name"] for s in traces[i]]
        assert "sched.reconcile" in names and "sched.prepare" in names
        solve = [s for s in traces[i] if s["name"] == "solve"]
        assert solve[0]["attrs"]["fused"] is True


def test_a_wait_is_one_stamp_for_its_row_and_its_sample(monkeypatch):
    """`waited` cuts the row and the sample at ONE read of the clock:
    however long the recorder takes to open the row (a lock, an id; a
    preempted thread under load), the row is no longer than its own
    sample."""
    real = global_tracer.stage

    def slow_stage(*a, **kw):
        time.sleep(0.005)
        return real(*a, **kw)

    monkeypatch.setattr(global_tracer, "stage", slow_stage)
    before = _samples()
    global_tracer.waited("test.wait", time.monotonic() - 0.010,
                         "trace-of-a-wait")
    row, = global_tracer.get("trace-of-a-wait")
    count, total = _grew(before, _samples(), "span.test.wait")
    assert count == 1 and row["name"] == "test.wait"
    assert row["dur_s"] >= 0.010
    assert total == pytest.approx(row["dur_s"], abs=2e-6)   # 6 digits


def test_recorder_off_still_writes_the_samples(monkeypatch):
    monkeypatch.setattr(global_tracer, "enabled", False)
    server = _server()
    try:
        before = _samples()
        eval_id = _one_eval(server)
    finally:
        server.stop()
    after = _samples()
    assert global_tracer.get(eval_id) is None
    for name in SINGLE_PATH:
        assert _grew(before, after, _key(name))[0] >= 1, name


def test_spans_are_host_events_of_a_profiler_trace(tmp_path):
    """The shared clock: under a `jax.profiler` session every layer span
    is a `nomad.<name>` event of the host plane, on the line of the
    thread that ran it, nested as the spans nest."""
    import jax
    from jax.profiler import ProfileData
    server = _server()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _one_eval(server)
        gc.collect(1)       # the server's start watches the collector
    finally:
        jax.profiler.stop_trace()
        server.stop()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    profile = ProfileData.from_file(path)
    lines = {}                  # line -> [(name, start, end)]
    for plane in profile.planes:
        for i, ln in enumerate(plane.lines):    # every line is "python"
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in ln.events if e.name.startswith("nomad.")]
            if evs:
                assert plane.name == "/host:CPU", plane.name
                lines[(plane.name, i)] = evs
    of = {}                     # span name -> the lines it is on
    for key, evs in lines.items():
        for name, _s, _e in evs:
            of.setdefault(name, set()).add(key)
    assert "nomad.gc.pause" in of
    worker_line, = of["nomad." + INVOKE]
    applier_line, = of["nomad.plan.evaluate"]
    assert worker_line != applier_line
    for name, (parent, _key_) in SINGLE_PATH.items():
        if name in ("plan.queue_wait", "plan.resume_wait"):
            # a wait recorded by `waited`: no profiler event
            assert "nomad." + name not in of
            continue
        on = of["nomad." + name]
        if parent in (INVOKE, "solve.fixup") or name == INVOKE:
            assert on == {worker_line}, name
        elif name in ("plan.snapshot", "plan.evaluate", "plan.raft_apply",
                      "fsm.apply", "plan.respond", "plan.commit_wait"):
            assert on == {applier_line}, name
    # nesting on the worker's line: children inside the parent's event
    evs = lines[worker_line]
    (_n, s0, e0), = [e for e in evs if e[0] == "nomad." + INVOKE]
    for name, (parent, _key_) in SINGLE_PATH.items():
        if parent == INVOKE:
            (_n, s, e), = [x for x in evs if x[0] == "nomad." + name]
            assert s0 <= s and e <= e0, name


def test_collector_pauses_are_sampled_and_marked_without_a_lock():
    """A collection can start wherever an allocation tips it, under the
    registry's or the recorder's lock too: the callback must take
    neither.  The sample is written when the next layer span ends."""
    global_tracer.watch_gc()
    global_tracer.watch_gc()            # idempotent: one callback
    assert gc.callbacks.count(global_tracer._on_gc) == 1
    before = _samples()

    def collect_under_the_locks():
        with global_metrics._lock:
            gc.collect(1)
        with global_tracer._tail_lock:
            gc.collect(2)
        gc.collect(0)                   # the youngest: not marked

    t = threading.Thread(target=collect_under_the_locks, daemon=True)
    t.start()
    t.join(timeout=30.0)
    assert not t.is_alive(), "the gc callback blocked on a lock"
    with global_tracer.layer("test.after_gc"):
        pass
    assert _grew(before, _samples(), "span.gc.pause")[0] >= 2


@pytest.mark.parametrize("generation, full", [(1, 0), (2, 1)])
def test_a_full_collection_is_also_sampled_as_gc_full(generation, full):
    """`span.gc.full` counts the generation-2 collections among
    `span.gc.pause`'s, so how many full collections a window held is a
    reading (`gc_full_collections`).  The collector is held off around
    the one collection asked for, so none of its own is counted."""
    global_tracer.watch_gc()
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        with global_tracer.layer("test.drain_gc"):
            pass
        before = _samples()
        gc.collect(generation)
        with global_tracer.layer("test.after_gc"):
            pass
        after = _samples()
    finally:
        if was_enabled:
            gc.enable()
    assert _grew(before, after, "span.gc.pause")[0] == 1
    count, seconds = _grew(before, after, "span.gc.full")
    assert count == full and (seconds > 0) == bool(full)
