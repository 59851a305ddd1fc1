"""Where a thread's time goes when it is not running: the CPU reading of
every layer span, the hand-off of a plan back to its worker, the
background loops' spans, and a span end that wakes no second thread.

  * a span that sleeps reads no CPU and adds its sleep to
    `thread.offcpu_s`; a spinning one reads CPU near its wall;
  * a wait span adds nothing off the CPU, and a child's seconds are not
    counted again in its parent's own time;
  * an unreadable schedstat raises nothing and writes no
    `thread.runq_s`;
  * one job through a real server: one `plan.resume_wait` and one
    `plan.respond` a plan, and `bg.pass` samples from `drainer.pass`;
  * rows that no reader asks for leave the drainer asleep, and a reader
    still sees every one.
"""
import os
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.utils import tracing
from nomad_tpu.utils.metrics import MetricsRegistry, global_metrics
from nomad_tpu.utils.tracing import FlightRecorder, global_tracer


@pytest.fixture
def registry(monkeypatch):
    """A registry of this test's own for the spans to write to."""
    reg = MetricsRegistry()
    monkeypatch.setattr(tracing, "global_metrics", reg)
    return reg


def _counter(reg, key):
    return reg.dump()["counters"].get(key, 0.0)


def _sample(reg, key):
    s = reg.dump()["samples"].get(key)
    return (s["count"], s["sum"]) if s else (0, 0.0)


def _spin(cpu_s):
    """Run until this thread has had `cpu_s` seconds of a core: on a
    loaded host the wall it takes may be longer."""
    t_end = time.thread_time() + cpu_s
    while time.thread_time() < t_end:
        pass


def test_a_sleeping_span_reads_no_cpu_and_adds_its_sleep_off_cpu(registry):
    rec = FlightRecorder(enabled=True)
    with rec.layer("test.sleep", "t-sleep") as sp:
        time.sleep(0.02)
    assert sp.dur_s >= 0.02
    assert sp.cpu_s < 0.005
    count, cpu = _sample(registry, "cpu.test.sleep")
    assert count == 1 and cpu == pytest.approx(sp.cpu_s, abs=2e-6)
    assert _counter(registry, "thread.offcpu_s") == pytest.approx(
        sp.dur_s - sp.cpu_s, abs=2e-6)
    row, = rec.get("t-sleep")
    assert row["cpu_s"] == sp.cpu_s


def test_a_spinning_span_reads_cpu_near_its_wall(registry):
    rec = FlightRecorder(enabled=True)
    c0 = time.thread_time()
    with rec.layer("test.spin", key="test.spun") as sp:
        _spin(0.03)
    c1 = time.thread_time()
    # the span reads the CPU the block spun, within the wall; what a
    # loaded host took the core away for is the rest, off the CPU
    assert 0.03 <= sp.cpu_s <= c1 - c0
    assert sp.cpu_s <= sp.dur_s
    assert _counter(registry, "thread.offcpu_s") == pytest.approx(
        sp.dur_s - sp.cpu_s, abs=2e-6)
    # the sample is named after the key where one is given
    count, cpu = _sample(registry, "cpu.test.spun")
    assert count == 1 and cpu == pytest.approx(sp.cpu_s, abs=2e-6)
    assert _sample(registry, "cpu.test.spin")[0] == 0


def test_a_wait_span_adds_nothing_off_cpu(registry):
    rec = FlightRecorder(enabled=True)
    with rec.layer("test.idle_wait") as sp:
        time.sleep(0.02)
    assert sp.dur_s >= 0.02
    assert _sample(registry, "cpu.test.idle_wait")[0] == 1
    assert _counter(registry, "thread.offcpu_s") == 0.0


@pytest.mark.parametrize("child", ["test.child", "test.child_wait"])
def test_a_childs_seconds_are_not_counted_again_in_its_parent(
        registry, child):
    """A parent that holds a sleeping child: the child's sleep counts
    once off the CPU where the child works, and not at all where it
    waits; the parent's CPU reading holds the child's."""
    rec = FlightRecorder(enabled=True)
    with rec.layer("test.parent") as parent:
        _spin(0.005)
        with rec.layer(child) as kid:
            time.sleep(0.02)
            _spin(0.002)
    off = _counter(registry, "thread.offcpu_s")
    parent_own_off = (parent.dur_s - kid.dur_s) - (parent.cpu_s - kid.cpu_s)
    kid_off = kid.dur_s - kid.cpu_s
    assert kid_off >= 0.019
    if child == "test.child":
        # the sleep once, not twice: the parent's own and the child's
        # add up to the parent's whole
        assert off == pytest.approx(max(parent_own_off, 0.0) + kid_off,
                                    abs=1e-5)
        assert off == pytest.approx(parent.dur_s - parent.cpu_s, abs=1e-5)
    else:
        # the waiting child's sleep not at all
        assert off == pytest.approx(max(parent_own_off, 0.0), abs=1e-5)
        assert off < parent.dur_s - parent.cpu_s - 0.018
    assert parent.cpu_s >= kid.cpu_s
    assert parent.dur_s >= kid.dur_s


@pytest.mark.parametrize("content", [None, b"not a schedstat line\n"])
def test_an_unreadable_schedstat_raises_nothing_and_writes_no_runq(
        registry, monkeypatch, tmp_path, content):
    path = tmp_path / "schedstat"
    if content is not None:
        path.write_bytes(content)
    monkeypatch.setattr(tracing, "SCHEDSTAT", str(path))
    rec = FlightRecorder(enabled=True)
    errors = []

    def spans():
        try:
            for _ in range(3):
                with rec.layer("test.outer"):
                    with rec.layer("test.inner"):
                        pass
        except Exception as e:          # pragma: no cover - the failure
            errors.append(e)

    t = threading.Thread(target=spans)
    t.start()
    t.join()
    assert not errors
    assert not rec._schedstat
    assert "thread.runq_s" not in registry.dump()["counters"]
    assert _sample(registry, "span.test.outer")[0] == 3


def test_runq_is_the_run_delay_gained_across_the_outermost_span(
        registry, monkeypatch, tmp_path):
    """A schedstat whose `run_delay` grows by 3 ms inside the spans: the
    counter takes it once, from the outermost span's two reads, on any
    host."""
    path = tmp_path / "schedstat"
    path.write_bytes(b"5000000 1000000 7\n")
    monkeypatch.setattr(tracing, "SCHEDSTAT", str(path))
    rec = FlightRecorder(enabled=True)
    with rec.layer("test.outer"):
        with rec.layer("test.inner"):
            path.write_bytes(b"9000000 4000000 9\n")
    assert rec._schedstat
    assert _counter(registry, "thread.runq_s") == pytest.approx(0.003)


@pytest.mark.skipif(not os.path.exists(tracing.SCHEDSTAT),
                    reason="no per-thread schedstat on this host")
def test_a_readable_schedstat_writes_runq_around_the_outermost_span_only(
        registry, monkeypatch):
    rec = FlightRecorder(enabled=True)
    reads = []
    real = rec._run_delay

    def counted():
        v = real()
        reads.append(v)
        return v

    monkeypatch.setattr(rec, "_run_delay", counted)
    with rec.layer("test.outer"):
        with rec.layer("test.inner"):
            _spin(0.002)
    assert len(reads) == 2 and all(v is not None for v in reads)
    assert _counter(registry, "thread.runq_s") == pytest.approx(
        reads[1] - reads[0])
    assert reads[1] >= reads[0] >= 0.0


@pytest.mark.parametrize("spill_limit, n, woken", [
    (tracing.DEFAULT_TRACE_SPILL, 64, False),
    (16, 12, True)])
def test_rows_no_reader_asks_for_leave_the_drainer_asleep(
        spill_limit, n, woken):
    """A span's end parks its row and wakes nobody until the spill is
    half full; a reader drains first and sees every row."""
    rec = FlightRecorder(depth=8, enabled=True)
    rec.spill_limit = spill_limit
    wakes = []
    real_set = rec._spill_event.set

    def counted_set():
        wakes.append(1)
        real_set()

    rec._spill_event.set = counted_set
    for i in range(n):
        with rec.layer("test.row", "t-rows", i=i):
            pass
    assert bool(wakes) is woken
    rows = rec.get("t-rows")
    assert [r["attrs"]["i"] for r in rows] == list(range(n))
    assert all(isinstance(r["cpu_s"], float) for r in rows)
    # a plain event carries no CPU reading
    rec.event("t-rows", "test.event")
    assert rec.get("t-rows")[-1]["cpu_s"] is None


def _samples():
    return {k: v["count"] for k, v in global_metrics.dump()["samples"].items()}


def test_a_plan_is_handed_back_once_and_the_drainer_is_a_background_pass(
        monkeypatch):
    """One job through a real server: every plan the worker submitted
    has one `plan.respond` on the applier and one `plan.resume_wait` on
    the worker; the drainer's passes are `drainer.pass` spans under the
    shared `bg.pass` sample."""
    from nomad_tpu.server.server import Server
    opened = []
    real_layer = global_tracer.layer

    def layer(name, trace_id="", key=None, **attrs):
        opened.append((name, key))
        return real_layer(name, trace_id, key, **attrs)

    monkeypatch.setattr(global_tracer, "layer", layer)
    server = Server(num_workers=1)
    server.start()
    try:
        for _ in range(4):
            n = mock.node()
            n.node_resources.cpu = 8000
            n.node_resources.memory_mb = 32768
            server.register_node(n)
        before = _samples()
        job = mock.job()
        job.task_groups[0].count = 3
        job.task_groups[0].tasks[0].resources.networks = []
        ev = server.register_job(job)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            e = server.store.eval_by_id(ev.id)
            if e is not None and e.terminal_status() \
                    and server.broker.stats()["total_unacked"] == 0:
                break
            time.sleep(0.02)
        assert server.store.eval_by_id(ev.id).status == "complete"
        # the drainer wakes on the job's writes; give it one more tick
        time.sleep(0.3)
        after = _samples()
    finally:
        server.stop()

    def grew(key):
        return after.get(key, 0) - before.get(key, 0)

    plans = grew("worker.submit_plan")
    assert plans >= 1
    assert grew("span.plan.respond") == plans
    assert grew("span.plan.resume_wait") == plans
    rows = global_tracer.get(ev.id)
    assert len([r for r in rows if r["name"] == "plan.resume_wait"]) == plans
    drainer = opened.count(("drainer.pass", "bg.pass"))
    assert drainer >= 1
    assert grew("bg.pass") >= 1
    assert "span.drainer.pass" not in after
    # a background pass writes no recorder row
    assert not [r for r in rows if r["name"] == "drainer.pass"]
