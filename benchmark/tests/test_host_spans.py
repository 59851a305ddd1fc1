"""The program's spans in a profiler trace (`host_spans.py`): the
attribution rule and the attributed share on a hand-built profile,
exactly; a trace recorded before the program wrote spans
(`recorded_v5e.xplane.pb`) still reads `unattributed` with the gaps
`xplane.idle_gaps` lists; on a trace recorded with them
(`recorded_v5e_spans.xplane.pb`, a 4 s window of c2-binpack-10k.closed1
on a TPU v5e, PR 25) the host's and the device's clocks agree."""
import os
from types import SimpleNamespace as NS

import pytest

import host_spans
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb")
RECORDED_SPANS = os.path.join(HERE, "recorded_v5e_spans.xplane.pb")


def ev(name, start_s, dur_s):
    return NS(name=name, start_ns=int(start_s * 1e9),
              duration_ns=int(dur_s * 1e9))


def profile(worker=(), applier=(), other=()):
    """One chip with ops at 0..1, 3..4, 9..10 and 10.5..11 s: gaps of
    2 s (1..3), 5 s (4..9) and 0.5 s (10..10.5)."""
    ops = [ev("%fusion.1 = fusion(...)", 0.0, 1.0),
           ev("%fusion.1 = fusion(...)", 3.0, 1.0),
           ev("%fusion.1 = fusion(...)", 9.0, 1.0),
           ev("%fusion.1 = fusion(...)", 10.5, 0.5)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[
        NS(name="worker", events=list(worker)),
        NS(name="applier", events=list(applier)),
        NS(name="python", events=list(other))])
    return NS(planes=[host, dev])


def spans_on_two_threads():
    return profile(
        worker=[
            # 0.5..3.5: invoke, with a child 1.0..1.5 and a wait
            # 1.5..3.2 in which the applier works
            ev("nomad.worker.invoke_scheduler", 0.5, 3.0),
            ev("nomad.sched.prepare", 1.0, 0.5),
            ev("nomad.plan.result_wait", 1.5, 1.7),
            # 4.5..8.5: nothing but a wait
            ev("nomad.worker.dequeue_wait", 4.5, 4.0)],
        applier=[
            ev("nomad.plan.raft_apply", 1.6, 1.2),      # 1.6..2.8
            ev("nomad.fsm.apply", 1.8, 0.9)],           # 1.8..2.7
        other=[ev("PjitFunction(solve_kernel)", 0.0, 11.0)])


def test_leaves_are_the_innermost_open_span_of_each_thread():
    got = sorted((n, round(s, 3), round(e, 3)) for n, s, e in
                 host_spans.leaves(host_spans.host_spans(
                     spans_on_two_threads())))
    assert got == sorted([
        ("worker.invoke_scheduler", 0.5, 1.0),
        ("sched.prepare", 1.0, 1.5),
        ("plan.result_wait", 1.5, 3.2),
        ("worker.invoke_scheduler", 3.2, 3.5),
        ("worker.dequeue_wait", 4.5, 8.5),
        ("plan.raft_apply", 1.6, 1.8),
        ("fsm.apply", 1.8, 2.7),
        ("plan.raft_apply", 2.7, 2.8)])


def test_a_gap_is_named_by_the_leaf_that_covers_most_of_it():
    p = spans_on_two_threads()
    # longest first, as xplane.idle_gaps lists them.  5 s gap (4..9):
    # only a wait overlaps it, so the wait names it.  2 s gap (1..3):
    # the wait covers 1.5 s of it, yet a working span beats a wait, and
    # of those fsm.apply (0.9 s) beats sched.prepare (0.5 s) and
    # plan.raft_apply (0.3 s).  0.5 s gap (10..10.5): no program span.
    assert host_spans.idle_gaps(p) == [
        ["worker.dequeue_wait", pytest.approx(5.0)],
        ["fsm.apply", pytest.approx(2.0)],
        ["unattributed", pytest.approx(0.5)]]


def test_gap_lengths_and_order_are_those_of_xplane_idle_gaps():
    p = spans_on_two_threads()
    assert [g for _l, g in host_spans.idle_gaps(p, k=2)] == \
        [g for _l, g in xplane.idle_gaps(p, k=2)]


def test_idle_attributed_share_exactly():
    # idle 7.5 s; covered: 1..3 whole (2 s, inside invoke 0.5..3.5) and
    # 4.5..8.5 of the 5 s gap (4 s); the last gap not at all
    assert host_spans.idle_attributed_share(spans_on_two_threads()) == \
        pytest.approx(100.0 * 6.0 / 7.5)
    assert host_spans.idle_attributed_share(profile()) == 0.0
    obs = NS(profile=None)
    assert host_spans.reduce_idle_attributed({}, obs) is None


def test_no_device_plane_gives_nothing():
    p = spans_on_two_threads()
    p.planes = p.planes[:1]
    assert host_spans.idle_gaps(p) == []
    assert host_spans.idle_attributed_share(p) is None
    assert host_spans.modules_ending_inside(
        p, "^jit_solve_kernel", ("solve.fetch",)) == (0, 0)


def test_a_trace_without_program_spans_reads_unattributed():
    prof = xplane.load(RECORDED)
    assert host_spans.host_spans(prof) == []
    mine, theirs = host_spans.idle_gaps(prof), xplane.idle_gaps(prof)
    assert mine == theirs and len(mine) == 10
    assert {label for label, _g in mine} == {"unattributed"}
    assert host_spans.idle_attributed_share(prof) == 0.0


def test_the_recorded_spans_share_the_devices_clock():
    """Every run of the solve kernel on the chip ends while the host is
    inside the span that launched it or the span that waits for it."""
    prof = xplane.load(RECORDED_SPANS)
    inside, n = host_spans.modules_ending_inside(
        prof, "^jit_solve_kernel", ("solve.dispatch", "solve.fetch"))
    assert n >= 5 and inside == n
    names = {name for _t, name, _s, _e in host_spans.host_spans(prof)}
    assert {"solve.pack", "solve.dispatch", "solve.fetch", "solve.fixup",
            "sched.reconcile", "sched.prepare", "sched.plan_build",
            "plan.submit", "plan.raft_apply", "fsm.apply", "eval.update",
            "worker.dequeue_wait", "job.register"} <= names
    gaps = host_spans.idle_gaps(prof)
    assert "unattributed" not in {label for label, _g in gaps}
    assert host_spans.idle_attributed_share(prof) > 95.0


def test_a_collection_takes_its_stretch_from_every_other_thread():
    """The collector stops all threads: the 2 s gap (1..3) has a pause
    of 1.2 s on the applier's line under a working span of the worker
    that was open all along, and the pause names it."""
    p = profile(
        worker=[ev("nomad.solve.fixup", 0.5, 3.0)],
        applier=[ev("nomad.plan.snapshot", 1.5, 1.4),
                 ev("nomad.gc.pause", 1.6, 1.2)])
    got = sorted((n, round(s, 3), round(e, 3)) for n, s, e in
                 host_spans.leaves(host_spans.host_spans(p)))
    assert got == sorted([
        ("solve.fixup", 0.5, 1.6), ("solve.fixup", 2.8, 3.5),
        ("plan.snapshot", 1.5, 1.6), ("gc.pause", 1.6, 2.8),
        ("plan.snapshot", 2.8, 2.9)])
    # 1.2 s of the gap against 0.8 s left to solve.fixup
    assert host_spans.idle_gaps(p, k=2)[1] == [
        "gc.pause", pytest.approx(2.0)]
