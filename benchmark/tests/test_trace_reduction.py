"""The reduction from a trace to device metrics: on a hand-built
profile (the arithmetic, exactly) and on a small recorded trace of the
served path on a TPU v5e (`recorded_v5e.xplane.pb`: the names the
patterns are written against).

And the program's spans in such a trace (`host_spans.py`): the
attribution rule and the attributed share on a hand-built profile,
exactly; a trace recorded before the program wrote spans
(`recorded_v5e.xplane.pb`) reads `unattributed`; on a trace recorded
with them (`recorded_v5e_spans.xplane.pb`, a 4 s window of
c2-binpack-10k.closed1 on a TPU v5e, PR 25) the host's and the device's
clocks agree."""
import os
from types import SimpleNamespace as NS

import pytest

import bytes_models
import cluster
import host_spans
import layers
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb")
RECORDED_SPANS = os.path.join(HERE, "recorded_v5e_spans.xplane.pb")


def ev(name, start_s, dur_s):
    return NS(name=name, start_ns=int(start_s * 1e9),
              duration_ns=int(dur_s * 1e9))


def fake_profile():
    """One chip, 10 s: two runs of jit_solve_kernel (a while op spanning
    its body's ops) and one of another program."""
    ops = [
        ev("%while.1 = while(...)", 1.0, 2.0),            # 1..3
        ev("%nomad_wave_topk.3 = custom-call(...)", 1.1, 0.5),
        ev("%fusion.7 = fusion(...)", 1.7, 1.0),          # inside while
        ev("%fusion.9 = fusion(...)", 3.0, 0.5),          # 3..3.5
        ev("%while.1 = while(...)", 6.0, 1.0),            # 6..7
        ev("%copy.2 = copy(...)", 8.0, 0.25),             # other program
    ]
    modules = [ev("jit_solve_kernel(123)", 0.9, 2.7),      # 0.9..3.6
               ev("jit_solve_kernel(123)", 5.9, 1.2),
               ev("jit__delta_scatter(9)", 7.9, 0.4)]
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops),
        NS(name="XLA Modules", events=modules)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("whatever", 0.0, 10.0)])])
    return NS(planes=[host, dev])


def test_busy_is_the_union_of_op_intervals():
    assert xplane.busy_seconds(fake_profile()) == pytest.approx(3.75)


def test_program_time_by_module_pattern():
    p = fake_profile()
    assert xplane.program_seconds(p, "^jit_solve_kernel") == \
        pytest.approx(3.5)
    assert xplane.program_seconds(p, "^jit__delta") == pytest.approx(0.25)
    assert xplane.program_seconds(p, "^jit_nothing") is None


def test_top_ops_and_idle_gaps():
    p = fake_profile()
    top = xplane.top_ops(p, k=2)
    assert top[0][0] == "while.1" and top[0][1] == pytest.approx(3.0)
    gaps = host_spans.idle_gaps(p, k=2)
    assert gaps[0] == ["unattributed", pytest.approx(2.5)]


def test_no_device_plane_gives_nothing():
    p = fake_profile()
    p.planes = p.planes[:1]
    assert xplane.busy_seconds(p) is None
    assert xplane.program_seconds(p, "^jit_solve_kernel") is None
    obs = layers.Observed(profile=p, harness={"window_s": 10.0})
    assert layers.read_metric("device_idle_share", obs) is None


def test_idle_share_and_roofline_arithmetic():
    cfg = cluster.load_config("c3-affinity-spread-10k")
    obs = layers.Observed(
        counters={"solver.solve.tpu": 10.0, "solver.waves": 40.0},
        harness={"window_s": 10.0, "placements_visible": 640.0},
        profile=fake_profile(), config=cfg,
        peaks=layers.load_peaks("TPU v5 lite"))
    assert layers.read_metric("device_idle_share", obs) == \
        pytest.approx(62.5)
    assert layers.read_metric("device_ms_per_wave", obs) == \
        pytest.approx(3.5 / 40 * 1000)
    # c3 names rack and zone beside the base planes: 13 planes
    want_bytes = 10 * 13 * 10_000 * 4 + 640 * 4
    assert bytes_models.least_solve_bytes(cfg, 10, 640) == want_bytes
    share = layers.read_metric("wave_loop_roofline", obs)
    assert share == pytest.approx(100 * (want_bytes / 819e9) / 3.5)
    assert 0 < share < 100
    # a reader with nothing to read returns nothing, never 0
    obs.counters = {}
    assert layers.read_metric("wave_loop_roofline", obs) is None
    assert layers.read_metric("waves_per_solve", obs) is None


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        layers.load_peaks("TPU v9 imaginary")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_trace_of_the_served_path():
    prof = xplane.load(RECORDED)
    assert [p.name for p in xplane.device_planes(prof)] == ["/device:TPU:0"]
    busy = xplane.busy_seconds(prof)
    solve = xplane.program_seconds(prof, "^jit_solve_kernel")
    assert busy is not None and busy > 0
    assert solve is not None and 0 < solve <= busy + 1e-9
    assert xplane.top_ops(prof, 3)


def test_every_per_layer_entry_has_its_file_and_no_file_lacks_an_entry():
    import json
    with open(os.path.join(os.path.dirname(cluster.HERE),
                           "BENCHMARK.json"), encoding="utf-8") as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    files = {f[:-len(".json")] for f in os.listdir(
        os.path.join(cluster.HERE, "layer_metrics"))}
    assert files == set(entries)
    for name, entry in entries.items():
        spec = layers.load_metric(name)
        assert spec["name"] == name and spec["layer"] == entry["layer"]
        assert spec["reducer"] in layers.REDUCERS


# ------------------------------------------------ the program's spans
def spans_profile(worker=(), applier=(), other=()):
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
    return spans_profile(
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
    # longest first.  5 s gap (4..9):
    # only a wait overlaps it, so the wait names it.  2 s gap (1..3):
    # the wait covers 1.5 s of it, yet a working span beats a wait, and
    # of those fsm.apply (0.9 s) beats sched.prepare (0.5 s) and
    # plan.raft_apply (0.3 s).  0.5 s gap (10..10.5): no program span.
    assert host_spans.idle_gaps(p) == [
        ["worker.dequeue_wait", pytest.approx(5.0)],
        ["fsm.apply", pytest.approx(2.0)],
        ["unattributed", pytest.approx(0.5)]]


def test_the_k_longest_gaps_come_longest_first():
    p = spans_on_two_threads()
    assert [g for _l, g in host_spans.idle_gaps(p, k=2)] == \
        [pytest.approx(5.0), pytest.approx(2.0)]


def test_idle_attributed_share_exactly():
    # idle 7.5 s; covered: 1..3 whole (2 s, inside invoke 0.5..3.5) and
    # 4.5..8.5 of the 5 s gap (4 s); the last gap not at all
    assert host_spans.idle_attributed_share(spans_on_two_threads()) == \
        pytest.approx(100.0 * 6.0 / 7.5)
    assert host_spans.idle_attributed_share(spans_profile()) == 0.0
    obs = NS(profile=None)
    assert host_spans.reduce_idle_attributed({}, obs) is None


def test_idle_attributed_share_the_metric_counts_working_spans_only():
    # of the 7.5 s idle, some thread's innermost span is a working one
    # for 1.0..1.5 and 1.6..2.8 of the first gap (1.7 s); the 5 s gap
    # holds a wait alone, which covers nothing under `waits: false`
    assert layers.load_metric("idle_attributed_share")["waits"] is False
    obs = layers.Observed(profile=spans_on_two_threads())
    assert layers.read_metric("idle_attributed_share", obs) == \
        pytest.approx(100.0 * 1.7 / 7.5)
    assert host_spans.reduce_idle_attributed({"waits": True}, obs) == \
        pytest.approx(100.0 * 6.0 / 7.5)
    assert layers.read_metric("idle_attributed_share",
                              layers.Observed()) is None


def test_no_device_plane_gives_no_gap_and_no_share():
    p = spans_on_two_threads()
    p.planes = p.planes[:1]
    assert host_spans.idle_gaps(p) == []
    assert host_spans.idle_attributed_share(p) is None
    assert host_spans.modules_ending_inside(
        p, "^jit_solve_kernel", ("solve.fetch",)) == (0, 0)


def test_a_trace_without_program_spans_reads_unattributed():
    prof = xplane.load(RECORDED)
    assert host_spans.host_spans(prof) == []
    gaps = host_spans.idle_gaps(prof)
    assert len(gaps) == 10
    assert {label for label, _g in gaps} == {"unattributed"}
    assert [g for _l, g in gaps] == sorted((g for _l, g in gaps),
                                           reverse=True)
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
    p = spans_profile(
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
