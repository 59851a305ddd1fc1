"""The reduction from a trace to device metrics: on a hand-built
profile (the arithmetic, exactly) and on a small recorded trace of the
served path on a TPU v5e (`recorded_v5e.xplane.pb`: the names the
patterns are written against)."""
import os
from types import SimpleNamespace as NS

import pytest

import bytes_models
import cluster
import layers
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb")


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
    gaps = xplane.idle_gaps(p, k=2)
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
