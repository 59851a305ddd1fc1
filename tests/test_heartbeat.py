"""Heartbeat TTL failure detector tests (reference: nomad/heartbeat.go)."""
import threading
import time

from nomad_tpu import mock, structs
from nomad_tpu.server.heartbeat import NodeHeartbeater, rate_scaled_interval
from nomad_tpu.server.server import Server


def test_rate_scaled_interval():
    assert rate_scaled_interval(0.0, 10.0, 100) == 10.0
    assert rate_scaled_interval(50.0, 10.0, 100) == 10.0
    # 10_000 nodes at 50/s -> 200s between heartbeats per node
    assert rate_scaled_interval(50.0, 10.0, 10_000) == 200.0


def test_heartbeater_expiry_and_reset():
    expired = []
    hb = NodeHeartbeater(expired.append, min_heartbeat_ttl_s=0.05,
                         heartbeat_grace_s=0.0)
    hb.set_enabled(True)
    assert hb.reset("n1") is not None
    time.sleep(0.3)
    assert expired == ["n1"]
    assert hb.active() == 0
    # a node that keeps heartbeating never expires
    hb.reset("n2")
    for _ in range(6):
        time.sleep(0.04)
        hb.reset("n2")
    assert "n2" not in expired
    hb.clear("n2")
    time.sleep(0.2)
    assert "n2" not in expired


def test_heartbeater_disabled_is_inert():
    expired = []
    hb = NodeHeartbeater(expired.append, min_heartbeat_ttl_s=0.05,
                         heartbeat_grace_s=0.0)
    assert hb.reset("n1") is None   # not leader: no timer
    hb.set_enabled(True)
    hb.reset("n1")
    hb.set_enabled(False)           # leadership lost: timers cancelled
    time.sleep(0.3)
    assert expired == []


def test_missed_heartbeats_reschedule_allocs():
    """Stop a node's heartbeats: the leader marks it down and its allocs
    are rescheduled onto the live node with no manual status call
    (VERDICT r1 missing #4 done-criterion)."""
    server = Server(num_workers=2, min_heartbeat_ttl_s=0.3,
                    heartbeat_grace_s=0.2)
    server.start()
    try:
        n_live = mock.node()
        n_dead = mock.node()
        # best-fit prefers the fuller node: enlarge the live node so the
        # job lands on the doomed (default-size) node first
        n_live.node_resources.cpu = n_live.node_resources.cpu * 4
        n_live.node_resources.memory_mb = n_live.node_resources.memory_mb * 4
        server.register_node(n_live)
        server.register_node(n_dead)

        stop = threading.Event()
        kill_dead = threading.Event()   # set -> n_dead stops heartbeating

        def beat():
            while not stop.is_set():
                server.node_heartbeat(n_live.id)
                if not kill_dead.is_set():
                    server.node_heartbeat(n_dead.id)
                time.sleep(0.05)
        t = threading.Thread(target=beat, daemon=True)
        t.start()

        job = mock.job()
        tg = job.task_groups[0]
        tg.count = 1
        for task in tg.tasks:
            task.resources.networks = []
        server.register_job(job)

        deadline = time.time() + 30
        placed = None
        while time.time() < deadline:
            allocs = server.store.allocs_by_job("default", job.id)
            live = [a for a in allocs if not a.terminal_status()]
            if live:
                placed = live[0]
                break
            time.sleep(0.05)
        assert placed is not None, "initial placement never happened"
        assert placed.node_id == n_dead.id, \
            "fixture broken: job should land on the fuller (doomed) node"

        # n_dead goes silent -> down -> alloc replaced on n_live
        kill_dead.set()
        deadline = time.time() + 30
        ok = False
        while time.time() < deadline:
            node = server.store.node_by_id(n_dead.id)
            allocs = server.store.allocs_by_job("default", job.id)
            replacement = [a for a in allocs
                           if a.node_id == n_live.id
                           and not a.terminal_status()]
            if node.status == structs.NODE_STATUS_DOWN and replacement:
                ok = True
                break
            time.sleep(0.05)
        assert ok, "node never marked down / alloc never rescheduled"
        stop.set()
    finally:
        server.stop()


def test_down_node_resuming_heartbeats_restored_to_ready():
    server = Server(num_workers=0, min_heartbeat_ttl_s=0.1,
                    heartbeat_grace_s=0.05)
    server.start()
    try:
        n = mock.node()
        server.register_node(n)
        # unknown nodes get no TTL: they must re-register
        assert server.node_heartbeat("no-such-node") is None
        deadline = time.time() + 10
        while time.time() < deadline:
            if server.store.node_by_id(n.id).status == \
                    structs.NODE_STATUS_DOWN:
                break
            time.sleep(0.02)
        assert server.store.node_by_id(n.id).status == \
            structs.NODE_STATUS_DOWN
        # heartbeats resume -> restored to ready
        assert server.node_heartbeat(n.id) is not None
        assert server.store.node_by_id(n.id).status == \
            structs.NODE_STATUS_READY
    finally:
        server.stop()


# --- a deadline that comes due is held against the fleet tracked then ---
# (heartbeat.py's one departure from heartbeat.go; every case asserts the
# upper limit as well as the hold).  The clock is the argument of
# `_pop_expired_locked`, so nothing sleeps; the defaults (10 s, 50 a
# second, 10 s grace) put the watcher's own deadlines out of the test's way.

def _tracking(n, **kw):
    hb = NodeHeartbeater(lambda nid: None, **kw)
    hb.set_enabled(True)
    t0 = time.monotonic()
    told = {f"n{i}": hb.reset(f"n{i}") for i in range(n)}
    return hb, t0, told, time.monotonic()


def _expired_by(hb, now):
    with hb._cv:
        return set(hb._pop_expired_locked(now))


def test_a_burst_that_stays_small_is_held_to_the_references_deadlines():
    """400 nodes register at once and fall silent: each is told 10 to
    20 s and is down at what it was told plus the grace, never later."""
    hb, t0, told, t1 = _tracking(400)
    try:
        assert all(10.0 <= ttl < 20.0 for ttl in told.values())
        assert _expired_by(hb, t0 + 19.9) == set()
        early = {nid for nid, ttl in told.items() if ttl < 12.0}
        late = {nid for nid, ttl in told.items() if ttl > 13.0}
        gone = _expired_by(hb, t1 + 22.5)
        assert early <= gone and not (late & gone)
        _expired_by(hb, t1 + 30.0)
        assert hb.active() == 0
    finally:
        hb.set_enabled(False)


def test_a_told_ttl_is_the_references_whatever_follows():
    hb, t0, told, t1 = _tracking(2000)
    try:
        for i in (0, 499, 500, 1000, 1999):
            base = rate_scaled_interval(50.0, 10.0, i)
            assert base <= told[f"n{i}"] < 2 * base
    finally:
        hb.set_enabled(False)


def test_an_early_node_of_a_grown_fleet_is_held_to_that_fleet_and_no_longer():
    """The first nodes of 10,000 that register in a burst are told 10 to
    20 s; when that comes due the fleet's nodes are told 200 to 400 s,
    and the early ones are held to the same: not down at 30 s, down by
    2 x 200 + 10 s after they were last heard."""
    hb, t0, told, t1 = _tracking(10_000)
    try:
        early = [f"n{i}" for i in range(500)]
        assert all(told[nid] < 20.0 for nid in early)
        assert _expired_by(hb, t1 + 30.0) == set()
        assert hb.active() == 10_000
        with hb._cv:
            for nid in early:
                heard, stagger = hb._heard[nid]
                assert hb._deadlines[nid] <= heard + 2 * 200.0 + 10.0
                assert hb._deadlines[nid] >= heard + 200.0 + 10.0
        # the upper limit, for the whole silent fleet
        _expired_by(hb, t1 + 410.0)
        assert hb.active() == 0
    finally:
        hb.set_enabled(False)


def test_a_heartbeat_after_the_hold_is_told_the_fleets_ttl():
    hb, t0, told, t1 = _tracking(10_000)
    try:
        _expired_by(hb, t1 + 30.0)          # n0 is held, not down
        assert 200.0 <= hb.reset("n0") < 400.0
    finally:
        hb.set_enabled(False)


def test_a_fleet_that_shrinks_expires_no_node_before_it_was_told():
    hb, t0, told, t1 = _tracking(10_000)
    try:
        last = "n9999"
        assert told[last] >= 199.0
        for i in range(9_999):
            hb.clear(f"n{i}")
        # alone now, and a fleet of one is allowed 10 to 20 s: it still
        # keeps what it was told
        assert _expired_by(hb, t0 + told[last] + 9.9) == set()
        assert _expired_by(hb, t1 + told[last] + 10.0) == {last}
    finally:
        hb.set_enabled(False)
