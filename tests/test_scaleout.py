"""Scale-out control plane (ISSUE 17): sharded broker equivalence,
cross-worker fused solves through the SolveCoordinator, group-commit
plan applies, and the end-to-end conservation storm on the sharded
paths."""
import random
import threading
import time
import zlib

import pytest

from nomad_tpu import mock, structs
from nomad_tpu.chaos.invariants import InvariantHarness
from nomad_tpu.client.sim import wait_until
from nomad_tpu.scheduler.fleet import SolveCoordinator, process_fleet
from nomad_tpu.server.blocked_evals import BlockedEvals
from nomad_tpu.server.eval_broker import EvalBroker
from nomad_tpu.server.plan_apply import PlanApplier
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.server.server import Server
from nomad_tpu.server.serving import AdmissionController
from nomad_tpu.server.worker import Worker
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import Plan
from nomad_tpu.utils.metrics import global_metrics
from nomad_tpu.utils.tracing import MeshEventLog


# ------------------------------------------------------------------
# Sharded broker: bit-identical terminal states vs the 1-shard broker
# ------------------------------------------------------------------
def _fate_nacks(eid: str) -> int:
    """Eval-keyed fate: how many nacks this eval eats before its ack.
    3 == delivery_limit, so those evals park in the failed queue.
    Keyed on content (not rng-stream order) so the terminal state is
    interleaving-independent — the property the shard count must not
    break."""
    return zlib.crc32(eid.encode()) % 4


def _run_broker_scenario(seed: int, shards: int):
    """Drive the SAME seeded op script (enqueue/shed/dequeue/ack/nack/
    readmit) against an S-shard broker; assert per-job serialization
    and at-least-once along the way, return {eval_id: terminal}."""
    rng = random.Random(seed)
    broker = EvalBroker(nack_delay_s=30.0, initial_nack_delay_s=0.001,
                        delivery_limit=3, shards=shards)
    broker.set_enabled(True)
    be = BlockedEvals(broker)
    be.set_enabled(True)
    adm = AdmissionController(max_pending=8, protect_priority=101,
                              brownout_high=0.9, brownout_low=0.5,
                              brownout_after_s=0.001,
                              ns_rate=500.0, ns_burst=50.0)
    jobs = [f"job-{i}" for i in range(6)]
    ingress = {}                  # id -> eval
    in_flight = {}                # id -> (eval, token)
    nacks_done = {}
    acked = set()
    made = 0

    def resolve(eid, tok):
        """Apply the eval's predetermined fate to one delivery."""
        if nacks_done.get(eid, 0) < _fate_nacks(eid):
            nacks_done[eid] = nacks_done.get(eid, 0) + 1
            assert broker.nack(eid, tok) is None
        else:
            assert broker.ack(eid, tok) is None
            acked.add(eid)

    for step in range(300):
        op = rng.random()
        if op < 0.5:
            ev = mock.eval_(job_id=jobs[rng.randrange(len(jobs))],
                            priority=rng.choice([30, 50, 70, 100]))
            # pinned ids: the same script must offer the same evals to
            # every shard count for the terminal states to compare
            ev.id = f"ev-{seed}-{made:04d}"
            made += 1
            ingress[ev.id] = ev
            if adm.offer(ev, broker.ready_count()):
                broker.enqueue(ev)
            else:
                be.shed(ev)
        elif op < 0.75:
            batch = broker.dequeue_batch(["service"],
                                         rng.randint(1, 4), 0.0)
            jobs_in_flight = {ingress[i].job_id for i in in_flight}
            for ev, tok in batch:
                assert ev.job_id not in jobs_in_flight, \
                    "two in-flight evals for one job"
                jobs_in_flight.add(ev.job_id)
                in_flight[ev.id] = (ev, tok)
        elif op < 0.9:
            for eid in sorted(in_flight):
                ev, tok = in_flight.pop(eid)
                resolve(eid, tok)
        else:
            q = adm.readmit_quota(broker.ready_count(), batch=4)
            for ev in be.pop_shed(q):
                broker.enqueue(ev)

    # drain to quiescence applying each eval's fate
    deadline = time.monotonic() + 20.0
    failed_parked = set()
    while time.monotonic() < deadline:
        for ev in be.pop_shed(1000):
            broker.enqueue(ev)
        batch = broker.dequeue_batch(["service"], 8, 0.02)
        for ev, tok in batch:
            resolve(ev.id, tok)
        fb = broker.dequeue_batch(["_failed"], 8, 0.0)
        for ev, tok in fb:
            failed_parked.add(ev.id)
            assert broker.ack(ev.id, tok) is None
        for eid in sorted(in_flight):
            ev, tok = in_flight.pop(eid)
            resolve(eid, tok)
        st = broker.stats()
        if (not batch and not fb and be.shed_count() == 0
                and st["total_ready"] == 0 and st["total_unacked"] == 0
                and st["total_waiting"] == 0
                and st["total_blocked"] == 0):
            break
    duplicates = {d.id for d in be.get_duplicates()}
    lost = set(ingress) - (acked | failed_parked | duplicates)
    assert not lost, f"lost evals: {sorted(lost)[:5]} (of {len(lost)})"

    terminal = {}
    for eid in ingress:
        if eid in failed_parked:
            terminal[eid] = "failed"
        elif eid in acked:
            terminal[eid] = "acked"
        else:
            terminal[eid] = "duplicate"
    return terminal


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sharded_broker_terminal_states_bit_identical(seed):
    """The same seeded interleaving against 1, 2, and 8 shards ends in
    bit-identical per-eval terminal states: sharding changes WHERE an
    eval queues, never its at-least-once outcome."""
    base = _run_broker_scenario(seed, 1)
    # the fates the scenario was built around actually exercised both
    # terminal lanes
    assert "failed" in base.values() and "acked" in base.values()
    for shards in (2, 8):
        assert _run_broker_scenario(seed, shards) == base


def test_sharded_broker_routing_and_stats():
    b = EvalBroker(shards=4)
    b.set_enabled(True)
    evs = [mock.eval_(job_id=f"job-{i}") for i in range(32)]
    for ev in evs:
        b.enqueue(ev)
    st = b.stats()
    assert st["shards"] == 4
    assert sum(st["ready_by_shard"]) == 32
    assert st["total_ready"] == 32
    # routing is stable: an eval's shard never changes
    for ev in evs:
        assert b.shard_of(ev) is b.shard_of(ev)
    # a worker with a home shard still drains everyone (work stealing)
    got = b.dequeue_batch(["service"], 32, 0.5, home=1)
    assert len(got) == 32
    for ev, tok in got:
        b.ack(ev.id, tok)
    assert b.stats()["total_unacked"] == 0


# ------------------------------------------------------------------
# SolveCoordinator: fused placements == serialized singles
# ------------------------------------------------------------------
def _dc_pinned_cluster(server, n):
    """One node per datacenter, one job pinned to each dc: placement is
    forced, so fused and serialized solves must agree exactly."""
    nodes, jobs = [], []
    for i in range(n):
        node = mock.node(datacenter=f"dc-{i}")
        node.id = f"node-{i:02d}-0000-0000-0000-000000000000"
        server.register_node(node)
        nodes.append(node)
        job = mock.job(datacenters=[f"dc-{i}"])
        job.id = f"job-dc-{i}"
        job.task_groups[0].count = 2
        jobs.append(job)
    return nodes, jobs


def _placements(server, jobs):
    return {j.id: sorted(a.node_id
                         for a in server.store.allocs_by_job("default", j.id)
                         if not a.terminal_status())
            for j in jobs}


def test_paused_coordinator_fusion_matches_serialized_singles():
    """Two workers' batches held on a paused coordinator, then released
    as ONE fused round, place exactly what solving every eval singly
    places — the determinism hook the coordinator exists to prove."""
    n_jobs = 6

    # control: serialized single-eval solves
    control = Server(num_workers=0)
    control.start()
    try:
        _nodes, jobs = _dc_pinned_cluster(control, n_jobs)
        for j in jobs:
            control.register_job(j)
        batch = control.broker.dequeue_batch(["service"], n_jobs, 1.0)
        assert len(batch) == n_jobs
        w = Worker(control, ["service"])
        for pair in batch:
            process_fleet(control, w, [pair])
        expect = _placements(control, jobs)
        assert all(len(v) == 2 for v in expect.values())
    finally:
        control.stop()

    # fused: two workers submit halves to a paused coordinator
    server = Server(num_workers=0)
    server.start()
    try:
        _nodes, jobs = _dc_pinned_cluster(server, n_jobs)
        for j in jobs:
            server.register_job(j)
        batch = server.broker.dequeue_batch(["service"], n_jobs, 1.0)
        assert len(batch) == n_jobs
        coord = SolveCoordinator(server)
        coord.pause()
        workers = [Worker(server, ["service"], index=i) for i in range(2)]
        threads = [
            threading.Thread(
                target=coord.submit,
                args=(workers[k], batch[k * n_jobs // 2:
                                        (k + 1) * n_jobs // 2]))
            for k in range(2)]
        for t in threads:
            t.start()
        assert wait_until(lambda: coord.pending() == 2, timeout=5.0)
        rounds0 = global_metrics.dump()["counters"].get(
            "coordinator.cross_worker_rounds", 0)
        coord.resume()
        for t in threads:
            t.join(timeout=30.0)
            assert not t.is_alive()
        got = _placements(server, jobs)
        # node ids were pinned identically on both servers, so the
        # placement maps compare bit-for-bit
        assert got == expect
        assert server.broker.stats()["total_unacked"] == 0
        counters = global_metrics.dump()["counters"]
        assert counters.get("coordinator.cross_worker_rounds", 0) > rounds0
    finally:
        server.stop()


def test_coordinator_relays_solve_error_to_every_submitter():
    server = Server(num_workers=0)
    server.start()
    try:
        coord = SolveCoordinator(server)
        coord.pause()
        errors = []

        def submit():
            ev = mock.eval_(job_id="nope")
            try:
                # a bogus token: process_fleet's broker calls survive,
                # but the scheduler fails on the missing job and the
                # eval is nacked — force harder with a raising server
                coord.submit(None, [(ev, "0.bogus")])
            except Exception as exc:
                errors.append(exc)

        # make the fused solve raise for certain
        class _Boom:
            def __getattr__(self, name):
                raise RuntimeError("boom")
        coord.server = _Boom()
        threads = [threading.Thread(target=submit) for _ in range(2)]
        for t in threads:
            t.start()
        assert wait_until(lambda: coord.pending() == 2, timeout=5.0)
        coord.resume()
        for t in threads:
            t.join(timeout=10.0)
        assert len(errors) == 2, "both submitters must see the error"
    finally:
        server.stop()


def test_submitter_waits_out_a_slow_round_and_abandons_only_orphans(
        monkeypatch):
    """A round slower than the submit patience (a cold TPU compile) must
    not make its submitters nack evals the drain leader still holds —
    that hands them to a second scheduler.  Only a batch no leader will
    ever take (paused coordinator) is withdrawn and handed back."""
    from nomad_tpu.scheduler import fleet
    monkeypatch.setattr(fleet, "SUBMIT_PATIENCE_S", 0.05)
    release = threading.Event()
    solved = []

    def slow_dispatch(_server, _worker, combined):
        release.wait(10.0)
        return combined

    coord = SolveCoordinator(
        None, max_fused=1, dispatch_fn=slow_dispatch,
        finish_fn=lambda _server, _worker, rnd: solved.append(len(rnd)))
    errors = []

    def submit(tag):
        try:
            coord.submit(None, [(mock.eval_(job_id=tag), "tok")])
        except Exception as exc:
            errors.append(exc)

    # one becomes the leader and sits in the slow round, the other is
    # queued behind it; both outlast many patience slices
    threads = [threading.Thread(target=submit, args=(f"j{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    assert wait_until(lambda: coord.pending() == 1, timeout=5.0)
    time.sleep(0.3)
    assert not errors and all(t.is_alive() for t in threads)
    release.set()
    for t in threads:
        t.join(timeout=10.0)
    assert not errors and solved == [1, 1]

    # no leader will come for this one: withdrawn, raised
    coord.pause()
    submit("orphan")
    assert len(errors) == 1 and isinstance(errors[0], TimeoutError)
    assert coord.pending() == 0
    coord.resume()


# ------------------------------------------------------------------
# Group-commit plan applies
# ------------------------------------------------------------------
def _small_cluster(n=4, cpu=1000):
    store = StateStore()
    nodes = []
    for i in range(n):
        node = mock.node()
        node.node_resources.cpu = cpu
        node.node_resources.memory_mb = 2000
        node.reserved_resources.cpu = 0
        node.reserved_resources.memory_mb = 0
        store.upsert_node(i + 1, node)
        nodes.append(node)
    return store, nodes


def _plan_with(job, node, cpu):
    plan = Plan(job=job)
    a = mock.alloc(job=job, node_id=node.id)
    for tr in a.allocated_resources.tasks.values():
        tr.networks = []
        tr.cpu = cpu
        tr.memory_mb = 100
    plan.node_allocation[node.id] = [a]
    return plan


class _BatchConsensus:
    """Fake raft: one entry per dispatch; a batch of K results lands
    under ONE shared commit index, like the plan_results_batch FSM
    entry."""

    def __init__(self, store, latency_s=0.01):
        self.store = store
        self.latency_s = latency_s
        self.index = 100
        self.batch_sizes = []
        self._lock = threading.Lock()

    def batch_fn(self, items):
        with self._lock:
            self.batch_sizes.append(len(items))
        done = threading.Event()
        box = {}

        def consensus():
            time.sleep(self.latency_s)
            with self._lock:
                self.index += 1
                ix = self.index
            for plan, result in items:
                self.store.upsert_plan_results(ix, result, job=plan.job)
            box["ix"] = ix
            done.set()
        threading.Thread(target=consensus, daemon=True).start()

        def finish(timeout=10.0):
            assert done.wait(timeout)
            return box["ix"]
        return 0, finish

    def single_fn(self, plan, result):
        return self.batch_fn([(plan, result)])


def test_group_commit_batches_queued_plans_into_one_raft_entry():
    """K plans queued back to back ride one consensus entry; every
    member future still gets its OWN result."""
    store, nodes = _small_cluster(n=8, cpu=10_000)
    cons = _BatchConsensus(store)
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, store, None, None,
                          apply_async_fn=cons.single_fn,
                          apply_batch_async_fn=cons.batch_fn,
                          group_commit=8)
    c0 = global_metrics.dump()["counters"]
    jobs = [mock.job() for _ in range(6)]
    # enqueue BEFORE the applier runs: the first _apply_one drains the
    # whole group deterministically
    pendings = [queue.enqueue(_plan_with(jobs[i], nodes[i], 100))
                for i in range(6)]
    applier.start()
    try:
        results = []
        for p in pendings:
            result, err = p.future.wait(10.0)
            assert err is None
            results.append(result)
        # per-plan results preserved: each plan's own single alloc, on
        # its own node, all under one shared commit index
        for i, r in enumerate(results):
            assert list(r.node_allocation) == [nodes[i].id]
            assert sum(len(v) for v in r.node_allocation.values()) == 1
        assert len({r.alloc_index for r in results}) == 1
        assert max(cons.batch_sizes) >= 2, cons.batch_sizes
        # one fsync per dispatch, not per plan
        assert len(cons.batch_sizes) < len(pendings)
        c1 = global_metrics.dump()["counters"]
        assert c1.get("plan.group_commits", 0) > c0.get(
            "plan.group_commits", 0)
        applies = c1.get("plan.raft_applies", 0) - c0.get(
            "plan.raft_applies", 0)
        assert applies == len(cons.batch_sizes)
        # the store saw every alloc exactly once
        live = sum(len([a for a in store.allocs_by_node(n.id)
                        if not a.terminal_status()]) for n in nodes)
        assert live == 6
    finally:
        applier.stop()
        queue.set_enabled(False)


def test_group_commit_intra_batch_conflict_partial_refresh():
    """Two plans for the same node's last capacity land in ONE group:
    the second validates against the first's overlaid result and
    bounces with a refresh index — exactly the pipelined semantics."""
    store, nodes = _small_cluster(n=1, cpu=1000)
    cons = _BatchConsensus(store)
    queue = PlanQueue()
    queue.set_enabled(True)
    applier = PlanApplier(queue, store, None, None,
                          apply_async_fn=cons.single_fn,
                          apply_batch_async_fn=cons.batch_fn,
                          group_commit=8)
    pa = queue.enqueue(_plan_with(mock.job(), nodes[0], 600))
    pb = queue.enqueue(_plan_with(mock.job(), nodes[0], 600))
    applier.start()
    try:
        ra, ea = pa.future.wait(10.0)
        rb, eb = pb.future.wait(10.0)
        assert ea is None and eb is None
        assert sum(len(v) for v in ra.node_allocation.values()) == 1
        assert sum(len(v) for v in rb.node_allocation.values()) == 0
        assert rb.refresh_index
        live = [a for a in store.allocs_by_node(nodes[0].id)
                if not a.terminal_status()]
        assert len(live) == 1
    finally:
        applier.stop()
        queue.set_enabled(False)


def test_group_commit_through_raft_fsm_batch_entry():
    """End to end through a real Server: the plan_results_batch FSM
    entry applies K results identically to K sequential entries."""
    server = Server(num_workers=2,
                    serving_config={"group_commit": 8})
    server.start()
    try:
        for _ in range(6):
            server.register_node(mock.node())
        jobs = []
        for i in range(8):
            job = mock.job()
            job.task_groups[0].count = 2
            jobs.append(job)
            server.register_job(job)
        for job in jobs:
            assert wait_until(
                lambda j=job: len([
                    a for a in server.store.allocs_by_job("default", j.id)
                    if not a.terminal_status()]) == 2,
                timeout=30), job.id
            ev = server.store.evals_by_job("default", job.id)[0]
            assert wait_until(
                lambda e=ev: server.store.eval_by_id(e.id).status ==
                structs.EVAL_STATUS_COMPLETE, timeout=30)
    finally:
        server.stop()


# ------------------------------------------------------------------
# Conservation storm against the sharded broker (chaos harness)
# ------------------------------------------------------------------
def test_sharded_broker_conservation_storm_with_harness():
    """PR 14's invariant harness against the sharded broker under a
    threaded storm: producers racing admission, consumers racing
    dequeue/ack/nack across shards — after the drain every eval is
    accounted for."""
    broker = EvalBroker(nack_delay_s=30.0, initial_nack_delay_s=0.001,
                        delivery_limit=20, shards=4)
    broker.set_enabled(True)
    be = BlockedEvals(broker)
    be.set_enabled(True)
    adm = AdmissionController(max_pending=64, protect_priority=101,
                              brownout_high=0.9, brownout_low=0.5,
                              brownout_after_s=0.001,
                              ns_rate=5000.0, ns_burst=500.0)
    h = InvariantHarness(event_log=MeshEventLog())
    stop = threading.Event()
    acked = set()
    acked_lock = threading.Lock()

    def producer(k):
        rng = random.Random(1000 + k)
        for i in range(60):
            ev = mock.eval_(job_id=f"job-{k}-{i}",
                            priority=rng.choice([30, 50, 70]))
            h.note_enqueued(ev.id)
            if adm.offer(ev, broker.ready_count()):
                broker.enqueue(ev)
            else:
                be.shed(ev)
                h.note_outcome(ev.id, "shed")
            if rng.random() < 0.2:
                time.sleep(0.001)

    def consumer(k):
        rng = random.Random(2000 + k)
        while not stop.is_set():
            batch = broker.dequeue_batch(["service"], 4, 0.02, home=k)
            seen_jobs = set()
            for ev, tok in batch:
                # per-job serialization inside one dequeue
                assert ev.job_id not in seen_jobs
                seen_jobs.add(ev.job_id)
                if rng.random() < 0.8:
                    broker.ack(ev.id, tok)
                    h.note_outcome(ev.id, "acked")
                    with acked_lock:
                        acked.add(ev.id)
                else:
                    broker.nack(ev.id, tok)

    producers = [threading.Thread(target=producer, args=(k,))
                 for k in range(4)]
    consumers = [threading.Thread(target=consumer, args=(k,))
                 for k in range(4)]
    for t in producers + consumers:
        t.start()
    for t in producers:
        t.join(timeout=30.0)
    # drain: readmit shed, let consumers finish the backlog
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        for ev in be.pop_shed(1000):
            broker.enqueue(ev)
        st = broker.stats()
        if (st["total_ready"] == 0 and st["total_unacked"] == 0
                and st["total_waiting"] == 0 and be.shed_count() == 0):
            break
        time.sleep(0.02)
    stop.set()
    for t in consumers:
        t.join(timeout=10.0)
    st = broker.stats()
    assert st["total_ready"] == 0 and st["total_unacked"] == 0 \
        and st["total_waiting"] == 0
    assert h.check_eval_conservation(broker)
    assert h.check_shed_accounting(admission=adm)
    h.raise_if_violated()
    assert len(acked) == 4 * 60


# ------------------------------------------------------------------
# Tier-1 scale-out smoke: 2 shards x 4 workers through the full loop
# ------------------------------------------------------------------
def test_scaleout_smoke_sharded_workers_coordinator():
    """The bench scaleout leg's fast twin: 2 broker shards, 4 workers
    feeding the coordinator, group commit on — every eval terminal,
    broker quiescent, coordinator actually fused."""
    server = Server(serving_config={"broker_shards": 2,
                                    "num_workers": 4,
                                    "group_commit": 8,
                                    "worker_pause_fraction": 0.0})
    assert len(server.workers) == 4
    assert server.broker.stats()["shards"] == 2
    assert server.solve_coordinator is not None
    server.start()
    try:
        for _ in range(8):
            server.register_node(mock.node())
        jobs = []
        for i in range(50):
            job = mock.job()
            job.task_groups[0].count = 1
            jobs.append(job)
            server.register_job(job)
        for job in jobs:
            ev = server.store.evals_by_job("default", job.id)[0]
            assert wait_until(
                lambda e=ev: server.store.eval_by_id(e.id).status in
                (structs.EVAL_STATUS_COMPLETE,
                 structs.EVAL_STATUS_BLOCKED), timeout=60), job.id
        assert wait_until(
            lambda: server.broker.stats()["total_unacked"] == 0,
            timeout=10)
        st = server.broker.stats()
        assert st["total_ready"] == 0
        counters = global_metrics.dump()["counters"]
        assert counters.get("coordinator.rounds", 0) > 0
    finally:
        server.stop()


# ------------------------------------------------------------------
# Pipelined coordinator (ISSUE 19): seeded parity + async fan-back
# ------------------------------------------------------------------
_MAX_FUSED = 4


def _drive_coordinator(server, workers, shares):
    """Release every share against a paused SolveCoordinator.
    max_fused=4 forces multiple rounds, so the drain actually overlaps
    round b+1's reconcile with round b's solve."""
    coord = SolveCoordinator(server, max_fused=_MAX_FUSED)
    coord.pause()
    threads = [threading.Thread(target=coord.submit, args=(w, share))
               for w, share in zip(workers, shares)]
    for t in threads:
        t.start()
    assert wait_until(lambda: coord.pending() == len(threads),
                      timeout=5.0)
    coord.resume()
    for t in threads:
        t.join(timeout=60.0)
        assert not t.is_alive()


def _drive_serialized(server, workers, shares):
    """The rounds the coordinator forms (whole submissions popped in
    order until max_fused evals are reached), each run end to end by
    `process_fleet` on the first submitter's worker: nothing in
    flight."""
    queue = list(shares)
    while queue:
        combined = []
        while queue and len(combined) < _MAX_FUSED:
            combined.extend(queue.pop(0))
        process_fleet(server, workers[0], combined)


def _coordinator_run(n_jobs, n_workers, seed, drive=_drive_coordinator):
    """One seeded scenario: shuffle the dequeued evals, deal them
    round-robin to `n_workers` submitters and let `drive` solve the
    shares.  Returns (placements, eval statuses) — the full observable
    state."""
    server = Server(num_workers=0)
    server.start()
    try:
        _nodes, jobs = _dc_pinned_cluster(server, n_jobs)
        for j in jobs:
            server.register_job(j)
        batch = server.broker.dequeue_batch(["service"], n_jobs, 1.0)
        assert len(batch) == n_jobs
        random.Random(seed).shuffle(batch)
        workers = [Worker(server, ["service"], index=i)
                   for i in range(n_workers)]
        shares = [batch[k::n_workers] for k in range(n_workers)]
        drive(server, workers, shares)
        assert server.broker.stats()["total_unacked"] == 0
        statuses = {j.id: server.store.evals_by_job("default", j.id)[0]
                    .status for j in jobs}
        return _placements(server, jobs), statuses
    finally:
        server.stop()


@pytest.mark.parametrize("n_workers", [2, 4, 8])
@pytest.mark.parametrize("pallas", ["off", "score"])
def test_pipelined_coordinator_matches_serialized(n_workers, pallas,
                                                  monkeypatch):
    """ISSUE 19 property: the async double-buffered drain must place
    EXACTLY what the same rounds place run serially, one `process_fleet`
    after another — same placements, same eval statuses — across
    worker counts and with the pallas scoring kernel forced on
    (interpreted on CPU) or off.  Round b+1
    reconciles against a snapshot that excludes round b's uncommitted
    plans; with dc-pinned jobs the solves are independent, so any
    divergence is a pipelining bug, not optimistic-concurrency slack."""
    from nomad_tpu.solver import pallas_kernel as PK
    monkeypatch.setenv("NOMAD_TPU_PALLAS",
                       "0" if pallas == "off" else "1")
    PK.enabled.cache_clear()
    try:
        n_jobs, seed = 8, 1900 + n_workers
        serialized = _coordinator_run(n_jobs, n_workers, seed,
                                      drive=_drive_serialized)
        pipelined = _coordinator_run(n_jobs, n_workers, seed)
        assert pipelined == serialized
        assert all(len(v) == 2 for v in pipelined[0].values())
    finally:
        PK.enabled.cache_clear()


def _recording_coordinator(calls, fail_on=()):
    """A SolveCoordinator on recording fakes through its seam: one
    eval a round, every half-round appended to `calls`."""
    def dispatch(_server, _worker, batch):
        tag = batch[0][0]
        calls.append(("dispatch", tag))
        if tag in fail_on:
            raise RuntimeError(f"dispatch {tag}")
        return tag

    def finish(_server, _worker, rnd):
        calls.append(("finish", rnd))

    return SolveCoordinator(None, max_fused=1, dispatch_fn=dispatch,
                            finish_fn=finish)


def test_coordinator_is_wired_and_pipelined_whatever_the_environment(
        monkeypatch):
    """The switches are gone: a multi-worker Server fuses through a
    coordinator, and the coordinator drains with one round in flight —
    round b+1's dispatch before round b's finish — also where an
    environment still carries the two variables that used to turn
    either off."""
    for gone in ("PIPELINE", "COORDINATOR"):
        monkeypatch.setenv("NOMAD_TPU_" + gone, "0")
    server = Server(num_workers=2)
    assert isinstance(server.solve_coordinator, SolveCoordinator)
    assert Server(num_workers=1).solve_coordinator is None

    calls = []
    coord = _recording_coordinator(calls)
    coord.pause()
    subs = [coord.submit_nowait(f"w{tag}", [(tag, "tok")])
            for tag in ("a", "b")]
    coord.resume()
    assert all(s.done.is_set() and s.error is None for s in subs)
    assert calls == [("dispatch", "a"), ("dispatch", "b"),
                     ("finish", "a"), ("finish", "b")]


def test_failed_dispatch_releases_its_round_and_the_one_in_flight_finishes():
    """A dispatch half that raises hands the error to every submitter
    of ITS round, at once; the round already in flight is still
    finished and released clean, and the drain goes on to the next."""
    calls = []
    coord = _recording_coordinator(calls, fail_on=("b",))
    coord.pause()
    subs = {tag: coord.submit_nowait(f"w{tag}", [(tag, "tok")])
            for tag in ("a", "b", "c")}
    coord.resume()
    assert all(s.done.is_set() for s in subs.values())
    assert subs["a"].error is None and subs["c"].error is None
    assert isinstance(subs["b"].error, RuntimeError)
    assert calls == [("dispatch", "a"), ("dispatch", "b"),
                     ("finish", "a"), ("dispatch", "c"), ("finish", "c")]
    assert coord.pending() == 0


def test_async_fanback_conservation_storm():
    """InvariantHarness conservation over the fire-and-forget fan-back:
    producers race admission, consumer threads dequeue, randomly nack,
    pause the rest's deadlines in bulk and submit_nowait — acks happen
    on the drain LEADER thread (another worker entirely) inside the
    round's finish hook.  After the drain: no eval lost, no eval held,
    the coordinator queue empty."""
    broker = EvalBroker(nack_delay_s=30.0, initial_nack_delay_s=0.001,
                        delivery_limit=20, shards=4)
    broker.set_enabled(True)
    be = BlockedEvals(broker)
    be.set_enabled(True)
    adm = AdmissionController(max_pending=64, protect_priority=101,
                              brownout_high=0.9, brownout_low=0.5,
                              brownout_after_s=0.001,
                              ns_rate=5000.0, ns_burst=500.0)
    h = InvariantHarness(event_log=MeshEventLog())
    stop = threading.Event()
    acked = set()
    acked_lock = threading.Lock()

    def _dispatch(_server, _worker, batch):
        return list(batch)

    def _finish(_server, _worker, rnd):
        broker.ack_batch([(ev.id, tok) for ev, tok in rnd])
        with acked_lock:
            for ev, _tok in rnd:
                h.note_outcome(ev.id, "acked")
                acked.add(ev.id)

    coord = SolveCoordinator(None, max_fused=8,
                             dispatch_fn=_dispatch, finish_fn=_finish)

    def producer(k):
        rng = random.Random(1000 + k)
        for i in range(60):
            ev = mock.eval_(job_id=f"job-{k}-{i}",
                            priority=rng.choice([30, 50, 70]))
            h.note_enqueued(ev.id)
            if adm.offer(ev, broker.ready_count()):
                broker.enqueue(ev)
            else:
                be.shed(ev)
                h.note_outcome(ev.id, "shed")
            if rng.random() < 0.2:
                time.sleep(0.001)

    def consumer(k):
        rng = random.Random(2000 + k)
        while not stop.is_set():
            batch = broker.dequeue_batch(["service"], 4, 0.02, home=k)
            keep = []
            for ev, tok in batch:
                if rng.random() < 0.2:
                    broker.nack(ev.id, tok)
                else:
                    keep.append((ev, tok))
            if keep:
                broker.pause_nack_batch(
                    [(ev.id, tok) for ev, tok in keep])
                coord.submit_nowait(k, keep)

    producers = [threading.Thread(target=producer, args=(k,))
                 for k in range(4)]
    consumers = [threading.Thread(target=consumer, args=(k,))
                 for k in range(4)]
    for t in producers + consumers:
        t.start()
    for t in producers:
        t.join(timeout=30.0)
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        for ev in be.pop_shed(1000):
            broker.enqueue(ev)
        st = broker.stats()
        if (st["total_ready"] == 0 and st["total_unacked"] == 0
                and st["total_waiting"] == 0 and be.shed_count() == 0
                and coord.pending() == 0):
            break
        time.sleep(0.02)
    stop.set()
    for t in consumers:
        t.join(timeout=10.0)
    st = broker.stats()
    assert st["total_ready"] == 0 and st["total_unacked"] == 0 \
        and st["total_waiting"] == 0
    assert coord.pending() == 0
    assert h.check_eval_conservation(broker)
    assert h.check_shed_accounting(admission=adm)
    h.raise_if_violated()
    assert len(acked) == 4 * 60
