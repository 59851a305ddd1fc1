"""The collector's freeze policy (`nomad_tpu/utils/collector.py`).

  * a full collection under the policy freezes what it found alive, so
    the next one walks only what came after;
  * a cycle made after a freeze is still collected, and one frozen alive
    that dies later is freed once the permanent generation is thawed;
  * a store restore, a raft compaction and a server's stop thaw it;
  * one hook for however many servers, taken out by the last stop;
  * the pause samples still time a collection made under the policy.

The collector's own collections are held off in every test (`gc.disable`
stops only those), so the explicit `gc.collect()`s are the only ones.
"""
import gc
import weakref

import pytest

from nomad_tpu import mock
from nomad_tpu.raft import InProcTransport, RaftConfig, RaftNode, StateFSM
from nomad_tpu.server.server import Server
from nomad_tpu.state.store import StateStore
from nomad_tpu.utils import collector
from nomad_tpu.utils.codec import to_wire
from nomad_tpu.utils.metrics import global_metrics
from nomad_tpu.utils.tracing import global_tracer


class Cell:
    """A container the collector tracks, that a weak reference can see."""


@pytest.fixture
def held_off():
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        collector.thaw()
        if was_enabled:
            gc.enable()


@pytest.fixture
def policy(held_off):
    holder = Cell()
    collector.install(holder)
    try:
        yield
    finally:
        collector.release(holder)


def _in(obj, objects) -> bool:
    return any(o is obj for o in objects)


def test_a_full_collection_freezes_what_it_found_alive(policy):
    alive = Cell()
    gc.collect()
    frozen = gc.get_freeze_count()
    assert frozen > 0
    assert not _in(alive, gc.get_objects(2))
    grown = [Cell() for _ in range(1000)]
    assert _in(grown[0], gc.get_objects())
    gc.collect()
    # the next full collection walked what came since, and froze it too
    assert gc.get_freeze_count() >= frozen + len(grown)
    assert not _in(grown[0], gc.get_objects(2))


def test_a_cycle_made_after_a_freeze_is_collected(policy):
    gc.collect()
    made = Cell()
    made.me = made
    ref = weakref.ref(made)
    del made
    gc.collect()
    assert ref() is None


def test_a_cycle_frozen_alive_is_freed_once_thawed(policy):
    kept = Cell()
    kept.me = kept
    ref = weakref.ref(kept)
    gc.collect()                        # frozen while alive
    del kept
    gc.collect()
    assert ref() is not None            # the bound: held until a thaw
    collector.thaw()
    gc.collect()
    assert ref() is None


def test_a_stopped_server_leaves_no_live_object_behind(held_off):
    server = Server(num_workers=1)
    server.start()
    server.register_node(mock.node())
    gc.collect()                        # the server's graph is frozen
    assert gc.get_freeze_count() > 0
    server.stop()
    assert gc.get_freeze_count() == 0
    # the leader's background loops see the stop at their next wake-up
    for loop in (server._dup_reaper, server._gc_timer,
                 server._metrics_timer):
        loop.join(timeout=5.0)
    ref = weakref.ref(server)
    del server
    gc.collect()
    assert ref() is None


def _restore():
    source = StateStore()
    source.upsert_node(1, mock.node())
    StateFSM(StateStore()).restore(StateFSM(source).snapshot())


def _compact():
    node = RaftNode(RaftConfig(node_id="s0", peers=["s0"],
                               snapshot_threshold=8),
                    StateFSM(StateStore()), InProcTransport())
    node.bootstrap_single()
    try:
        for _ in range(12):
            node.propose("node_upsert", {"node": to_wire(mock.node())})
        assert node.log.offset > 0, "the log did not compact"
    finally:
        node.stop()


@pytest.mark.parametrize("drop", [_restore, _compact],
                         ids=["fsm_restore", "raft_compaction"])
def test_a_whole_store_operation_thaws_the_permanent_generation(
        policy, drop):
    gc.collect()
    assert gc.get_freeze_count() > 0
    drop()
    assert gc.get_freeze_count() == 0


def test_two_servers_share_one_hook_and_the_last_stop_takes_it_out(
        held_off):
    hook = collector._freeze_survivors
    first, second = Server(num_workers=1), Server(num_workers=1)
    first.start()
    second.start()
    collector.install(first)            # a second install holds no more
    try:
        assert gc.callbacks.count(hook) == 1
        assert first in collector._holders and second in collector._holders
        first.stop()
        assert first not in collector._holders
        assert gc.callbacks.count(hook) == 1
    finally:
        second.stop()
    assert second not in collector._holders
    # a server another test left running still holds the policy
    assert gc.callbacks.count(hook) == (1 if collector._holders else 0)


def _drain():
    with global_tracer.layer("test.collector"):
        pass
    dump = global_metrics.dump()
    return dump["samples"], dump["counters"]


def test_the_pause_samples_time_a_collection_made_under_the_policy(policy):
    global_tracer.watch_gc()
    hook = collector._freeze_survivors
    # the tracer's hook comes after the freeze and closes the pause
    assert gc.callbacks.index(hook) < gc.callbacks.index(global_tracer._on_gc)
    samples0, counters0 = _drain()
    alive = [Cell() for _ in range(200_000)]
    frozen0 = gc.get_freeze_count()
    gc.collect()
    frozen1 = gc.get_freeze_count()
    samples1, counters1 = _drain()
    assert not _in(alive[-1], gc.get_objects(2))

    def grew(key):
        a, b = samples1[key], samples0.get(key, {"count": 0, "sum": 0.0})
        return a["count"] - b["count"], a["sum"] - b["sum"]

    for key in ("span.gc.pause", "span.gc.full"):
        count, seconds = grew(key)
        assert count == 1 and seconds > 0, key
    # the counter is what the freeze moved, but for what another thread
    # allocated or freed between the count and the freeze
    counted = counters1["gc.frozen"] - counters0.get("gc.frozen", 0)
    moved = frozen1 - frozen0
    assert moved > len(alive)
    assert abs(counted - moved) <= moved / 20, (counted, moved)
