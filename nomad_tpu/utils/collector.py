"""What a full collection finds alive is frozen, so the next one skips it.

CPython's collector walks every tracked object at each full (generation-2)
collection, and starts one each time the objects promoted since the last
exceed a quarter of what that one found alive.  A server whose store holds
10,000 nodes and 100,000 allocs pays a walk of all of them, a second with
every thread stopped, for every quarter the store grows.

While a `Server` runs, a `gc.callbacks` hook calls `gc.freeze()` at the
end of every generation-2 collection: what that collection found alive
moves to the permanent generation, which later collections skip, so the
next full collection walks only what was promoted since.  The thresholds
are CPython's own; nothing here disables the collector.

A frozen object is still freed by reference counting, but a cycle that
was alive at a freeze and dies later stays until `thaw()` gives the
permanent generation back to the collector: where a store is dropped
whole (`StateFSM.restore`), where raft compacts its log, and at every
`Server.stop()`; the last running server's stop also removes the hook.

The counter `gc.frozen` adds the objects each freeze moved, counted just
before it: what `gc.get_freeze_count()` rises by, read without walking
the whole permanent generation as that call does.  The hook runs
wherever an allocation tipped the collector, possibly under the metrics
registry's lock, so it parks the count with the tracer's pause samples
(`watch_gc`), which are written at the next layer span's end.

The policy is process-wide, as the collector is: one hook, and the set of
servers that hold it (weakly: a server dropped without `stop()` lets go).
"""
from __future__ import annotations

import gc
import threading
import weakref

from .tracing import global_tracer

_lock = threading.Lock()
_holders: weakref.WeakSet = weakref.WeakSet()


def _freeze_survivors(phase: str, info: dict) -> None:
    if phase != "stop" or info["generation"] != 2:
        return
    # the full collection has emptied the younger generations into the
    # oldest, so what the three hold now is what the freeze moves
    moved = len(gc.get_objects())
    gc.freeze()
    global_tracer.count_from_gc("gc.frozen", moved)


def install(holder) -> None:
    """Freeze what every full collection finds alive while `holder` holds
    the policy.  One hook however many hold it; a second install by the
    same holder changes nothing."""
    with _lock:
        _holders.add(holder)
        if _freeze_survivors not in gc.callbacks:
            # first in the list: at a collection's end the tracer's own
            # hook (`watch_gc`) runs after this one, so `gc.pause` and
            # `span.gc.full` time the freeze with the collection
            gc.callbacks.insert(0, _freeze_survivors)


def release(holder) -> None:
    """`holder` lets go of the policy, and the permanent generation is
    thawed; the last holder's release also removes the hook.  A holder
    that does not hold it changes nothing."""
    with _lock:
        if holder not in _holders:
            return
        _holders.discard(holder)
        if not _holders:
            gc.callbacks.remove(_freeze_survivors)
    thaw()


def thaw() -> None:
    """Give the permanent generation back to the collector: the next full
    collection walks everything once, frees the cycles that died frozen,
    and (while the hook is installed) freezes what it found alive."""
    gc.unfreeze()
