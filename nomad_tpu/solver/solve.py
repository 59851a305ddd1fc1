"""Solver orchestration: pack -> device solve -> unpack into placements.

The discrete leftovers the tensor solve can't express (which dynamic
ports, the per-address collision check, device instance IDs — SURVEY
§7.3) are fixed up host-side here, walking the kernel's top-K candidates
per placement so a port/instance conflict falls through to the next-best
node instead of failing the eval.  A static port a group reserves is a
counted column of the wave (tensorize.py, "Counted columns"), so the
candidates already have it free; a placement whose candidates the
network assignment all refused while the wave saw more placeable nodes
is retried, not failed, and names its `network:` dimension.
"""
from __future__ import annotations

import os
import threading
import time as _t
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..structs import (AllocatedDeviceResource, AllocatedResources,
                       AllocatedSharedResources, AllocatedTaskResources,
                       AllocMetric, DeviceAccounter, NetworkIndex, Node)
from ..utils.metrics import global_metrics as _m
from ..utils.tracing import global_tracer as _tr
from .kernel import TOP_K, solve_kernel
from .tensorize import (NUM_R, ClusterDelta, PackedBatch, PlacementAsk,
                        Tensorizer, alloc_device_usage,
                        alloc_usage_vector, apply_node_delta_host,
                        evict_width, property_target,
                        static_port_columns,
                        R_CPU, R_DISK, R_MEM, R_NET)

_DIM_NAMES = {R_CPU: "cpu", R_MEM: "memory", R_DISK: "disk", R_NET: "network"}

#: clusters below this size full-pack per eval (the walk is cheap and
#: every compiled shape stays identical to the seed behavior); at or
#: above it the Solver keeps a delta-updated resident world
RESIDENT_MIN_NODES = int(os.environ.get("NOMAD_TPU_RESIDENT_MIN_NODES",
                                        "512"))

#: brownout wave budget (serving tier, ISSUE 6): under sustained
#: overload the admission controller flips workers into degraded mode
#: and solves run with this reduced budget — undecided placements come
#: back retryable and follow the normal blocked/requeue path, trading
#: per-eval completeness for queue drain.  One extra cached compile
#: variant per shape (max_waves is a static kernel arg).
BROWNOUT_MAX_WAVES = int(os.environ.get("NOMAD_TPU_BROWNOUT_MAX_WAVES",
                                        "6"))


class LazyAllocsView(dict):
    """Proposed live allocs by node, filled lazily from the snapshot
    (live minus `excluded` alloc ids).  The steady-state scheduler only
    touches a handful of nodes per eval (chosen candidates' port/device
    fixups, sticky preferences), so the O(cluster) walk the eager dict
    pays per eval collapses to O(touched).

    Reads one node and never walks the cluster: `get`, `[]`, `in`,
    `setdefault`, and the truth test (`bool(view)`, `if view:`), which
    says whether the snapshot holds any alloc at all: the eager dict it
    stands for is empty only when the cluster is (a cluster whose every
    alloc is terminal or excluded reads true and every `get` then comes
    back empty, which no caller can tell from the eager dict).
    Materializes, walking every alloc of the snapshot once: `items()`,
    `keys()`, `values()`, iteration, `len()` and `dict(view)`: the
    full-pack fallback and the host preemption walk need the whole
    world and say so by iterating.

    Both ways keep an alloc by the one test `_live`, and a node filled
    before a materialize keeps its list, so a filled key is a plain
    dict entry and in-place mutation (sticky probes, preemption
    rewrites) behaves exactly like the eager dict.  Counters:
    `solver.allocs_view.nodes` (nodes whose allocs were read from the
    snapshot) and `solver.allocs_view.materialized` (full walks)."""

    def __init__(self, snapshot, excluded=frozenset()):
        super().__init__()
        self._snap = snapshot
        self.excluded = set(excluded)
        self._filled = set()
        self._all = False

    def _live(self, a) -> bool:
        return not a.terminal_status() and a.id not in self.excluded

    def _fill(self, nid) -> None:
        if self._all or nid in self._filled:
            return
        self._filled.add(nid)
        _m.incr_counter("solver.allocs_view.nodes")
        live = [a for a in self._snap.allocs_by_node(nid)
                if self._live(a)]
        if live:                 # eager dict only has non-empty keys
            dict.__setitem__(self, nid, live)

    def materialize(self) -> "LazyAllocsView":
        if not self._all:
            pending: Dict[str, list] = {}
            for a in self._snap.allocs():
                if self._live(a) and a.node_id not in self._filled:
                    pending.setdefault(a.node_id, []).append(a)
            for nid, lst in pending.items():
                dict.__setitem__(self, nid, lst)
            self._all = True
            _m.incr_counter("solver.allocs_view.materialized")
            _m.incr_counter("solver.allocs_view.nodes", len(pending))
        return self

    def __bool__(self):
        if self._all or dict.__len__(self):
            return dict.__len__(self) > 0
        return any(True for _ in self._snap.allocs())

    def get(self, nid, default=None):
        self._fill(nid)
        return dict.get(self, nid, default)

    def __getitem__(self, nid):
        self._fill(nid)
        return dict.__getitem__(self, nid)

    def __contains__(self, nid):
        self._fill(nid)
        return dict.__contains__(self, nid)

    def setdefault(self, nid, default=None):
        self._fill(nid)
        return dict.setdefault(self, nid, default)

    def items(self):
        return dict.items(self.materialize())

    def keys(self):
        return dict.keys(self.materialize())

    def values(self):
        return dict.values(self.materialize())

    def __iter__(self):
        return dict.__iter__(self.materialize())

    def __len__(self):
        return dict.__len__(self.materialize())


class _ResidentWorld:
    """Delta-updated packed cluster state for a Solver (ISSUE 2
    tentpole, worker side): the node tensors are packed ONCE from a
    snapshot and then advanced by exact changesets — plan-apply results
    fed eagerly by the worker (note_plan_result) plus the state store's
    change log for everything written by other actors (client status
    updates, node joins/drains) — so steady-state scheduling never
    re-walks or re-tensorizes the world.  Falls back to a full rebuild
    when the change log was truncated, the delta escapes the interned
    universe, or it touches more than `delta_threshold` of the nodes."""

    def __init__(self, tz: Tensorizer, store, snapshot,
                 probe_asks: Sequence[PlacementAsk],
                 delta_threshold: float):
        self._tz = tz
        self.store = store
        self.delta_threshold = delta_threshold
        # probe asks define the ask universe; grown (dedup by spec
        # signature, capped) when an ask escapes it
        self._probe_sigs: Dict = {}
        self.probe_asks: List[PlacementAsk] = []
        self.add_probes(probe_asks)
        self.counters = {"delta_syncs": 0, "repack_fallbacks": 0,
                         "plan_feeds": 0, "last_delta_ratio": 0.0}
        self.drv_cache: Dict[str, np.ndarray] = {}
        self.row_cache: Dict = {}
        self.rebuild(snapshot)
        self.counters["repack_fallbacks"] = 0   # initial build is free

    def add_probes(self, asks: Sequence[PlacementAsk]) -> bool:
        added = False
        signer = self._tz.ask_signer()
        for a in asks:
            sig = signer(a)
            if sig not in self._probe_sigs and len(self.probe_asks) < 64:
                self._probe_sigs[sig] = True
                self.probe_asks.append(a)
                added = True
        return added

    def rebuild(self, snapshot) -> None:
        _m.incr_counter("solver.resident.rebuild")
        self.nodes = list(snapshot.nodes())          # join order
        by_node: Dict[str, list] = {}
        self.live: Dict[str, tuple] = {}             # id -> (nid, alloc)
        for a in snapshot.allocs():
            if not a.terminal_status():
                by_node.setdefault(a.node_id, []).append(a)
                self.live[a.id] = (a.node_id, a)
        # evictable planes ride on the template (in-kernel preemption,
        # ISSUE 7) and are delta-maintained with every other node plane
        self.template = self._tz.pack(self.nodes, self.probe_asks,
                                      by_node, evict_e=evict_width())
        # the template packs EVERY node; readiness (status, drain,
        # eligibility) lives in the valid mask instead of list filtering
        for i, n in enumerate(self.nodes):
            self.template.valid[i] = n.ready()
        self.node_index = {n.id: i for i, n in enumerate(self.nodes)}
        self.last_index = snapshot.index
        self.drv_cache.clear()
        self.row_cache.clear()
        self.counters["repack_fallbacks"] += 1

    def feed(self, delta: ClusterDelta) -> bool:
        """Apply an eagerly-fed changeset (plan-apply results).  The
        live map was already updated by the caller; only the tensors
        move here.  Returns False if the delta was inexpressible (the
        next sync() will rebuild)."""
        nd = self._tz.delta_pack(self.template, self.node_index, delta)
        if nd is None:
            return False
        apply_node_delta_host(self.template, nd, self.nodes,
                              self.node_index)
        if nd.touches_nodes():
            self.drv_cache.clear()
            self.row_cache.clear()
        return True

    def sync(self, snapshot) -> None:
        """Advance the world to `snapshot.index` via the store change
        log, building an exact ClusterDelta from the changed entities
        only."""
        if snapshot.index == self.last_index:
            return
        if snapshot.index < self.last_index:
            self.rebuild(snapshot)       # state moved backwards: a new
            return                       # snapshot from another store
        entries = self.store.changes_since(self.last_index,
                                           snapshot.index)
        if entries is None:              # ring truncated past us
            self.rebuild(snapshot)
            return
        delta = ClusterDelta()
        seen: set = set()
        for _ix, kind, key in reversed(entries):
            if (kind, key) in seen:      # newest entry per key wins
                continue
            seen.add((kind, key))
            if kind == "node":
                n = snapshot.node_by_id(key)
                if n is None:
                    if key in self.node_index:
                        delta.remove_node_ids.append(key)
                else:
                    delta.upsert_nodes.append(n)
            else:
                a = snapshot.alloc_by_id(key)
                live_now = a is not None and not a.terminal_status()
                tracked = self.live.get(key)
                if live_now and tracked is None:
                    delta.place.append((a.node_id, a))
                    self.live[key] = (a.node_id, a)
                elif tracked is not None and not live_now:
                    delta.stop.append(tracked)
                    del self.live[key]
                elif tracked is not None and live_now:
                    old_nid, old = tracked
                    if (old_nid != a.node_id
                            or not np.array_equal(
                                alloc_usage_vector(old),
                                alloc_usage_vector(a))):
                        delta.stop.append(tracked)
                        delta.place.append((a.node_id, a))
                    self.live[key] = (a.node_id, a)
        self.counters["delta_syncs"] += 1
        _m.incr_counter("solver.resident.delta_sync")
        if delta.empty():
            self.last_index = snapshot.index
            return
        nd = self._tz.delta_pack(self.template, self.node_index, delta)
        if nd is not None:
            ratio = nd.ratio(self.template.n_real)
            self.counters["last_delta_ratio"] = round(ratio, 6)
            if nd.touches_nodes() and ratio > self.delta_threshold:
                nd = None
        if nd is None:
            self.rebuild(snapshot)
            return
        apply_node_delta_host(self.template, nd, self.nodes,
                              self.node_index)
        if nd.touches_nodes():
            self.drv_cache.clear()
            self.row_cache.clear()
        self.last_index = snapshot.index


def _overlay_usage(world: _ResidentWorld, pb: PackedBatch,
                   proposed_delta) -> PackedBatch:
    """Copy-on-read overlay: apply this plan's proposed stops/probes to
    COPIES of the resident template's carried usage (and, for stops,
    the eviction candidate rows), leaving `world` bit-identical.  Both
    the steady-state solve and the what-if plan path
    (PlanSolverView) go through here — neither ever mutates
    _ResidentWorld state."""
    import copy as _copy
    pb = _copy.copy(pb)
    t = world.template
    used0 = t.used0.copy()
    dev_used0 = t.dev_used0.copy()
    stops, probes = proposed_delta or ((), ())
    D = dev_used0.shape[1]
    ev_gone: Dict[int, set] = {}
    for sign, group in ((-1.0, stops), (1.0, probes)):
        for a in group:
            i = world.node_index.get(a.node_id)
            if i is None:
                continue
            used0[i] += sign * alloc_usage_vector(a)
            drow = alloc_device_usage(t.dev_pattern_ids, D, a)
            if drow is not None:
                dev_used0[i] += sign * drow
            if sign < 0 and t.ev_lists is not None:
                ev_gone.setdefault(i, set()).add(a.id)
    pb.used0, pb.dev_used0 = used0, dev_used0
    if ev_gone and pb.ev_prio is not None:
        # an eager-stopped alloc's usage already left the overlay; it
        # must not ALSO be selectable as an eviction victim (its freed
        # capacity would double-count).  Rebuild the touched rows on
        # copies; sticky probes are additions and never candidates.
        from .tensorize import _evict_row
        ev_prio = pb.ev_prio.copy()
        ev_res = pb.ev_res.copy()
        ev_ids = list(pb.ev_ids)
        E = ev_prio.shape[1]
        for i, gone in ev_gone.items():
            cands = [c for c in t.ev_lists[i] if c[2] not in gone]
            ev_prio[i], ev_res[i], ev_ids[i] = _evict_row(cands, E)
        pb.ev_prio, pb.ev_res, pb.ev_ids = ev_prio, ev_res, ev_ids
    return pb


@dataclass
class Placement:
    ask_index: int
    node: Optional[Node]
    score: float
    metrics: AllocMetric
    resources: Optional[AllocatedResources] = None
    failed_reason: str = ""
    #: the solve's wave budget ran out before this placement was decided
    #: (kernel `unfinished`): nothing says capacity is missing, so the
    #: scheduler re-solves it at once instead of blocking the eval on a
    #: capacity change that need not come
    retryable: bool = False
    #: alloc ids the in-kernel preemption pass selected as victims for
    #: this placement (empty for normal placements) — the scheduler
    #: turns these into plan.node_preemptions
    evicted: List[str] = field(default_factory=list)


@dataclass
class SolveOutput:
    placements: List[Placement]
    class_eligibility: List[Dict[str, bool]] = field(default_factory=list)
    # ^ per ask: computed-class -> any feasible node of that class
    #: flight-recorder attributes for the solve span (ISSUE 10): device
    #: wave/rescore/evict counters, the two-tier modeled HBM bytes and
    #: the resident-world delta counters — callers attach this to the
    #: eval's trace instead of re-deriving it
    trace: Dict = field(default_factory=dict)


class _DeviceTally:
    """What the device asks of one solve cost its host fixup, summed
    over the placements by `Solver._host_commit`; `_finish_solve`
    writes it out once (a solve whose tasks ask for no device leaves
    it at zero and nothing is written)."""

    __slots__ = ("commits", "seconds", "instances", "refused")

    def __init__(self):
        self.commits = 0        # _host_commit calls whose group asks
        self.seconds = 0.0      # ... and the time of their device part
        self.instances = 0      # instance ids handed out
        self.refused = 0        # chosen nodes the instance ids refused

    def add(self, offers: Optional[Dict[str, list]],
            seconds: float) -> None:
        self.commits += 1
        self.seconds += seconds
        if offers is None:
            self.refused += 1
        else:
            self.instances += sum(len(got.device_ids)
                                  for task in offers.values()
                                  for got in task)


class _PortTally:
    """What the network asks of one solve cost its host fixup, summed
    over the placements by `Solver._host_commit` as `_DeviceTally` sums
    the device asks; a solve whose groups ask for no network leaves it
    at zero and nothing is written."""

    __slots__ = ("commits", "seconds", "assigned", "refused", "reason")

    def __init__(self):
        self.commits = 0        # _host_commit calls whose group asks
        self.seconds = 0.0      # ... and the time of their network part
        self.assigned = 0       # ports handed out, static and dynamic
        self.refused = 0        # candidate nodes assign_network refused
        self.reason = ""        # ... and why the last of them

    def add(self, offers: Optional[list], reason: str,
            seconds: float) -> None:
        self.commits += 1
        self.seconds += seconds
        if offers is None:
            self.refused += 1
            self.reason = reason
        else:
            self.assigned += sum(len(o.reserved_ports)
                                 + len(o.dynamic_ports) for o in offers)


class PendingSolve:
    """An in-flight fused solve: packed and dispatched to the device,
    fetch + host fixup deferred.  `wait()` is the ONLY blocking step —
    it materializes the device result, runs the host fixup walk and
    returns the SolveOutput; idempotent, single-owner (the pipelined
    coordinator's drain leader).

    `t_dispatched` (perf_counter domain) is the stamp right after the
    kernel launch returned, and `pack_wall_s` / `dispatch_wall_s` /
    `fetch_wall_s` the walls of the pack, launch and fetch layer spans:
    the fused round accounts its stages, and device time as interval
    unions under pipelining, from them.  `spans` prefixes the four
    layer spans (`solve.pack` ...; the fused round passes `fleet`).
    """

    __slots__ = ("_solver", "_pb", "_sol_nodes", "_asks",
                 "_allocs_by_node", "_by_dc", "_used_resident", "_res",
                 "_out", "_spans", "t_dispatched", "pack_wall_s",
                 "dispatch_wall_s", "fetch_wall_s")

    def __init__(self, solver, pb=None, sol_nodes=None, asks=None,
                 allocs_by_node=None, by_dc=None,
                 used_resident: bool = False, res=None,
                 out: Optional[SolveOutput] = None, spans: str = "solve"):
        self._solver = solver
        self._pb = pb
        self._sol_nodes = sol_nodes
        self._asks = asks
        self._allocs_by_node = allocs_by_node
        self._by_dc = by_dc
        self._used_resident = used_resident
        self._res = res
        self._out = out
        self._spans = spans
        self.t_dispatched = 0.0
        self.pack_wall_s = 0.0
        self.dispatch_wall_s = 0.0
        self.fetch_wall_s = 0.0

    def wait(self) -> SolveOutput:
        """Block until the device result lands, then run the host fixup.
        Safe to call again after completion (returns the cached output);
        NOT safe to call concurrently from two threads."""
        if self._out is not None:
            return self._out
        with _tr.layer(self._spans + ".fetch") as fetch:
            np.asarray(self._res.choice)  # blocks until the kernel is done
        self.fetch_wall_s = fetch.dur_s
        with _tr.layer(self._spans + ".fixup"):
            out = self._solver._finish_solve(
                self._pb, self._sol_nodes, self._asks, self._res,
                self._used_resident, self._allocs_by_node, self._by_dc,
                self._spans)
        self._out = out
        # drop the packed batch + device refs so a long-lived pending
        # handle doesn't pin buffers
        self._res = self._pb = self._sol_nodes = self._asks = None
        self._allocs_by_node = self._by_dc = None
        return out


class Solver:
    """Stateful wrapper owning tensorizer memoization. One per scheduler
    worker (reference analog: the Stack owned by each scheduler).

    `host` picks the compute path: "auto" (default) solves small
    problems with the numpy twin of the kernel (host.py — identical
    placements, no device round trip; SURVEY §7.3's latency fallback),
    "never"/"always" pin a path (tests, benchmarks)."""

    def __init__(self, host: str = "auto", store=None,
                 resident: str = "auto",
                 resident_min_nodes: Optional[int] = None,
                 delta_threshold: float = 0.25) -> None:
        self._tensorizer = Tensorizer()
        self._host = host
        #: resident-world wiring (ISSUE 2): with a store attached, big
        #: clusters pack the node side once and advance it by changesets
        #: (plan-apply feed + store change log) instead of re-packing
        #: the world per eval.  "off" pins the seed behavior.
        self._store = store
        self._resident = resident if store is not None else "off"
        self._resident_min_nodes = (RESIDENT_MIN_NODES
                                    if resident_min_nodes is None
                                    else resident_min_nodes)
        self._delta_threshold = delta_threshold
        self._world: Optional[_ResidentWorld] = None
        self._degraded = False
        #: serializes resident-world access between the worker thread
        #: and overlay (what-if) solves from the HTTP plan endpoint
        self._world_lock = threading.Lock()

    # ---------------------------------------------------------- brownout
    def set_degraded(self, degraded: bool) -> None:
        """Serving-tier brownout: solve with the reduced
        BROWNOUT_MAX_WAVES budget while set (leftovers stay
        retryable)."""
        with self._world_lock:
            self._degraded = bool(degraded)

    @property
    def degraded(self) -> bool:
        with self._world_lock:
            return self._degraded

    # ------------------------------------------------- resident world
    def resident_active(self, snapshot=None) -> bool:
        """Whether the next solve against `snapshot` can take the
        resident-delta path (callers use this to pick the lazy allocs
        view over the eager world walk)."""
        if self._resident == "off" or self._store is None:
            return False
        if self._world is not None:
            return True
        if snapshot is None:
            return False
        return len(snapshot._t["nodes"]) >= self._resident_min_nodes

    def note_plan_result(self, plan, result) -> None:
        """Feed an applied plan's outcome into the resident world — the
        worker calls this right after submit_plan so the next eval's
        solve starts from already-advanced tensors and the change-log
        sync degenerates to a no-op dedup."""
        with self._world_lock:
            world = self._world
            if world is None or result is None:
                return
            delta = ClusterDelta()
            for nid, allocs in (result.node_update or {}).items():
                for a in allocs:
                    tracked = world.live.pop(a.id, None)
                    if tracked is not None:
                        delta.stop.append(tracked)
            for allocs in (result.node_preemptions or {}).values():
                for a in allocs:
                    tracked = world.live.pop(a.id, None)
                    if tracked is not None:
                        delta.stop.append(tracked)
            for nid, allocs in (result.node_allocation or {}).items():
                for a in allocs:
                    if a.id not in world.live \
                            and not a.terminal_status():
                        delta.place.append((nid, a))
                        world.live[a.id] = (nid, a)
            if delta.empty():
                return
            world.counters["plan_feeds"] += 1
            if not world.feed(delta):
                # inexpressible eagerly (e.g. alloc on an unknown
                # node): drop the world; the next solve rebuilds from
                # its snapshot
                self._world = None

    def resident_counters(self) -> Optional[Dict]:
        with self._world_lock:
            world = self._world
            return dict(world.counters) if world else None

    def health_counters(self):
        """Fleet health sample over the resident world's delta-
        maintained host template (ISSUE 15 telemetry tick).  Uses the
        numpy twin of the device health kernel — bit-identical by the
        telemetry property tests — so the server's 1 Hz beat never
        touches the device.  None while no resident world is active
        (small clusters host-walk; nothing to sample)."""
        with self._world_lock:
            world = self._world
            if world is None:
                return None
            from ..telemetry.health import health_host
            t = world.template
            return health_host(t, t.used0, t.dev_used0)

    def plan_view(self) -> "PlanSolverView":
        """Facade for dry-run (what-if) schedulers: same resident
        template, overlay-only solves, zero writes to carried state."""
        return PlanSolverView(self)

    def _resident_pack(self, snapshot, asks, proposed_delta,
                       overlay_only: bool = False):
        """The steady-state pack: sync the world to the snapshot via
        the change log, repack ONLY the ask side against the resident
        template, and overlay this plan's proposed stops/probes onto a
        copy of the maintained usage.  None -> caller full-packs.

        `overlay_only` (the what-if plan path): NEVER create, sync,
        rebuild, or grow the world — read the current template under
        the lock and overlay onto copies, so carried state stays
        bit-identical no matter how many plan solves interleave.
        Returns (pb, nodes) so callers never re-read self._world (a
        concurrent rebuild could swap the node list under them)."""
        with self._world_lock:
            if self._world is None:
                if overlay_only:
                    return None
                if len(snapshot._t["nodes"]) < self._resident_min_nodes:
                    return None
                self._world = _ResidentWorld(
                    self._tensorizer, self._store, snapshot, asks,
                    self._delta_threshold)
            world = self._world
            if not overlay_only:
                world.sync(snapshot)
            gp = max(self._pad(len(asks)), 1)
            kp = max(self._pad(sum(max(a.count, 1) for a in asks)), 1)
            pb = self._tensorizer.repack_asks(
                world.nodes, asks, world.template, gp=gp, kp=kp,
                drv_cache=world.drv_cache, row_cache=world.row_cache,
                property_slots=True)
            if pb is None:
                if overlay_only:
                    return None
                # ask universe escape: grow the probes and rebuild once
                if not world.add_probes(asks):
                    return None
                world.rebuild(snapshot)
                pb = self._tensorizer.repack_asks(
                    world.nodes, asks, world.template, gp=gp, kp=kp,
                    drv_cache=world.drv_cache, row_cache=world.row_cache,
                    property_slots=True)
                if pb is None:
                    return None
            return (_overlay_usage(world, pb, proposed_delta),
                    world.nodes)

    @staticmethod
    def _pad(n: int) -> int:
        return 1 << max(0, (n - 1).bit_length())

    def solve(self, nodes: Sequence[Node], asks: Sequence[PlacementAsk],
              allocs_by_node: Optional[Dict[str, list]] = None,
              by_dc: Optional[Dict[str, int]] = None, *,
              snapshot=None, proposed_delta=None, preempt: bool = False,
              _overlay_only: bool = False) -> SolveOutput:
        """`preempt`: the scheduler resolved preemption as enabled for
        this eval — the resident path then runs the in-kernel eviction
        wave pass (ISSUE 7) and failed-capacity placements may come
        back with `Placement.evicted` victim ids instead of a failure.
        `_overlay_only`: what-if plan mode (see PlanSolverView)."""
        return self.solve_async(
            nodes, asks, allocs_by_node, by_dc, snapshot=snapshot,
            proposed_delta=proposed_delta, preempt=preempt,
            _overlay_only=_overlay_only).wait()

    def solve_async(self, nodes: Sequence[Node],
                    asks: Sequence[PlacementAsk],
                    allocs_by_node: Optional[Dict[str, list]] = None,
                    by_dc: Optional[Dict[str, int]] = None, *,
                    snapshot=None, proposed_delta=None,
                    preempt: bool = False,
                    _overlay_only: bool = False,
                    spans: str = "solve") -> "PendingSolve":
        """Dispatch phase of `solve`: pack and LAUNCH the kernel without
        fetching the result.  Returns a PendingSolve whose `wait()`
        blocks on the device fetch, runs the host fixup walk and yields
        the SolveOutput — the seam the pipelined coordinator rides to
        pack round b+1 while round b solves (the same dispatch/fetch
        split `solve_stream_async`/`finish_stream` and
        `device_health_raw`/`fetch_health` already use).

        When the solve resolves to the host kernel the "dispatch" runs
        it to completion (numpy has no async) and wait() is free; when
        the watchdog is armed the solve also degrades to eager, because
        the watchdog deadline must cover dispatch AND fetch as one
        window — a device wedge surfacing only at the fetch would
        escape a dispatch-only deadline.

        `spans` names the layer spans of pack, dispatch, fetch and
        fixup: `solve.*` on the single-eval path, `fleet.*` from the
        fused round, so that neither's samples count the other's."""
        if not asks:
            return PendingSolve(self, out=SolveOutput(placements=[]))
        with _tr.layer(spans + ".pack") as pack:
            pb = None
            sol_nodes = nodes
            passed_over = 0
            if snapshot is not None and self.resident_active(snapshot):
                packed = self._resident_pack(
                    snapshot, asks, proposed_delta,
                    overlay_only=_overlay_only)
                if packed is not None:
                    pb, sol_nodes = packed
                else:
                    passed_over = 1
            # every solve writes it: a window whose solves all took the
            # resident world reads 0, not nothing
            _m.incr_counter("solver.resident.passed_over", passed_over)
            used_resident = pb is not None
            if pb is None:
                with self._world_lock:
                    # the tensorizer's interners are shared with
                    # concurrent plan-view solves — serialize every pack
                    # through it
                    pb = self._tensorizer.pack(nodes, asks, allocs_by_node)
        from .watchdog import global_watchdog
        if self._degraded:
            _m.incr_counter("solver.degraded")
        with _tr.layer(spans + ".dispatch") as dispatch:
            res = _run_kernel(pb, host_mode=self._host,
                              max_waves=BROWNOUT_MAX_WAVES
                              if self._degraded else 0,
                              preempt=preempt,
                              materialize=global_watchdog.enabled)
        pending = PendingSolve(self, pb=pb, sol_nodes=sol_nodes,
                               asks=list(asks),
                               allocs_by_node=allocs_by_node,
                               by_dc=by_dc,
                               used_resident=used_resident, res=res,
                               spans=spans)
        pending.t_dispatched = _t.perf_counter()
        pending.pack_wall_s = pack.dur_s
        pending.dispatch_wall_s = dispatch.dur_s
        return pending

    def _finish_solve(self, pb: PackedBatch, sol_nodes, asks, res,
                      used_resident: bool, allocs_by_node,
                      by_dc, spans: str = "solve") -> SolveOutput:
        """Fetch-side half of `solve`: result materialization happened
        in PendingSolve.wait(); this walks the host fixup and builds
        the SolveOutput.  Runs inside the caller's `<spans>.fixup` layer
        span; its two bulk passes are child spans, the per-placement
        walk is what is left."""
        trace_attrs = solve_trace_attrs(pb, res)
        trace_attrs["resident"] = used_resident
        # where solves answer from, as counters: an operator (and
        # chip_smoke.py) can see a solve leave the device — prefer_host,
        # a watchdog failover, pallas resolving to "off" — without
        # reading traces
        _m.incr_counter(f"solver.solve.{trace_attrs['platform']}")
        _m.incr_counter(f"solver.pallas.{trace_attrs['pallas_mode']}")
        _m.incr_counter("solver.waves", trace_attrs["waves"])
        _m.incr_counter("solver.rescore_waves",
                        trace_attrs["rescore_waves"])
        if used_resident:
            world = self._world
            if world is not None:
                trace_attrs["world"] = dict(world.counters)

        with _tr.layer(spans + ".d2h"):
            choice = np.asarray(res.choice)
            choice_ok = np.asarray(res.choice_ok)
            score = np.asarray(res.score)
            n_feasible = np.asarray(res.n_feasible)
            n_exhausted = np.asarray(res.n_exhausted)
            dim_exhausted = np.asarray(res.dim_exhausted)
            feas = np.asarray(res.feas)
            cons_filtered = np.asarray(res.cons_filtered)
            unfinished = np.asarray(res.unfinished)
            evict = (np.asarray(res.evict) if res.evict is not None
                     else None)

        # host fixup state: per-node port/device accounting incl. in-batch.
        # host_used is the AUTHORITATIVE usage: when a placement falls through
        # to a lower-ranked candidate, the kernel's in-batch commit charged
        # the wrong node, so every candidate is re-checked against host_used
        # before acceptance. Likewise distinct_hosts / distinct_property are
        # re-enforced here across in-batch commits.
        net_cache: Dict[int, NetworkIndex] = {}
        dev_cache: Dict[int, DeviceAccounter] = {}
        dev_tally = _DeviceTally()
        port_tally = _PortTally()
        host_used = pb.used0.copy()
        chosen_by_ask: Dict[int, set] = {}
        # distinct_property charges shared batch-wide by (scope, target) key
        prop_used: Dict[tuple, Dict[str, int]] = {}
        prop_refused = 0

        # Replay commits in KERNEL WAVE order when the preemption pass
        # ran: evictions make in-batch usage non-monotone, so an
        # ask-order replay can transiently exceed `avail` on a node
        # whose eviction the kernel sequenced earlier (false fall-
        # through).  Without evictions usage only grows and any prefix
        # of a feasible final state is feasible, so ask order is fine
        # (and commit_wave is None).
        order = list(range(pb.n_place))
        if res.commit_wave is not None:
            cwave = np.asarray(res.commit_wave)
            order.sort(key=lambda p: (int(cwave[p]) if cwave[p] >= 0
                                      else np.iinfo(np.int32).max, p))
        by_p: Dict[int, Placement] = {}
        for p in order:
            g = int(pb.p_ask[p])
            ask = asks[g]
            m = AllocMetric()
            m.nodes_evaluated = pb.n_real
            m.nodes_available = dict(by_dc or {})
            if unfinished[p]:
                # never decided: its per-wave metric slots were never
                # written, so don't fabricate filtered/exhausted counts
                m.nodes_filtered = 0
            else:
                m.nodes_filtered = pb.n_real - int(n_feasible[p])
                for ci, label in enumerate(pb.constraint_labels[g]):
                    cnt = int(cons_filtered[g, ci])
                    if cnt:
                        m.constraint_filtered[label] = cnt
                m.nodes_exhausted = int(n_exhausted[p])
                for d in range(NUM_R):
                    cnt = int(dim_exhausted[p, d])
                    if cnt:
                        m.dimension_exhausted[_DIM_NAMES[d]] = cnt

            placed = None
            ask_vec = pb.ask_res[g]
            if (evict is not None and evict[p].any()
                    and bool(choice_ok[p, 0])):
                # in-kernel preemption pass committed this placement:
                # slot 0 is its single node (no fall-through — the
                # victim set is node-specific); validate the discrete
                # leftovers with the victims removed, then charge
                # host_used with the NET usage (ask minus freed)
                placed = self._evict_commit(
                    int(choice[p, 0]), g, ask, pb, sol_nodes,
                    allocs_by_node, evict[p], host_used,
                    float(score[p, 0]), m, dev_tally, port_tally)
                if placed is not None:
                    by_p[p] = placed
                    continue
                # discrete fixup failed (ports, stale victim view):
                # fall through as a normal failure — the scheduler's
                # host-side preemption walk remains the safety net
            net_refused: Dict[str, int] = {}
            for k in range(TOP_K):
                if not choice_ok[p, k]:
                    break
                ni = int(choice[p, k])
                node = sol_nodes[ni]
                if not np.all(host_used[ni] + ask_vec <= pb.avail[ni]):
                    continue
                gid = int(pb.distinct[g])
                if gid >= 0 and ni in chosen_by_ask.get(gid, ()):
                    continue
                prop_vals = self._property_fit(node, ask, prop_used)
                if prop_vals is None:
                    prop_refused += 1
                    continue
                refused0 = port_tally.refused
                resources = self._host_commit(node, ni, ask, net_cache,
                                              dev_cache, allocs_by_node,
                                              dev_tally, port_tally)
                if resources is None:
                    if port_tally.refused > refused0:
                        why = "network: " + port_tally.reason
                        net_refused[why] = net_refused.get(why, 0) + 1
                    continue
                host_used[ni] += ask_vec
                if gid >= 0:
                    chosen_by_ask.setdefault(gid, set()).add(ni)
                for key, val in prop_vals:
                    by_val = prop_used.setdefault(key, {})
                    by_val[val] = by_val.get(val, 0) + 1
                m.score_meta = [
                    {"node_id": pb.node_ids[int(choice[p, j])],
                     "normalized_score": float(score[p, j])}
                    for j in range(TOP_K) if choice_ok[p, j]]
                placed = Placement(ask_index=g, node=node,
                                   score=float(score[p, k]), metrics=m,
                                   resources=resources)
                break
            if placed is None:
                retryable = bool(unfinished[p])
                if retryable:
                    # the wave budget ran out before this placement was
                    # decided; the scheduler's retry loop re-solves it
                    reason = "solve wave budget exhausted (retryable)"
                elif net_refused:
                    # the network assignment refused the candidates it
                    # was offered (rank.go: such a node is exhausted and
                    # the iterator goes on).  Where the wave saw more
                    # placeable nodes than were walked, the next solve,
                    # which starts from this plan's commits, offers
                    # them: retry, as for a placement left undecided
                    n = sum(net_refused.values())
                    m.nodes_exhausted += n
                    for why, cnt in net_refused.items():
                        m.dimension_exhausted[why] = \
                            m.dimension_exhausted.get(why, 0) + cnt
                    reason = max(net_refused, key=net_refused.get)
                    retryable = int(n_feasible[p]) \
                        - int(n_exhausted[p]) > n
                elif n_feasible[p] > 0:
                    reason = "resources exhausted"
                else:
                    reason = "no feasible nodes"
                placed = Placement(ask_index=g, node=None, score=0.0,
                                   metrics=m, failed_reason=reason,
                                   retryable=retryable)
            by_p[p] = placed
        # emit in ask order regardless of replay order: the scheduler
        # maps placements back to its per-ask missing queues by
        # position
        placements: List[Placement] = [by_p[p]
                                       for p in range(pb.n_place)]
        if dev_tally.commits:
            # the device part of the walk, once a solve: a layer span a
            # placement would cost more than the work it times
            _m.incr_counter("solver.device.instances",
                            dev_tally.instances)
            _m.incr_counter("solver.device.refused", dev_tally.refused)
            if spans == "solve":
                # no metric reads the fused round's
                _tr.summed("solve.devices", dev_tally.seconds)
        if port_tally.commits:
            # and the network part, the same way
            _m.incr_counter("solver.ports.assigned", port_tally.assigned)
            _m.incr_counter("solver.ports.refused", port_tally.refused)
            _m.incr_counter("solver.ports.static_columns",
                            static_port_columns(pb))
            if spans == "solve":
                _tr.summed("solve.ports", port_tally.seconds)
        if pb.pl_slot is not None:
            # the wave's property slots, once a solve: what it counted,
            # and what the fixup's re-check still refused (expected 0)
            _m.incr_counter("solver.property.columns", pb.pl_values)
            _m.incr_counter("solver.property.refused", prop_refused)

        # class eligibility for blocked-eval optimization
        class_elig: List[Dict[str, bool]] = []
        with _tr.layer(spans + ".class_elig"):
            node_class = pb.node_class[:pb.n_real]
            inv_class = {v: k for k, v in pb.class_ids.items()}
            for g in range(pb.n_asks):
                fg = feas[g, :pb.n_real]
                elig: Dict[str, bool] = {}
                for cid, cname in inv_class.items():
                    members = node_class == cid
                    if members.any():
                        elig[cname] = bool(fg[members].any())
                class_elig.append(elig)

        return SolveOutput(placements=placements,
                           class_eligibility=class_elig,
                           trace=trace_attrs)

    def _evict_commit(self, ni: int, g: int, ask: PlacementAsk,
                      pb: PackedBatch, sol_nodes, allocs_by_node,
                      ev_row: np.ndarray, host_used: np.ndarray,
                      score: float, m: AllocMetric,
                      dev_tally: _DeviceTally,
                      port_tally: _PortTally) -> Optional[Placement]:
        """Host fixup for a kernel-committed (place, evict) pair: map
        the victim-slot mask back to alloc ids through the packed
        `ev_ids` rows, re-check capacity net of the freed usage, and
        run the discrete port/device assignment against the node MINUS
        its victims (fresh accounting — the shared caches still hold
        the victims' reservations).  Returns None when the discrete
        leftovers fail; the caller falls back to the host preemption
        walk."""
        if pb.ev_ids is None or ni >= len(pb.ev_ids):
            return None
        node = sol_nodes[ni]
        victim_ids = [pb.ev_ids[ni][e] for e in np.nonzero(ev_row)[0]
                      if e < len(pb.ev_ids[ni]) and pb.ev_ids[ni][e]]
        if not victim_ids:
            return None
        vset = set(victim_ids)
        proposed = (list(allocs_by_node.get(node.id, ()))
                    if allocs_by_node is not None else [])
        victims = [a for a in proposed if a.id in vset]
        if len(victims) != len(vset):
            # the lazy view and the packed planes disagree (stale
            # world): refuse rather than evict the wrong alloc
            return None
        freed = np.zeros(NUM_R, np.float32)
        for a in victims:
            freed += alloc_usage_vector(a)
        ask_vec = pb.ask_res[g]
        if not np.all(host_used[ni] + ask_vec - freed
                      <= pb.avail[ni]):
            return None
        remaining = [a for a in proposed if a.id not in vset]
        resources = self._host_commit(node, ni, ask, {}, {},
                                      {node.id: remaining}, dev_tally,
                                      port_tally)
        if resources is None:
            return None
        host_used[ni] += ask_vec - freed
        m.score_meta = [{"node_id": pb.node_ids[ni],
                         "normalized_score": score}]
        return Placement(ask_index=g, node=node, score=score,
                         metrics=m, resources=resources,
                         evicted=sorted(victim_ids))

    @staticmethod
    def _host_commit(node: Node, node_ix: int, ask: PlacementAsk,
                     net_cache: Dict[int, NetworkIndex],
                     dev_cache: Dict[int, DeviceAccounter],
                     allocs_by_node,
                     dev_tally: Optional[_DeviceTally] = None,
                     port_tally: Optional[_PortTally] = None
                     ) -> Optional[AllocatedResources]:
        """Build AllocatedResources with real ports + device instance ids.

        Works on clones and reserves each offer immediately, so multiple
        tasks in one group see each other's ports/instances; the clone is
        only promoted into the cache on success (all-or-nothing).
        Returns None if the discrete assignment fails on this node.
        The node's DeviceAccounter is built, and `dev_tally` (a
        solve's; the schedulers' own walks keep none) written, only
        where a task of the group asks for a device; `port_tally` only
        where the group asks for a network.
        """
        t0 = _t.perf_counter()
        idx, net_offers, why = Solver._network_offers(
            node, node_ix, ask, net_cache, allocs_by_node)
        if port_tally is not None and (net_offers or idx is None):
            port_tally.add(
                None if idx is None else
                [o for got in net_offers.values() for o in got],
                why, _t.perf_counter() - t0)
        if idx is None:
            return None

        acct, dev_offers = None, {}
        if any(t.resources.devices for t in ask.tg.tasks):
            t0 = _t.perf_counter()
            offered = Solver._device_offers(node, node_ix, ask, dev_cache,
                                            allocs_by_node)
            if dev_tally is not None:
                dev_tally.add(None if offered is None else offered[1],
                              _t.perf_counter() - t0)
            if offered is None:
                return None
            acct, dev_offers = offered

        out = AllocatedResources()
        for t in ask.tg.tasks:
            tr = AllocatedTaskResources(cpu=t.resources.cpu,
                                        memory_mb=t.resources.memory_mb)
            tr.networks.extend(net_offers.get(t.name, ()))
            tr.devices.extend(dev_offers.get(t.name, ()))
            out.tasks[t.name] = tr
        out.shared = AllocatedSharedResources(
            disk_mb=ask.tg.ephemeral_disk.size_mb,
            networks=net_offers.get(None, []))
        net_cache[node_ix] = idx
        if acct is not None:
            dev_cache[node_ix] = acct
        return out

    @staticmethod
    def _network_offers(node: Node, node_ix: int, ask: PlacementAsk,
                        net_cache: Dict[int, NetworkIndex],
                        allocs_by_node):
        """The network part of `_host_commit`: a clone of the node's
        NetworkIndex (built from the node and its allocs at its first
        touch in a solve) with an offer for every network ask of the
        group reserved on it, those offers by task name (None: the
        group's own), and "".  (None, {}, why) where the node cannot
        serve an ask: the wave counted bandwidth and static ports, the
        values are settled here."""
        idx = net_cache.get(node_ix)
        if idx is None:
            idx = NetworkIndex()
            idx.set_node(node)
            if allocs_by_node is not None:
                idx.add_allocs(allocs_by_node.get(node.id, ()))
            net_cache[node_ix] = idx
        idx = idx.clone()
        offers: Dict[Optional[str], list] = {}
        for name, asks in [(t.name, t.resources.networks)
                           for t in ask.tg.tasks] \
                + [(None, ask.tg.networks)]:
            for ask_net in asks:
                offer, why = idx.assign_network(ask_net)
                if offer is None:
                    return None, {}, why
                idx.add_reserved(offer)
                offers.setdefault(name, []).append(offer)
        return idx, offers, ""

    @staticmethod
    def _device_offers(node: Node, node_ix: int, ask: PlacementAsk,
                       dev_cache: Dict[int, DeviceAccounter],
                       allocs_by_node):
        """The device part of `_host_commit`: a clone of the node's
        accounter (built from the node's allocs at its first touch in a
        solve) with the instance ids of every task of the group reserved
        on it, and those offers by task name.  None where the node
        cannot serve an ask: the device's fit counted instances, the ids
        are settled here."""
        acct = dev_cache.get(node_ix)
        if acct is None:
            acct = DeviceAccounter(node)
            if allocs_by_node is not None:
                acct.add_allocs(allocs_by_node.get(node.id, ()))
            dev_cache[node_ix] = acct
        acct = acct.clone()
        offers: Dict[str, list] = {}
        for t in ask.tg.tasks:
            for d in t.resources.devices:
                got = Solver._assign_devices(acct, node, d)
                if got is None:
                    return None
                acct.add_reserved(got.vendor, got.type, got.name,
                                  got.device_ids)
                offers.setdefault(t.name, []).append(got)
        return acct, offers

    @staticmethod
    def _property_fit(node: Node, ask: PlacementAsk,
                      used: Dict[tuple, Dict[str, int]]):
        """Check distinct_property limits against existing + in-batch counts:
        the re-check of what the wave counted (tensorize "Property
        limits"), so it refuses nothing where the wave chose the node.
        Limits are keyed (scope, attr target); charges under one key are
        shared across all asks carrying it (job-level scope spans the whole
        batch). Returns the (key, value) pairs to charge on acceptance, or
        None if any property is at its limit."""
        if not ask.property_limits:
            return ()
        from ..structs import resolve_node_target
        out = []
        for key, (limit, existing) in ask.property_limits.items():
            val, ok = resolve_node_target(node, property_target(key))
            if not ok:
                # nodes missing the property are infeasible for
                # distinct_property (reference: propertyset.go:240)
                return None
            val = str(val)
            count = existing.get(val, 0) + used.get(key, {}).get(val, 0)
            if count + 1 > limit:
                return None
            out.append((key, val))
        return out

    @staticmethod
    def _assign_devices(acct: DeviceAccounter, node: Node, req
                        ) -> Optional[AllocatedDeviceResource]:
        """Pick free instance ids matching the request pattern
        (reference: scheduler/device.go:32 AssignDevice)."""
        for dev in node.node_resources.devices:
            dv, dt, dm = dev.id_tuple()
            if not req.matches(dv, dt, dm):
                continue
            free = acct.free_instances(dv, dt, dm)
            if len(free) >= req.count:
                return AllocatedDeviceResource(
                    vendor=dv, type=dt, name=dm,
                    device_ids=free[:req.count])
        return None


class PlanSolverView:
    """Read-only facade over a worker's Solver for what-if planning
    (`/v1/job/:id/plan`, ISSUE 7): dry-run schedulers share the
    resident template — plan solves answer at steady-state speed
    instead of re-walking the cluster — but every solve goes through
    the copy-on-read overlay with `overlay_only` pinned, so the world
    is never created, synced, rebuilt, grown, or fed from a plan.
    Carried usage stays bit-identical under any plan/solve
    interleaving (tests/test_plan_overlay.py)."""

    def __init__(self, inner: Solver):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def resident_active(self, snapshot=None) -> bool:
        # only ride a world that already exists; a plan never builds one
        return (self._inner._resident != "off"
                and self._inner._world is not None)

    def note_plan_result(self, plan, result) -> None:
        return None              # dry-run plans never feed the world

    def set_degraded(self, degraded: bool) -> None:
        return None              # brownout belongs to the worker

    def solve(self, *args, **kw) -> SolveOutput:
        kw["_overlay_only"] = True
        return self._inner.solve(*args, **kw)


def solve_trace_attrs(pb: PackedBatch, res,
                      lane_counters: Optional[Dict] = None) -> Dict:
    """Flight-recorder attributes for one kernel run: the device wave/
    rescore/evict counters from the SolveResult plus the ISSUE-4
    two-tier modeled HBM bytes for this solve shape.  Pure read — the
    result arrays were fetched by the caller's unpack anyway.

    `lane_counters` (ISSUE 20): when the solve ran through the chunked
    scan-of-vmap stream (ResidentSolver.lane_counters()), the lane
    width and the cross-lane revalidation's bounce accounting join the
    trace — the explainability surface the bit-identity property test
    pins at L=1."""
    import numpy as _np
    waves = int(_np.asarray(res.n_waves))
    rescore = (int(_np.asarray(res.n_rescore))
               if res.n_rescore is not None else waves)
    evicted = (int(_np.asarray(res.evict).any(axis=1).sum())
               if res.evict is not None else 0)
    # where the result arrays live: numpy means a host twin answered
    # (prefer_host, a watchdog failover); a jax array names its
    # device's platform, so a CPU-backend run can never pass for a chip
    if isinstance(res.choice, _np.ndarray):
        backend, platform = "host", "numpy"
    else:
        backend = "device"
        platform = next(iter(res.choice.devices())).platform
    from . import pallas_kernel as _pk
    from .kernel import resolve_shortlist_c, window_tk
    from .resident import model_wave_bytes
    Np, R = pb.avail.shape
    Gp = pb.ask_res.shape[0]
    K = pb.p_ask.shape[0]
    S, V = pb.sp_desired.shape[1:3]
    has_spread = bool((_np.asarray(pb.sp_col[:, 0]) >= 0).any())
    # the one-shot path passes no group_count_hint, so this is the
    # window — and with it the pallas mode — solve_kernel resolved
    TK = window_tk(Gp, K, Np)
    mode = ("off" if backend == "host"
            else _pk.resolve_mode(Np, Gp, TK, V, has_spread))
    C = (0 if (bool((_np.asarray(pb.distinct) >= 0).any())
               or pb.pl_slot is not None)
         else resolve_shortlist_c(Np, TK, 0))
    b1, brw, _passes = model_wave_bytes(Np, Gp, K, S, R, has_spread,
                                        mode, TK, C)
    attrs = {"n_asks": int(pb.n_asks), "n_place": int(pb.n_place),
             "n_nodes": int(pb.n_real), "backend": backend,
             "platform": platform, "pallas_mode": mode,
             "waves": waves, "rescore_waves": rescore,
             "shortlist_waves": waves - rescore,
             "evict_commits": evicted,
             "unfinished": int(_np.asarray(res.unfinished).sum()),
             "bytes_wave1": int(b1), "bytes_rewave": int(brw),
             "modeled_bytes_total": int(
                 b1 * rescore + brw * (waves - rescore))}
    if lane_counters is not None:
        attrs["lanes"] = int(lane_counters.get("lanes", 1))
        attrs["lane_chunks"] = int(lane_counters.get("chunks", 0))
        attrs["lane_bounced"] = int(lane_counters.get("bounced", 0))
        attrs["lane_committed"] = int(lane_counters.get("committed", 0))
        attrs["lane_bounce_rate"] = float(
            lane_counters.get("bounce_rate", 0.0))
    return attrs


def _run_kernel(pb: PackedBatch, host_mode: str = "auto",
                pallas: str = "auto", max_waves: int = 0,
                preempt: bool = False, materialize: bool = True):
    """`materialize=False` is the async-dispatch mode: the device
    kernel is launched but its result is NOT fetched — the caller owns
    the later materialization (PendingSolve.wait).  Ignored on the host
    path (numpy is eager) and forced on under the watchdog (its
    deadline must cover the fetch)."""
    import numpy as _np
    has_spread = bool((_np.asarray(pb.sp_col[:, 0]) >= 0).any())
    # in-kernel preemption (ISSUE 7): only when the batch carries the
    # evictable-alloc planes (resident path, evict_width() > 0) and has
    # no distinct_hosts groups — cross-group blocking is invisible to
    # the eviction pass, so those batches keep the host-side walk.
    # Host twin and device kernel get the SAME decision (bit-identity).
    # the batch's distinct_property slots (tensorize "Property limits");
    # like distinct_hosts they keep the host-side preemption walk
    pl_kw = ({} if pb.pl_slot is None else
             dict(pl_slot=pb.pl_slot, pl_col=pb.pl_col, pl_left=pb.pl_left))
    ev_kw = {}
    if (preempt and pb.ev_prio is not None and not pl_kw
            and not bool((_np.asarray(pb.distinct) >= 0).any())):
        ev_kw = dict(has_preempt=True, ev_res=pb.ev_res,
                     ev_prio=pb.ev_prio, ask_prio=pb.ask_prio)
        if max_waves == 0:
            # eviction commits serialize one-per-node-per-wave, so an
            # overcommitted batch needs more waves than the default
            # budget; host twin and device kernel get the same value
            from .kernel import MAX_WAVES
            max_waves = 2 * MAX_WAVES
    if host_mode != "never":
        from .host import host_solve_kernel, prefer_host
        if host_mode == "always" or prefer_host(
                pb.avail.shape[0], pb.n_asks, pb.n_place):
            return host_solve_kernel(*_kernel_args(pb),
                                     has_spread=has_spread,
                                     max_waves=max_waves, **ev_kw,
                                     **pl_kw)
    # "auto" resolves to the pallas fused wave on TPU backends (or when
    # NOMAD_TPU_PALLAS forces it) and to the unfused kernel otherwise —
    # placement-identical either way (tests/test_pallas_kernel.py)
    host_ev_kw = dict(ev_kw, **pl_kw)
    if ev_kw:
        # the eviction pass statically asserts no distinct batches;
        # the check above established it for this batch
        ev_kw["has_distinct"] = False

    def _device():
        from ..chaos.injection import global_injections
        inj = global_injections.get("device_solve")
        if inj is not None:
            inj.fire()
        # lane_axis stays None on the one-shot path: the lane-uniform
        # predicate form (psum over the vmap axis) only exists inside
        # the chunked scan-of-vmap stream — a one-shot solve under a
        # lane axis would trade its carried-window cond for a
        # collective for no reason (ISSUE 20)
        args = _kernel_args(pb)
        # what this solve hands the device from the host: a numpy
        # argument crosses on every call, one already on the device is
        # a jax array and is not counted
        _m.incr_counter("solver.h2d_bytes", float(sum(
            a.nbytes for a in args + tuple(pl_kw.values())
            if isinstance(a, _np.ndarray))))
        res = solve_kernel(*args, has_spread=has_spread,
                           pallas_mode=pallas, max_waves=max_waves,
                           lane_axis=None, **ev_kw, **pl_kw)
        # materialize under the watchdog deadline: an async dispatch
        # that only wedges at a later fetch would escape it
        if materialize or global_watchdog.enabled:
            _np.asarray(res.choice)
        return res

    from .watchdog import global_watchdog
    if not global_watchdog.enabled:
        return _device()

    def _host():
        from .host import host_solve_kernel
        return host_solve_kernel(*_kernel_args(pb),
                                 has_spread=has_spread,
                                 max_waves=max_waves, **host_ev_kw)

    res, _backend = global_watchdog.run(
        _device, _host, label=f"solve:{pb.n_asks}x{pb.n_real}")
    return res


def _kernel_args(pb: PackedBatch):
    return (
        pb.avail, pb.reserved, pb.used0, pb.valid, pb.node_dc, pb.attr_rank,
        pb.ask_res, pb.ask_desired, pb.distinct, pb.dc_ok, pb.host_ok,
        pb.coll0,
        pb.penalty, pb.c_op, pb.c_col, pb.c_rank, pb.a_op, pb.a_col,
        pb.a_rank, pb.a_weight, pb.a_host, pb.sp_col, pb.sp_weight,
        pb.sp_targeted,
        pb.sp_desired, pb.sp_implicit, pb.sp_used0, pb.dev_cap, pb.dev_used0,
        pb.dev_ask, pb.p_ask, pb.n_place)
