"""Federated resident solve: R regions fused into ONE device call.

The reference federates by running an independent server cluster per
region and forwarding RPCs between them (nomad/serf.go WAN gossip,
nomad/rpc.go `forward`); each region's scheduler is oblivious to the
others.  The TPU recast keeps that isolation — each region owns its own
node universe, usage tensors, and eval stream — but fuses the *solves*:
every stream step carries one batch per region, vmapped over a leading
region axis inside a single `lax.scan` device program.  One dispatch and
one result fetch cover every region's whole workload, where R separate
streams would pay R dispatches and R fetches (see solver/resident.py).

On a multi-chip mesh the region axis is the natural sharding axis: the
same program with the vmap replaced by a `shard_map` over a
`Mesh(('region',))` places one region's universe per chip and needs no
cross-chip collectives at all — regions never share state (see
parallel/sharded.federated_solve for the mesh variant used by the
multi-chip dryrun).

Semantics per region are identical to ResidentSolver.solve_stream:
resource usage carries batch-to-batch on device, job-scoped state is
seeded per batch, and the per-job stream guard applies within a region's
stream.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..structs import Node
from ..solver.kernel import NEG_INF, TOP_K
from ..solver.resident import (ResidentSolver, STATUS_COMMITTED,
                               STATUS_FAILED, STATUS_RETRY, _ASK_ARGS,
                               _solve_one)
from ..solver.tensorize import PackedBatch, PlacementAsk


@functools.partial(jax.jit,
                   static_argnames=("has_spread", "group_count_hint",
                                    "max_waves", "has_distinct",
                                    "has_devices", "compact"))
def _federated_stream_kernel(avail, reserved, valid, node_dc, attr_rank,
                             dev_cap, used0, dev_used0, stacked, n_places,
                             seeds, has_spread=True, group_count_hint=0,
                             max_waves=0, has_distinct=True,
                             has_devices=True, compact=True):
    """Node args carry a leading [R] region axis; `stacked` ask tensors
    carry [B, R, ...]; scan over B steps, vmap over R regions."""

    def step(carry, xs):
        used, dev_used = carry                       # [R, ...]
        batch, n_place, seed = xs                    # [R, ...] each

        def one_region(av, rs_, vl, ndc, ar, dcp, u, du, b, n, s):
            # "while" wave mode: under this vmap a cond-skipped scan
            # would execute every budget wave for every region lane
            # (cond lowers to select when batched); the while_loop runs
            # exactly as many waves as the slowest region needs
            return _solve_one(av, rs_, vl, ndc, ar, dcp, u, du, b, n, s,
                              has_spread, group_count_hint, max_waves,
                              "while", has_distinct, has_devices,
                              # under the region vmap the shortlist
                              # cond lowers to select (both branches
                              # run every wave) — keep it off
                              shortlist_c=-1)

        res = jax.vmap(one_region)(avail, reserved, valid, node_dc,
                                   attr_rank, dev_cap, used, dev_used,
                                   batch, n_place, seed)
        status = jnp.where(res.choice_ok[:, :, 0], STATUS_COMMITTED,
                           jnp.where(res.unfinished, STATUS_RETRY,
                                     STATUS_FAILED))
        if compact:
            from ..solver.resident import pack_out_compact
            packed = pack_out_compact(res.choice, res.score, status)
        else:
            packed = jnp.concatenate(
                [res.choice.astype(jnp.float32), res.score,
                 status.astype(jnp.float32)[:, :, None]], axis=-1)
        return (res.used_final, res.dev_used_final), packed

    (used_f, dev_used_f), out = jax.lax.scan(
        step, (used0, dev_used0), (stacked, n_places, seeds))
    return used_f, dev_used_f, out                   # out [B, R, K, .]


class FederatedResidentSolver:
    """R regional node universes solved in one fused device stream.

    Every region gets its own ResidentSolver for packing (merge_asks /
    pack_batch run against that region's rank universe); the node-side
    tensors are stacked [R, ...] once at construction.  All regions'
    templates must agree on padded shapes — build them from the same
    probe asks over same-sized clusters (pass `gp`/`kp` explicitly to
    pin the ask-side padding).
    """

    def __init__(self, region_nodes: Sequence[Sequence[Node]],
                 probe_asks: Sequence[PlacementAsk],
                 gp: Optional[int] = None, kp: Optional[int] = None,
                 max_waves: int = 0, evict_e: int = 0):
        if not region_nodes:
            raise ValueError("need at least one region")
        # regions passed the SAME node-list object share one packed
        # template and tensorizer (packing a 10K-node universe costs
        # ~1s; usage stays per-region in the fed-level stacks, so
        # sharing is purely a pack-once optimization)
        # keep the keyed list object alive alongside its solver: a
        # freed list's id could be reused by a different region's list
        # and silently alias their universes
        shared: Dict[int, Tuple[object, ResidentSolver]] = {}
        self.solvers = []
        for nodes in region_nodes:
            entry = shared.get(id(nodes))
            if entry is None or entry[0] is not nodes:
                entry = (nodes, ResidentSolver(nodes, probe_asks,
                                               gp=gp, kp=kp,
                                               max_waves=max_waves,
                                               evict_e=evict_e))
                shared[id(nodes)] = entry
            self.solvers.append(entry[1])
        self.R = len(self.solvers)
        self.gp = self.solvers[0].gp
        self.kp = self.solvers[0].kp
        self.max_waves = max_waves
        # ragged regions (ISSUE 13): unequal universes pad to the max
        # padded node axis with DEAD rows (the same tile-granular row
        # extension the elastic grow path uses) instead of rejecting —
        # dead slots are invalid, score nothing, and never win, so a
        # padded region solves bit-identically to its unpadded self
        np_max = max(s.template.avail.shape[0] for s in self.solvers)
        for s in {id(s): s for s in self.solvers}.values():
            Np = s.template.avail.shape[0]
            if Np < np_max:
                from ..solver.tensorize import extend_template_rows
                extend_template_rows(s.template, np_max - Np)
                s._compact = np_max < 32768
                s._default_host_ok = np.zeros((s.gp, np_max), bool)
                s._default_host_ok[:, :s.template.n_real] = True
        # non-node dims cannot be padded away — name the region so a
        # mis-built federation fails loudly, not at trace time
        for name in ("attr_rank", "dc_ok", "dev_cap"):
            ref_dim = tuple(getattr(self.solvers[0].template,
                                    name).shape)
            for r, s in enumerate(self.solvers):
                dim = tuple(getattr(s.template, name).shape)
                if dim != ref_dim:
                    raise ValueError(
                        f"region {r} disagrees on {name} shape: "
                        f"{dim} vs region 0's {ref_dim}; regions "
                        "must share attribute/datacenter/device "
                        "universes (node counts may differ)")
        t0 = self.solvers[0].template
        self._node_stack = {
            "avail": jax.device_put(np.stack(
                [s.template.avail for s in self.solvers])),
            "reserved": jax.device_put(np.stack(
                [s.template.reserved for s in self.solvers])),
            "valid": jax.device_put(np.stack(
                [s.template.valid for s in self.solvers])),
            "node_dc": jax.device_put(np.stack(
                [s.template.node_dc for s in self.solvers])),
            "attr_rank": jax.device_put(np.stack(
                [s.template.attr_rank for s in self.solvers])),
            "dev_cap": jax.device_put(np.stack(
                [s.template.dev_cap for s in self.solvers])),
        }
        self._used = jax.device_put(np.stack(
            [s.template.used0 for s in self.solvers]))
        self._dev_used = jax.device_put(np.stack(
            [s.template.dev_used0 for s in self.solvers]))
        self._const_cache: Dict = {}
        self._default_host_ok = np.stack(
            [s._default_host_ok for s in self.solvers])  # [R, gp, Np]

    # ---------------- packing (delegates per region) ----------------
    def merge_asks(self, region: int, asks: Sequence[PlacementAsk]):
        return self.solvers[region].merge_asks(asks)

    def pack_batch(self, region: int, asks: Sequence[PlacementAsk],
                   job_keys: Optional[set] = None
                   ) -> Optional[PackedBatch]:
        return self.solvers[region].pack_batch(asks, job_keys=job_keys)

    def pack_batch_cached(self, region: int,
                          asks: Sequence[PlacementAsk],
                          job_keys: Optional[set] = None
                          ) -> Optional[PackedBatch]:
        return self.solvers[region].pack_batch_cached(asks,
                                                      job_keys=job_keys)

    # ---------------- solving ----------------
    def solve_stream(self, batches: Sequence[Sequence[PackedBatch]],
                     seeds: Optional[Sequence[Sequence[int]]] = None):
        """batches[r][b]: region r's b-th batch; every region must carry
        the same number of steps (pad with an empty repeat batch if a
        region's workload is shorter).  Returns (choice, ok, score,
        status) each with leading [R, B] axes."""
        return self.finish_stream(self.solve_stream_async(batches, seeds))

    def solve_stream_async(self,
                           batches: Sequence[Sequence[PackedBatch]],
                           seeds=None):
        NBs = {len(rb) for rb in batches}
        if len(batches) != self.R or len(NBs) != 1:
            raise ValueError(
                f"need {self.R} regions with equal step counts, got "
                f"{[len(rb) for rb in batches]}")
        NB = NBs.pop()
        for r, rb in enumerate(batches):
            self.solvers[r]._check_stream_jobs(rb)
        stacked = self._stack_args(batches, NB)
        n_places = np.asarray(
            [[batches[r][b].n_place for r in range(self.R)]
             for b in range(NB)], np.int32)               # [B, R]
        if seeds is None:
            seed_arr = np.zeros((NB, self.R), np.int32)
        else:
            seed_arr = np.asarray(
                [[seeds[r][b] for r in range(self.R)]
                 for b in range(NB)], np.int32)
        flat = [pb for rb in batches for pb in rb]
        self._used, self._dev_used, out = _federated_stream_kernel(
            self._node_stack["avail"], self._node_stack["reserved"],
            self._node_stack["valid"], self._node_stack["node_dc"],
            self._node_stack["attr_rank"], self._node_stack["dev_cap"],
            self._used, self._dev_used, stacked, n_places, seed_arr,
            has_spread=ResidentSolver._has_spread(flat),
            group_count_hint=ResidentSolver._group_count_hint(flat),
            max_waves=self.max_waves,
            has_distinct=ResidentSolver._has_distinct(flat),
            has_devices=ResidentSolver._has_devices(flat),
            compact=self.solvers[0]._compact)
        return out

    def finish_stream(self, out) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray, np.ndarray]:
        from ..solver.resident import unpack_stream
        out = np.asarray(out)                        # [B, R, K, .]
        out = np.swapaxes(out, 0, 1)                 # [R, B, K, .]
        return unpack_stream(out)

    def _stack_args(self, batches, NB):
        """[B, R, ...] host stack with the device-resident zero-constant
        shortcut for the big [G, N] tensors (see ResidentSolver).  A
        re-dispatched step (same PackedBatch objects — the steady-state
        delta-wave schedule) returns its fully device-put dict from
        cache and ships nothing.

        The cache key includes every region solver's resident NODE
        EPOCH (bumped by apply_delta/repack): a delta applied to a
        region between steps invalidates that step's cached stack, so a
        re-dispatch can never serve ask planes packed against the old
        node universe.  It ALSO keys on each solver's EVICT-PLANE epoch
        (ISSUE 8 satellite): PR 7's ev rows advance on pure alloc
        place/stop deltas that never move the node epoch — today the
        stacked dict carries no ev operand (the federated kernel solves
        preemption-free), but any future ev plumbing through this stack
        would otherwise serve rows from before the replay, so the key
        is pinned conservatively now and the regression test holds it."""
        step_key = (tuple(s._node_epoch for s in self.solvers),
                    tuple(s._ev_epoch for s in self.solvers),
                    tuple(id(pb) for rb in batches for pb in rb))
        cached = getattr(self, "_step_cache", None)
        if cached is None:
            cached = self._step_cache = {}
        flat_pbs = [pb for rb in batches for pb in rb]
        hit = cached.get(step_key)
        if hit is not None and len(hit[0]) == len(flat_pbs) \
                and all(a is b for a, b in zip(hit[0], flat_pbs)):
            return hit[1]
        stacked = {}
        for name in _ASK_ARGS:
            mats = [[getattr(batches[r][b], name) for r in range(self.R)]
                    for b in range(NB)]
            if name in ("coll0", "penalty", "a_host") and not any(
                    m.any() for row in mats for m in row):
                ckey = (name, NB)
                if ckey not in self._const_cache:
                    self._const_cache[ckey] = jax.device_put(np.zeros(
                        (NB, self.R) + mats[0][0].shape,
                        mats[0][0].dtype))
                stacked[name] = self._const_cache[ckey]
                continue
            if name == "host_ok" and all(
                    np.array_equal(m, self._default_host_ok[r])
                    for row in mats for r, m in enumerate(row)):
                ckey = (name, NB)
                if ckey not in self._const_cache:
                    self._const_cache[ckey] = jax.device_put(
                        np.broadcast_to(
                            self._default_host_ok[None],
                            (NB,) + self._default_host_ok.shape).copy())
                stacked[name] = self._const_cache[ckey]
                continue
            stacked[name] = np.stack(
                [np.stack(row) for row in mats])
        dev = {k: (jax.device_put(v) if isinstance(v, np.ndarray)
                   else v) for k, v in stacked.items()}
        if len(cached) > 64:
            cached.clear()
        cached[step_key] = (flat_pbs, dev)
        return dev

    # ---------------- compile-cache surface ----------------
    @staticmethod
    def compile_count() -> int:
        """Traced-computation count of the federated stream kernel.
        The jit keys on the stacked operand shapes — which carry the
        region count R and every padded dim — plus the static config,
        so adding a region (new [B, R, ...] shapes) costs exactly one
        new entry and leaves every existing entry warm."""
        return int(_federated_stream_kernel._cache_size())

    # ---------------- usage ----------------
    def usage(self) -> Tuple[np.ndarray, np.ndarray]:
        return np.asarray(self._used), np.asarray(self._dev_used)

    def reset_usage(self, used0: Optional[np.ndarray] = None,
                    dev_used0: Optional[np.ndarray] = None) -> None:
        if used0 is None:
            used0 = np.stack([s.template.used0 for s in self.solvers])
        if dev_used0 is None:
            dev_used0 = np.stack(
                [s.template.dev_used0 for s in self.solvers])
        # copy before placing: CPU device_put can alias a caller-owned
        # numpy buffer zero-copy, and a later in-place edit on the
        # caller's side would leak into the resident usage carry (the
        # PR-5 double-charge class; nomadlint ALIAS503)
        self._used = jax.device_put(np.array(used0))
        self._dev_used = jax.device_put(np.array(dev_used0))

    # ---------------- health (ISSUE 15) ----------------
    def health_counters(self):
        """Union-fleet health in ONE kernel call: the [R, Np, ...]
        region stacks flatten to a single [R*Np, ...] node axis (the
        health reduction is a sum over nodes, so region boundaries
        are irrelevant — regions already share the attr/dc/device
        universes by construction).  Bit-identical to merging the
        per-region host twins."""
        from ..telemetry.health import (HealthCounters, MAX_NODES,
                                        _health_kernel)
        ns = self._node_stack
        R, Np = ns["valid"].shape
        if R * Np > MAX_NODES:
            raise ValueError(
                f"health kernel split accumulators are i32-safe up "
                f"to {MAX_NODES} stacked node rows; got {R * Np}")
        key = ("health_ask_res", 0)
        ask = self._const_cache.get(key)
        if ask is None:
            ask = self._const_cache[key] = jax.device_put(
                np.asarray(self.solvers[0].template.ask_res,
                           np.float32))
        nres = ns["avail"].shape[-1]
        raw = _health_kernel(
            ns["avail"].reshape(-1, nres),
            ns["valid"].reshape(-1),
            ns["node_dc"].reshape(-1),
            ns["dev_cap"].reshape(R * Np, -1),
            self._used.reshape(-1, nres),
            self._dev_used.reshape(R * Np, -1),
            ask, None, None, None)
        return HealthCounters.from_raw(jax.device_get(raw))

    def health_host_twin(self):
        """Per-region numpy twins, integer-merged — the reference the
        property tests hold `health_counters` to."""
        from ..telemetry.health import HealthCounters, health_host
        used, dev_used = self.usage()
        out: Optional[HealthCounters] = None
        for r, s in enumerate(self.solvers):
            hc = _twin_no_ev(s.template, used[r], dev_used[r])
            out = hc if out is None else out.merge(hc)
        return out


def _twin_no_ev(template, used, dev_used):
    """Host twin over a template whose DEVICE stack carries no ev
    planes (the federated node stack) — mask them off so the twin
    mirrors what the kernel saw."""
    from ..telemetry.health import health_host
    if getattr(template, "ev_prio", None) is None:
        return health_host(template, used, dev_used)
    import copy
    t = copy.copy(template)
    t.ev_prio = None
    t.ev_res = None
    return health_host(t, used, dev_used)


# ===================================================================
# Cross-region scheduling (ISSUE 13)
# ===================================================================

class RegionDirectory:
    """Federation membership table: region -> live gossip members,
    driven by serf WAN-gossip join/fail events (the TPU recast of
    nomad/serf.go's WAN pool — plug ``on_join``/``on_fail`` straight
    into ``membership.gossip.GossipAgent``).  Every transition lands
    in the mesh event log as a ``region.*`` event, so the agent event
    surface (and ``MeshEventLog.region_table()``) can replay the
    federation state after the fact."""

    def __init__(self, event_log=None):
        from ..utils.tracing import global_mesh_events
        self.event_log = (global_mesh_events if event_log is None
                          else event_log)
        self._members: Dict[str, set] = {}

    @staticmethod
    def _region_member(member) -> Tuple[str, str]:
        region = getattr(member, "region", None) or "global"
        mid = getattr(member, "id", None) or str(member)
        return str(region), str(mid)

    def on_join(self, member) -> None:
        region, mid = self._region_member(member)
        new_region = not self._members.get(region)
        self._members.setdefault(region, set()).add(mid)
        self.event_log.record(
            "region.join", region=region, member=mid,
            n_members=len(self._members[region]),
            new_region=bool(new_region))

    def on_fail(self, member) -> None:
        region, mid = self._region_member(member)
        self._members.get(region, set()).discard(mid)
        left = not self._members.get(region)
        self.event_log.record(
            "region.fail", region=region, member=mid,
            n_members=len(self._members.get(region, ())))
        if left:
            # last member gone: the whole region leaves the federation
            self.event_log.record("region.leave", region=region)

    def regions(self) -> List[str]:
        return sorted(r for r, m in self._members.items() if m)

    def members_of(self, region: str) -> List[str]:
        return sorted(self._members.get(region, ()))


class CrossRegionResidentSolver:
    """Cross-region SCHEDULING over one three-tier elastic mesh (the
    ISSUE 13 tentpole).

    Where FederatedResidentSolver keeps stock Nomad's isolation (each
    region's scheduler sees only its own universe; nomad/rpc.go only
    ever FORWARDS whole evals between regions), this solver places
    every eval against the UNION of all regions' nodes — the
    intentional extension stock never does.  The interconnect stays
    honest about region boundaries: the union node axis shards over a
    ``("regions", "hosts", "chips")`` mesh, each region's shards run
    the wave loop locally, candidate keys merge per host over ICI and
    per region over DCN, and only region-winner top-K key windows
    ``(score f32, global node id i32)`` cross the modeled WAN tier
    per wave — in the same ``(score desc, id asc)`` lex-merge order
    as every inner tier, so placements and ALL explainability
    counters are bit-identical to a single flat mesh (equivalently,
    the single-device host twin over the union).  Commit psums tier
    the same way: ONE commit vector crosses the WAN per region per
    wave, not one per host (see solver/kernel.py ``_psum_mesh`` /
    ``_tier_merge`` and sharded.model_ici_dcn_wan_bytes).

    Built on ElasticShardedResidentSolver, so shard loss inside a
    region degrades gracefully (the lost tiles' nodes drop out
    fleet-wide; every surviving shard keeps the device fast path) and
    ``recover()`` rejoins at the original three-tier topology."""

    def __init__(self, region_nodes: Sequence[Sequence[Node]],
                 probe_asks: Sequence[PlacementAsk], *,
                 region_names: Optional[Sequence[str]] = None,
                 n_hosts_per_region: int = 1,
                 n_devices: Optional[int] = None,
                 directory: Optional[RegionDirectory] = None,
                 **kw):
        from .sharded import (ElasticShardedResidentSolver,
                              make_three_tier_mesh)
        if not region_nodes:
            raise ValueError("need at least one region")
        self.R = len(region_nodes)
        self.region_names = (list(region_names) if region_names
                             else [f"region{r}"
                                   for r in range(self.R)])
        if len(self.region_names) != self.R:
            raise ValueError(
                f"{len(self.region_names)} region names for "
                f"{self.R} regions")
        union: List[Node] = []
        #: node id -> owning region name (the placement attribution
        #: surface: which region a cross-region placement landed in)
        self.region_of: Dict[str, str] = {}
        self._region_slices: Dict[str, Tuple[int, int]] = {}
        for name, nodes in zip(self.region_names, region_nodes):
            lo = len(union)
            union.extend(nodes)
            self._region_slices[name] = (lo, len(union))
            for n in nodes:
                self.region_of[n.id] = name
        mesh = make_three_tier_mesh(self.R, n_hosts_per_region,
                                    n_devices)
        self.solver = ElasticShardedResidentSolver(
            union, probe_asks, mesh=mesh, **kw)
        self.directory = directory
        self.event_log = self.solver.event_log
        for name, (lo, hi) in self._region_slices.items():
            self.event_log.record(
                "region.join", region=name, n_nodes=hi - lo,
                shards_per_region=self.solver.shards_per_region)

    # ---------------- delegation to the union solver ----------------
    def pack_batch(self, asks, job_keys=None):
        return self.solver.pack_batch(asks, job_keys=job_keys)

    def pack_batch_cached(self, asks, job_keys=None):
        return self.solver.pack_batch_cached(asks, job_keys=job_keys)

    def merge_asks(self, asks):
        return self.solver.merge_asks(asks)

    def solve_stream(self, batches, seeds=None):
        return self.solver.solve_stream(batches, seeds)

    def solve_stream_async(self, batches, seeds=None):
        return self.solver.solve_stream_async(batches, seeds)

    def apply_delta(self, delta):
        return self.solver.apply_delta(delta)

    def reset_usage(self, used0=None, dev_used0=None):
        return self.solver.reset_usage(used0=used0,
                                       dev_used0=dev_used0)

    def usage(self):
        return self.solver.usage()

    def health_counters(self):
        """Fleet health over the UNION mesh — the inner elastic
        solver's kernel runs with its tile-liveness mask, so a
        region-degraded mesh reports only the device-resident fleet
        (lost regions' rows drop out, exactly like the solve path)."""
        return self.solver.health_counters()

    def health_row_mask(self):
        return self.solver.health_row_mask()

    def wave_traffic(self, batches) -> Dict:
        """The full tier stack: HBM + ICI + per-region DCN + the WAN
        block (``wan_cut_vs_flat`` and the measured-counter totals —
        see ShardedResidentSolver.wave_traffic)."""
        return self.solver.wave_traffic(batches)

    @property
    def template(self):
        return self.solver.template

    @property
    def mesh_state(self) -> str:
        return self.solver.mesh_state

    # ---------------- region surfaces ----------------
    def _region_index(self, region) -> int:
        if isinstance(region, str):
            return self.region_names.index(region)
        return int(region)

    def region_shards(self, region) -> List[int]:
        """Linear shard ids owned by one region of the healthy mesh."""
        ix = self._region_index(region)
        spr = self.solver.shards_per_region
        return list(range(ix * spr, (ix + 1) * spr))

    def region_bias_plane(self, gp: int, home,
                          weight: float = 1.0) -> np.ndarray:
        """[gp, Np] region-affinity plane for the score_spec `region`
        term (solve_kernel/host_solve_kernel ``region_bias=``):
        +weight on the home region's rows, 0 elsewhere.  Driven
        backends only — see solver/score_spec.py term_region."""
        Np = self.solver.template.avail.shape[0]
        plane = np.zeros((gp, Np), np.float32)
        lo, hi = self._region_slices[self.region_names[
            self._region_index(home)]]
        plane[:, lo:hi] = np.float32(weight)
        return plane

    def fail_region_shard(self, region,
                          shard_in_region: int = 0) -> List[int]:
        """Shard loss INSIDE a region (the region-degraded state):
        the lost tiles' nodes drop out of every solve fleet-wide
        while all surviving shards — the region's remaining ones
        included — keep solving on the device fast path.  Returns
        the lost tile ids."""
        ix = self._region_index(region)
        shard = self.region_shards(ix)[shard_in_region]
        lost = self.solver.fail_shard(shard)
        self.event_log.record(
            "region.degraded", region=self.region_names[ix],
            shard=int(shard), lost_tiles=len(lost))
        return lost

    def recover_region(self) -> int:
        """Rejoin the failed shard at the original three-tier
        topology (see ElasticShardedResidentSolver.recover)."""
        recovered = self.solver.recover()
        self.event_log.record("region.recovered",
                              bytes=int(recovered))
        return recovered
