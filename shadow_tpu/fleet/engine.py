"""FleetSimulation: F independent experiments as ONE device program.

Shadow runs parameter sweeps one process per config; every solo run on the
TPU engine pays the same XLA compile and leaves the device under-occupied
at small host counts. The fleet stacks per-job state/`NetParams`/seeds
along a NEW leading vmap axis over the existing window kernel
(core/state.py stack_pytrees) and vmaps the driver kernels over it:

  * per-job HALT comes from per-lane (runahead, stop) window bounds — a
    finished job's fused-loop condition goes false and JAX's batched
    while_loop masks its lane, so jobs of different lengths finish
    raggedly without mutating each other;
  * a freed lane is REUSED: the host-side scheduler (fleet/scheduler.py)
    swaps the next queued job's freshly-built state into the lane slice —
    the compiled kernel's shapes never change, so the whole sweep costs
    ONE window-kernel compile (`kernel_traces` is the auditable metric);
  * the fleet axis composes with the islands engine: vmap-of-jobs
    OUTSIDE, shards INSIDE (parallel/islands.make_shard_run_to), so each
    lane is itself an S-shard island program;
  * per-job results ship through sliced counter/obs blocks at harvest
    (metrics schema v4 `fleet.jobs[*]`), and per-job checkpoint slices
    (fleet/checkpoint.py) make a partially-finished fleet resumable;
  * job-scoped fault quarantine: a `kill_host` injection in one job's
    fault plan drains THAT lane's rows only (the PR-3 crashed-host
    semantic, scoped to a lane), and a lane that cannot progress fails
    its job — never the fleet.

Determinism: a lane's trajectory is a pure function of its own (state,
params, window bounds); vmapped integer kernels compute the same values
as solo runs, so each job is bit-identical to the same scenario run solo
(tests/test_fleet.py asserts this for conservative AND optimistic,
global AND islands engines).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from shadow_tpu.core import gearbox, simtime
from shadow_tpu.core import engine as engine_mod
from shadow_tpu.core import hostplane as hostplane_mod
from shadow_tpu.core import pipeline as pipeline_mod
from shadow_tpu.core import pressure as pressure_mod
from shadow_tpu.core import state as state_mod
from shadow_tpu.core import supervisor as supervisor_mod
from shadow_tpu.core.config import load_config
from shadow_tpu.fleet.scheduler import (
    DONE, FAILED, TIMEOUT, FleetScheduler, JobRecord,
)
from shadow_tpu.fleet.sweep import JobSpec, validate_jobs
from shadow_tpu.obs import audit as audit_mod
from shadow_tpu.obs import counters as obs_mod
from shadow_tpu.obs import metrics as metrics_mod
from shadow_tpu.parallel import islands as islands_mod

NEVER = simtime.NEVER

# Per-attempt sub-step ceiling for optimistic fleet rounds (mirrors
# parallel/islands._MAX_SUBSTEPS: generous, but a pool-headroom stall
# surfaces as a driver error in seconds rather than hanging).
_MAX_SUBSTEPS = 4096


class FleetError(ValueError):
    pass


@dataclasses.dataclass
class _LaneFaults:
    """Job-scoped fault plane: resolved kill_host / skew_hosts injections
    + the lane's dead-host set (drained recurringly, the crashed-host
    semantic)."""

    # [(at_ns, op, payload)] sorted, unfired: payload is the host id for
    # kill_host, ([host_ids], factor) for skew_hosts
    pending: list
    dead: set
    stats: dict

    @classmethod
    def empty(cls) -> "_LaneFaults":
        return cls(pending=[], dead=set(), stats={})


def _build_solo(spec: JobSpec):
    """Build one job's solo Simulation (host-side: topology bake + initial
    events; no kernel is ever dispatched on it)."""
    from shadow_tpu.sim import BuildError, build_simulation

    sim = build_simulation(load_config(spec.config))
    router = sim.state.subs.get("router")
    if router is not None and router.deep_rows is not None:
        raise BuildError(
            f"job {spec.name!r}: the fleet layout does not carry the deep "
            f"router ring (hosts.<group>.router_queue_slots)"
        )
    return sim


def _align_gear(sim, level: int) -> None:
    """Force a freshly-built solo sim onto the fleet's gear (pool shapes
    must match the compiled lanes). Pure resize — no kernel rebind, no
    telemetry bump (the solo kernels are never used)."""
    if sim._gear == level:
        return
    spec = sim._gear_ladder[level]
    pool, dropped = gearbox.resize_pool(sim.state.pool, spec.capacity)
    if int(np.sum(np.asarray(jax.device_get(dropped)))):
        raise FleetError(
            f"job pool resize to gear {level} dropped events (initial "
            f"occupancy exceeds the fleet gear's capacity)"
        )
    sim.state = sim.state.replace(pool=pool)
    sim._gear = level


class FleetSimulation:
    """Batched multi-experiment runner over one compiled window kernel.

    Build via `build_fleet(jobs, lanes=...)`. Drive with `run()`
    (conservative windows) or `run_optimistic()` (per-lane speculative
    windows); read `results()` / `fleet_stats()` afterwards.
    """

    def __init__(
        self,
        jobs: list[JobSpec],
        lanes: int | None = None,
        windows_per_dispatch: int = 32,
        keep_final_subs: bool = False,
        checkpoint_dir: str | None = None,
        checkpoint_every_ns: int = 0,
    ):
        if not jobs:
            raise FleetError("fleet needs at least one job")
        validate_jobs(jobs)
        L = min(len(jobs), lanes) if lanes else len(jobs)
        self.sched = FleetScheduler(jobs, L)
        self.lanes = L
        self.windows_per_dispatch = int(windows_per_dispatch)
        self.keep_final_subs = bool(keep_final_subs)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every_ns = int(checkpoint_every_ns)
        self._ckpt_next_t = self.checkpoint_every_ns or int(NEVER)
        self.kernel_traces = 0
        self.gear_shifts = 0
        # Backend supervision (core/supervisor.py): dispatches route
        # through _sv(); a drain pauses admission until recovery. Backend
        # fault injections (kill_backend/stall_backend) are FLEET-scoped —
        # the accelerator serves every lane — and fire against the fleet
        # frontier, unlike the per-job kill_host plans.
        self.supervisor = None
        self._cpu_failover = False
        self._admission_paused = False
        self._backend_faults: list = []
        # Resource-pressure plane (core/pressure.py): lazily attached on
        # the first pressure signal. Lane eviction holds admission for a
        # few handoffs so the freed lane actually lowers the resident
        # set; reshaping rungs are forbidden mid-optimistic-attempt.
        self.pressure = None
        self._pressure_reshape_ok = True
        self._evict_hold = 0
        # AOT kernel cache (serve/kcache.py): when attached, fleet window
        # kernels bind from serialized exports on disk — a warm restart
        # re-binds every known shape with ZERO Python traces
        # (kernel_traces stays 0, the serve-smoke gated property).
        self.kernel_cache = None
        self._kc_digest = None
        # Telemetry session (obs/metrics.ObsSession): attached by the
        # sweep CLI (--metrics-out/--trace-out) via attach_obs. Fleet
        # traces give each lane its own tid (lane index + 1; tid 0 is the
        # driver row), named with "M" metadata events.
        self.obs_session = None

        # --- build the first wave of solo sims; the first is the template
        # whose kernel config (handlers, shapes, ladder) the fleet adopts
        sims = [_build_solo(r.spec) for r in self.sched.records[:L]]
        t = sims[0]
        self.template = t
        self._islands = isinstance(t, islands_mod.IslandSimulation)
        # Pipelined CPU↔TPU handoff (core/pipeline.py): the fleet adopts
        # the template job's experimental.pipelined_dispatch knob — one
        # sweep, one dispatch discipline. Stats lazily created so serial
        # sweeps emit no pipeline.* keys; handoff hooks run in the
        # host-drain phase of every fleet dispatch boundary.
        self.pipelined_dispatch = bool(
            getattr(t, "pipelined_dispatch", True)
        )
        self._pipeline_stats: dict | None = None
        self._handoff_hooks: list = []
        # Multi-worker host plane (core/hostplane.py): the fleet adopts
        # the template job's experimental.host_workers knob; sharded
        # handoff hooks partition PER LANE (the lane is the fleet's
        # owning-host unit) across pinned drain workers and merge in
        # canonical (frontier, lane) order. 1 = serial inline hooks and
        # no hostplane.* keys.
        self.host_workers = max(1, int(getattr(t, "host_workers", 1)))
        self._hostplane_obj = None
        self._hostplane_stats: dict | None = None
        if self._islands and t.mode != "vmap":
            raise FleetError(
                "fleet islands jobs run in island_mode: vmap (virtual "
                "shards batch under the job axis); shard_map composition "
                "is not supported yet"
            )
        self._ladder = t._gear_ladder
        self._shifter = (
            gearbox.GearShifter(self._ladder) if len(self._ladder) > 1
            else None
        )
        for s in sims[1:]:
            self._check_compat(s)

        # --- fleet gear: smallest level admitting every first-wave job
        g = t._gear
        for s in sims:
            g = max(g, FleetScheduler.admission_gear(
                self._ladder, self._occupancy_of(s), g
            ))
        self._gear = g
        for s in sims:
            _align_gear(s, g)

        # --- stack along the new leading job axis ---
        try:
            self.state = state_mod.stack_pytrees([s.state for s in sims])
            self.params = state_mod.stack_pytrees([s.params for s in sims])
        except ValueError as e:
            raise FleetError(str(e)) from e
        self._runahead = np.array([s.runahead for s in sims], np.int64)
        self._stop = np.array([s.stop_time for s in sims], np.int64)
        # Asynchronous conservative sync (parallel/islands.py): islands
        # jobs built with async_islands carry per-shard window widths and
        # an in-edge lookahead matrix; the fleet stacks them per lane —
        # traced inputs, so a lane swap never recompiles — and the sweep
        # gets BOTH axes of asynchrony: per-lane (runahead, stop) bounds
        # outside, per-shard frontiers inside.
        self._async = bool(self._islands and getattr(t, "_async", False))
        if self._async:
            # neighbor-only frontier exchange (parallel/lookahead.py):
            # the compiled ppermute schedule must cover every lane's
            # in-edges, so the fleet compiles the UNION of the initial
            # jobs' shift sets (per-edge lookahead VALUES stay per-lane
            # traced rows); _check_compat refuses a later swap-in whose
            # topology needs an uncovered shift — structural drift would
            # otherwise force the recompile the factory seam exists to
            # avoid. None = the template runs the all_gather arm.
            self._async_shifts = None
            if getattr(t, "_exchange", "all_gather") == "ppermute":
                self._async_shifts = tuple(sorted({
                    int(d) for s in sims
                    for d in getattr(s, "_async_shifts", ())
                }))
            self._async_runahead = np.stack([
                np.asarray(jax.device_get(s._async_runahead)) for s in sims
            ])
            self._async_look = np.stack([
                np.asarray(jax.device_get(s._async_look_in)) for s in sims
            ])
            self._async_spread = np.array(
                [int(s._async_spread) for s in sims], np.int64
            )
            self._async_counters = {
                "dispatches": 0, "supersteps": 0, "shard_windows": 0,
                "yields": 0, "blocked_on_neighbor": 0,
            }
            self._async_spread_max = 0
            self._async_frontier = None
            # cumulative per-(lane, shard) [3, L, S] steps/yields/blocked
            # — the fleet's critical-path signal (obs/prof.py)
            self._async_shard_stats = None
            self._look_in_cache = None
        self._lane_faults = [
            self._resolve_faults(s) for s in sims
        ]
        for j, rec in enumerate(self.sched.records[:L]):
            self.sched.admit(j, rec)

        self._gear_fns: dict[int, dict] = {}
        self._bind_gear()

    # ------------------------------------------------------------------
    # compatibility + admission plumbing
    # ------------------------------------------------------------------

    def _check_compat(self, sim) -> None:
        t = self.template
        if type(sim) is not type(t):
            raise FleetError(
                "fleet jobs mix engine layouts (islands vs global); the "
                "sweep must hold experimental.num_shards fixed"
            )
        if sim.num_hosts != t.num_hosts:
            raise FleetError(
                f"fleet jobs disagree on host count ({sim.num_hosts} vs "
                f"{t.num_hosts}); host topology compiles into the kernel"
            )
        if self._islands and (
            sim.num_shards != t.num_shards
            or getattr(sim, "exclude_chips", ())
            != getattr(t, "exclude_chips", ())
        ):
            raise FleetError(
                f"fleet jobs disagree on the mesh partition "
                f"(num_shards {getattr(sim, 'num_shards', 1)} vs "
                f"{getattr(t, 'num_shards', 1)}, exclude_chips "
                f"{getattr(sim, 'exclude_chips', ())} vs "
                f"{getattr(t, 'exclude_chips', ())}); after an elastic "
                f"relayout every swap-in must be rebuilt for the "
                f"surviving mesh (fleet/checkpoint.resume_fleet "
                f"num_shards=)"
            )
        if self._islands and bool(getattr(sim, "_async", False)) != bool(
            getattr(t, "_async", False)
        ):
            raise FleetError(
                "fleet jobs mix sync modes (async_islands vs barrier); "
                "the sweep must hold experimental.async_islands fixed"
            )
        if self._islands and getattr(self, "_async", False):
            if getattr(sim, "_exchange", None) != getattr(
                t, "_exchange", None
            ):
                raise FleetError(
                    "fleet jobs mix frontier-exchange modes (ppermute vs "
                    "all_gather); the sweep must hold "
                    "experimental.mesh_exchange fixed"
                )
            need = set(getattr(sim, "_async_shifts", ()) or ())
            have = self._async_shifts
            if have is not None and not need <= set(have):
                raise FleetError(
                    f"job topology needs ppermute shifts "
                    f"{sorted(need - set(have))} the fleet kernel did not "
                    f"compile (compiled {list(have)}); the sweep must "
                    f"hold shard-level connectivity fixed, or run with "
                    f"experimental.mesh_exchange: all_gather"
                )
        lt = [(s.capacity, s.K) for s in t._gear_ladder]
        ls = [(s.capacity, s.K) for s in sim._gear_ladder]
        if lt != ls:
            raise FleetError(
                f"fleet jobs disagree on the pool gear ladder ({ls} vs "
                f"{lt}); event_capacity / K / pool_gears compile into the "
                f"kernel"
            )

    def _occupancy_of(self, sim) -> int:
        """Live resident rows of a solo sim (max shard under islands) —
        the admission-control signal."""
        occ = jnp.sum(sim.state.pool.time != NEVER, axis=-1)
        return int(np.max(np.asarray(jax.device_get(occ))))

    def _resolve_faults(self, sim) -> _LaneFaults:
        """Resolve the job's fault plan (kill_host / skew_hosts; validated
        by fleet/sweep.py) into (at_ns, op, payload) records against ITS
        config's host names — job-scoped: the injections only ever touch
        this lane."""
        lf = _LaneFaults.empty()
        cfg = getattr(sim, "config", None)
        faults = cfg.faults.load_faults() if cfg is not None else []
        for f in faults:
            if f.op == "kill_host":
                lf.pending.append(
                    (int(f.at_ns), "kill_host", sim._resolve_host_id(f.host))
                )
            elif f.op == "skew_hosts":
                ids = [
                    sim._resolve_host_id(h)
                    for h in sim._skew_fault_ids(f)
                ]
                lf.pending.append(
                    (int(f.at_ns), "skew_hosts", (ids, int(f.factor)))
                )
            else:  # validated earlier; belt-and-braces
                raise FleetError(
                    f"fleet fault plans support kill_host/skew_hosts "
                    f"only, got {f.op!r}"
                )
        lf.pending.sort(key=lambda r: r[0])
        return lf

    # ------------------------------------------------------------------
    # kernel binding (one compiled program per active gear)
    # ------------------------------------------------------------------

    def _lane_step(self, spec: gearbox.GearSpec, optimistic: bool = False):
        """The raw per-job window step in the template's layout."""
        t = self.template
        if self._islands:
            isl = t._island_spec
            if optimistic:
                isl = isl._replace(optimistic=True)
            return t._step_builder(isl, spec.K)
        return engine_mod.make_window_step(
            t.handlers, t.num_hosts, K=spec.K, B=t.B, O=t.O,
            bulk_kinds=t._bulk_kinds,
            matrix_handlers=t._matrix_handlers,
            with_cpu_model=t._with_cpu,
            bulk_gate=t._bulk_gate,
            bulk_self_excluded=t._bulk_self_excluded,
            payload_words=t._payload_words,
            audit=t._audit_digest,
            # under vmap a lax.cond with a batched predicate executes BOTH
            # branches, so matrix-capable sims pin the matrix path — the
            # same rule sim.py applies to vmap islands
            _force_path="matrix" if t._matrix_handlers else None,
        )

    def _counted(self, fn):
        """jit with a trace counter: tracing happens exactly once per
        compiled program, so the count IS the window-kernel compile
        metric the fleet-smoke gate asserts on."""
        def counted(*args):
            self.kernel_traces += 1
            return fn(*args)

        return self._jit(counted)

    def attach_kernel_cache(self, kcache) -> None:
        """Bind an AOT kernel cache (serve/kcache.py) BEFORE the first
        dispatch: subsequent kernel binds consult the cache and only
        trace on a miss (exporting + persisting the artifact so the next
        process hits). The cache key folds in the template job's kernel-
        shaping config digest, so kernel-compatible sweeps share entries
        while any shape/handler change misses safely."""
        from shadow_tpu.serve.kcache import kernel_config_digest

        self.kernel_cache = kcache
        self._kc_digest = kernel_config_digest(
            self.sched.records[0].spec.config
        )
        # re-bind the active gear through the cache (build bound the jit
        # path before the cache existed; nothing has been traced yet when
        # this is called pre-dispatch, so the swap is free)
        self._gear_fns = {}
        self._bind_gear()

    def _kernel(self, tag: str, fn):
        """Cache-aware kernel bind: with no cache (or during CPU
        failover, whose re-lowered kernels are transient) this is plain
        counted jit; with one, the first call looks the export up by
        (config digest, tag, arg avals) and only traces on a miss.

        Export serialization cannot carry the repo's custom pytree nodes
        (SimState/EventPool/...), so the exported artifact is the LEAF-
        FLATTENED kernel: flat arrays in, flat arrays out. No treedef
        needs to survive on disk because every fleet kernel returns
        (state', *scalar_extras) where state' has exactly the INPUT
        state's structure — the call-time wrapper re-folds the leading
        leaves with the live treedef and passes the extras through."""
        kc = self.kernel_cache
        if kc is None or self._cpu_failover:
            return self._counted(fn)
        holder: dict = {}

        def call(*args):
            flat, in_tree = jax.tree_util.tree_flatten(args)
            bound = holder.get("fn")
            if bound is None:
                state_def = jax.tree_util.tree_structure(args[0])
                key = kc.key(self._kc_digest, tag, flat)
                ex = kc.get(key)
                if ex is None:

                    def flat_fn(*leaves):
                        out = fn(*jax.tree_util.tree_unflatten(
                            in_tree, leaves
                        ))
                        return tuple(jax.tree_util.tree_leaves(out))

                    self.kernel_traces += 1
                    ex = kc.export_and_put(key, flat_fn, flat)
                jf = jax.jit(ex.call)
                n = state_def.num_leaves

                def bound(leaves, _jf=jf, _n=n, _sd=state_def):
                    out = _jf(*leaves)
                    st = jax.tree_util.tree_unflatten(_sd, out[:_n])
                    return (st, *out[_n:])

                holder["fn"] = bound
            return holder["fn"](flat)

        return call

    def _jit(self, fn):
        """jit honoring supervisor CPU failover: while the accelerator is
        gone, fleet kernels re-lower on the CPU backend and the sweep
        keeps advancing (core/supervisor.py)."""
        jf = jax.jit(fn)
        if not self._cpu_failover:
            return jf
        try:
            dev = jax.devices("cpu")[0]
        except RuntimeError:
            return jf

        def on_cpu(*args):
            with jax.default_device(dev):
                return jf(*args)

        return on_cpu

    def _build_gear_fns(self, spec: gearbox.GearSpec) -> dict:
        step = self._lane_step(spec)
        if self._islands and self._async:
            # async conservative loop: vmap-of-jobs outside, shards
            # inside; per-lane [S] runahead / [S, S] lookahead / spread
            # stack one more leading axis
            lane = islands_mod.make_shard_run_to_async(
                step, spec.hi, shifts=self._async_shifts,
                num_shards=self.template.num_shards,
            )
            inner = jax.vmap(
                lane, in_axes=(0, None, 0, 0, None, None, None),
                axis_name=islands_mod.AXIS,
            )
            run_to = jax.vmap(inner, in_axes=(0, 0, 0, 0, 0, 0, None))
        elif self._islands:
            lane = islands_mod.make_shard_run_to(step, spec.hi)
            inner = jax.vmap(
                lane, in_axes=(0, None, None, None, None),
                axis_name=islands_mod.AXIS,
            )
            run_to = jax.vmap(inner, in_axes=(0, 0, 0, 0, None))
        else:
            inner = engine_mod.make_run_to(step, spec.hi)
            run_to = jax.vmap(inner, in_axes=(0, 0, 0, 0, None))
        return {
            "run_to": self._kernel(f"run_to:g{spec.level}", run_to),
            "attempt": None,  # compiled lazily by run_optimistic
        }

    def _bind_gear(self) -> None:
        spec = self._ladder[self._gear]
        fns = self._gear_fns.get(spec.level)
        if fns is None:
            fns = self._gear_fns[spec.level] = self._build_gear_fns(spec)
        self._run_to = fns["run_to"]
        self._attempt = fns["attempt"]

    def _ensure_attempt(self) -> None:
        """Lazily build the optimistic kernel for the bound gear:
        conservative fleets never pay for the done_t machinery."""
        if self._attempt is not None:
            return
        spec = self._ladder[self._gear]
        if self._islands:
            sub = islands_mod.make_shard_substep(
                self._lane_step(spec, optimistic=True)
            )
            inner = jax.vmap(
                sub, in_axes=(0, None, None, None),
                axis_name=islands_mod.AXIS,
            )
        else:
            inner = engine_mod.make_attempt(
                self._lane_step(spec)
            )
        att = jax.vmap(inner, in_axes=(0, 0, 0, 0))
        self._attempt = self._gear_fns[spec.level]["attempt"] = \
            self._kernel(f"attempt:g{spec.level}", att)

    def _shift_gear(self, level: int) -> None:
        """Move EVERY lane's pool to `level`'s capacity (one batched
        truncating/padding re-sort) and rebind the fleet kernels. Handoff
        boundary only, exactly like the solo drivers."""
        spec = self._ladder[level]
        pool, dropped = gearbox.resize_pool(self.state.pool, spec.capacity)
        n = int(np.sum(np.asarray(jax.device_get(dropped))))
        if n:
            raise FleetError(
                f"fleet gear shift to level {level} dropped {n} events "
                f"(decision-rule bug: occupancy exceeded the target gear)"
            )
        self.state = self.state.replace(pool=pool)
        self._gear = level
        self.gear_shifts += 1
        if self._shifter is not None:
            self._shifter.reset()
        self._bind_gear()

    # ------------------------------------------------------------------
    # backend supervision (core/supervisor.py): drain pauses admission,
    # in-flight lanes requeue for the resumed sweep, recovery resumes it
    # ------------------------------------------------------------------

    def attach_supervisor(self, supervisor) -> None:
        supervisor.bind(self)
        self.supervisor = supervisor

    def _sv(self, label: str, thunk):
        if self.supervisor is None:
            return thunk()
        return self.supervisor.call(label, thunk)

    def _sv_issue(self, label: str, issue_fn, fetch_fn):
        """ISSUE half of a split fleet dispatch (core/supervisor.py
        PendingDispatch): enqueue async, never block."""
        if self.supervisor is None:
            return supervisor_mod.PendingDispatch.direct(
                label, issue_fn, fetch_fn
            )
        return self.supervisor.issue(label, issue_fn, fetch_fn)

    def _sv_await(self, pending):
        """AWAIT half: blocking fetches under the classified retry
        ladder / watchdog / loss policies when supervised."""
        if self.supervisor is None:
            return pending.await_direct()
        return self.supervisor.await_result(pending)

    def _sv_disrupted(self) -> bool:
        sup = self.supervisor
        return sup is not None and sup.pending_disruption

    # -- pipelined CPU↔TPU handoff (core/pipeline.py) --

    def _pipeline(self):
        if not self.pipelined_dispatch:
            return None
        if self._pipeline_stats is None:
            self._pipeline_stats = pipeline_mod.new_stats()
        return pipeline_mod.TwoSlotPipeline(self._pipeline_stats)

    def pipeline_stats(self) -> dict:
        """`pipeline.*` telemetry (schema v14); {} until a pipelined
        fleet loop ran (serial sweeps emit no pipeline keys)."""
        st = self._pipeline_stats
        return dict(st) if st is not None else {}

    def add_handoff_hook(self, fn, sharded: bool = False) -> None:
        """Register per-boundary host work, called in the host-drain
        phase of every fleet dispatch boundary (after scheduler work).
        sharded=False: fn(fleet, frontier_ns), one whole-fleet call on
        the coordinator. sharded=True: fn(fleet, frontier_ns, lane), one
        call per lane, partitioned by lane across the multi-worker host
        plane (core/hostplane.py) — partition-local state only. With
        host_workers == 1 sharded hooks run inline in the same canonical
        (frontier, lane) order the parallel merge uses."""
        self._handoff_hooks.append((fn, bool(sharded)))

    # -- multi-worker host plane (core/hostplane.py) --

    def _hostplane(self):
        if self.host_workers <= 1:
            return None
        if self._hostplane_obj is None:
            if self._hostplane_stats is None:
                self._hostplane_stats = hostplane_mod.new_stats(
                    self.host_workers
                )
            self._hostplane_obj = hostplane_mod.HostPlane(
                self.host_workers, self._hostplane_stats
            )
        return self._hostplane_obj

    def hostplane_stats(self) -> dict:
        """`hostplane.*` telemetry (schema v15); {} until a multi-worker
        fleet drain ran (host_workers == 1 emits no hostplane keys)."""
        st = self._hostplane_stats
        return dict(st) if st is not None else {}

    def _run_handoff_hooks(self, mn) -> None:
        if not self._handoff_hooks:
            return
        frontier = int(np.min(mn)) if np.ndim(mn) else int(mn)
        sharded = [fn for fn, sh in self._handoff_hooks if sh]
        if sharded:
            hp = self._hostplane()
            if hp is None:
                for lane in range(self.lanes):
                    for fn in sharded:
                        fn(self, frontier, lane)
            else:
                obs = self.obs_session
                hp.drain(
                    [
                        hostplane_mod.HostAction(
                            frontier, lane,
                            (lambda f=fn, j=lane: f(self, frontier, j)),
                        )
                        for lane in range(self.lanes)
                        for fn in sharded
                    ],
                    tracer=obs.tracer if obs is not None else None,
                )
        for fn, sh in self._handoff_hooks:
            if not sh:
                fn(self, frontier)

    def _handoff_quiet(self, mn: np.ndarray) -> bool:
        """True when the upcoming fleet handoff cannot take a scheduler
        or state action at frontier vector `mn`: no lane finished, no
        due lane/backend injection, no wall deadline armed on a running
        job, no checkpoint mark due, no pressure hold. Speculation only
        crosses QUIET boundaries; everything else is a barrier point."""
        if self._evict_hold > 0 or self._admission_paused:
            return False
        frontier = int(NEVER)
        for j in range(self.lanes):
            rec = self.sched.lane_job[j]
            if rec is None:
                # an empty lane means queued work could admit
                if self.sched.pending():
                    return False
                continue
            if mn[j] >= self._stop[j]:
                return False  # harvest due
            if rec.spec.deadline_s:
                return False  # wall-clock deadline: unpredictable
            lf = self._lane_faults[j]
            if lf.pending and lf.pending[0][0] <= mn[j]:
                return False
            if lf.dead:
                return False  # recurring quarantine drain
            frontier = min(frontier, int(mn[j]))
        if self._backend_fault_mark() <= frontier:
            return False
        if (self.checkpoint_dir and self.checkpoint_every_ns
                and frontier >= self._ckpt_next_t):
            return False
        pc = self.pressure
        if (pc is not None and pc.saturate_frac is not None
                and pc.saturate_frac < 1.0):
            return False
        return True

    def attach_faults(self, faults) -> None:
        """Arm FLEET-scoped injections: backend ops (kill_backend /
        stall_backend / exhaust_backend) plus saturate_pool — the
        accelerator and the pressure plane serve every lane, so these
        fire at the handoff whose fleet frontier (min over active lanes)
        reaches `at`. Per-job plans carry kill_host only (validated by
        fleet/sweep.py)."""
        from shadow_tpu.faults import plan as plan_mod

        allowed = plan_mod.BACKEND_OPS | {"saturate_pool"}
        for f in faults:
            if f.op not in allowed:
                raise FleetError(
                    f"fleet-level fault plans support backend + pressure "
                    f"ops only ({sorted(allowed)}); {f.op!r} belongs "
                    f"in a per-job plan"
                )
        self._backend_faults = sorted(faults, key=lambda f: (f.at_ns, f.seq))
        if self.supervisor is None and any(
            f.op in plan_mod.BACKEND_OPS for f in self._backend_faults
        ):
            from shadow_tpu.core.supervisor import BackendSupervisor

            self.attach_supervisor(BackendSupervisor())

    def _backend_fault_mark(self) -> int:
        """Earliest unfired backend injection: dispatches clamp their
        stop here so the loss lands at a deterministic frontier."""
        for f in self._backend_faults:
            if not f.fired:
                return f.at_ns
        return int(NEVER)

    def _backend_fault_tick(self, mn: np.ndarray) -> None:
        active = [
            mn[j] for j in range(self.lanes)
            if self.sched.lane_job[j] is not None
        ]
        if not active:
            return
        frontier = int(min(active))
        for f in self._backend_faults:
            if f.fired or f.at_ns > frontier:
                continue
            f.fired = True
            sup = self.supervisor
            if f.op == "kill_backend":
                sup.inject_kill(f.recover_after)
            elif f.op == "kill_chip":
                sup.inject_kill_chip(f.chip, f.recover_after)
            elif f.op == "exhaust_backend":
                sup.inject_exhaust(f.recover_after)
            elif f.op == "saturate_pool":
                # fleet-scoped pool saturation: the controller records
                # the pressure; the fleet has no spill tier, so relief
                # is gear headroom / lane eviction via the ladder
                self._pressure().saturate(f.frac)
            else:  # stall_backend
                sup.inject_stall(f.count)
            obs = self.obs_session
            if obs is not None and obs.tracer is not None:
                obs.tracer.fault("fault_injection", op=f.op, at_ns=f.at_ns)

    def _rebind_kernels(self) -> None:
        """Fresh compiled kernels for the active gear (hot resume /
        failover re-lowering); re-ensures the optimistic attempt kernel
        when one was bound, and reopens admission — the drained sweep
        resumes."""
        had_attempt = self._attempt is not None
        self._gear_fns = {}
        self._bind_gear()
        if had_attempt and self._attempt is None:
            self._ensure_attempt()
        self._admission_paused = False

    def _enter_cpu_failover(self) -> None:
        if self._islands and self.template.mode == "shard_map":
            raise RuntimeError(
                "CPU failover is not available under shard_map islands; "
                "use --on-backend-loss wait or abort"
            )
        try:
            dev = jax.devices("cpu")[0]
        except RuntimeError as e:
            raise RuntimeError(f"no CPU backend to fail over to: {e}") from e
        self.state = jax.device_put(jax.device_get(self.state), dev)
        self.params = jax.device_put(jax.device_get(self.params), dev)
        self._cpu_failover = True
        self._rebind_kernels()

    def _exit_cpu_failover(self) -> None:
        self._cpu_failover = False
        self.state = jax.device_put(jax.device_get(self.state))
        self.params = jax.device_put(jax.device_get(self.params))
        self._rebind_kernels()

    def _drain_to_checkpoint(self, reason: str,
                             ckpt_dir: str | None = None) -> str | None:
        """Backend-loss drain: pause admission, flush every running
        lane's slice + the manifest (fleet/checkpoint.py) with the drain
        reason, and — under policies `abort` and `relayout` — requeue
        the in-flight jobs so the scheduler truth matches reality
        (nothing is running on a dead backend; the saved slices let
        `sweep --resume` restore their progress instead of re-running
        them). `relayout` requeues because a fleet cannot reshape its
        compiled lane × shard program in place: the ChipLost that
        follows hands the rebuild to the caller, and `resume_fleet
        (num_shards=...)` restores every lane through the relayout seam
        on the shrunk mesh — the lane-requeue-on-shrink contract."""
        self._admission_paused = True
        sup = self.supervisor
        policy = sup.policy if sup is not None else "abort"
        d = ckpt_dir or self.checkpoint_dir
        path = None
        if d:
            from shadow_tpu.fleet import checkpoint as fleet_ckpt

            path = fleet_ckpt.save_fleet(self, d, extra_meta={"drain": {
                "reason": reason, "policy": policy,
            }})
        obs = self.obs_session
        if obs is not None and obs.tracer is not None:
            obs.tracer.fault("drain_checkpoint", reason=reason)
        if policy in ("abort", "relayout"):
            for j in range(self.lanes):
                if self.sched.lane_job[j] is not None:
                    self.sched.requeue(j, reason="backend drain")
        return path

    def resilience_stats(self) -> dict:
        """The `resilience.*` metrics namespace (schema v6): supervisor
        counters plus the scheduler's reclaim/requeue totals."""
        sup = self.supervisor
        d = sup.stats() if sup is not None else {}
        d["lane_reclaims"] = self.sched.lane_reclaims
        d["jobs_requeued"] = self.sched.jobs_requeued
        return d

    # ------------------------------------------------------------------
    # resource-pressure plane (core/pressure.py): fleet-shaped rungs
    # ------------------------------------------------------------------

    def attach_pressure(self, controller) -> None:
        self.pressure = controller

    def _pressure(self):
        if self.pressure is None:
            self.pressure = pressure_mod.PressureController()
        return self.pressure

    def _pressure_ladder_step(self, label: str) -> bool:
        return self._pressure().on_backend_exhausted(self, label)

    def _pressure_stall(self, *, window=None, occupancy=None,
                        capacity=None) -> bool:
        return self._pressure().on_pool_exhausted(
            self, window=window, occupancy=occupancy, capacity=capacity
        )

    def _pool_exhausted(self, message: str, window=None, occupancy=None,
                        capacity=None):
        """Terminal pool exhaustion: drain the fleet (slices + manifest,
        jobs requeued so `sweep --resume` restores them at a reshaped
        config) and build the typed error — never a bare RuntimeError."""
        path = self._drain_to_checkpoint("pool_exhausted")
        if path:
            message += f" (drained to {path}; resume with sweep --resume)"
        return pressure_mod.PoolExhausted(
            message, window=window, occupancy=occupancy, capacity=capacity
        )

    def _lane_occupancies(self) -> np.ndarray:
        occ = jnp.sum(self.state.pool.time != NEVER, axis=-1)
        return np.asarray(jax.device_get(occ)).reshape(
            self.lanes, -1
        ).max(axis=1)

    def _pressure_relieve_pool(self, step: int):
        """Per-lane pools share ONE compiled shape, so more headroom is a
        fleet-wide upshift; at the top gear, shed the heaviest job rather
        than the fleet (the existing fail-THIS-job posture)."""
        pc = self._pressure()
        if (not pc.hold_gear and self._pressure_reshape_ok
                and self._gear < self._ladder[-1].level):
            self._shift_gear(self._gear + 1)
            return "upshift"
        if pc.policy.allow_lane_eviction:
            j = self._heaviest_lane()
            if j is not None:
                self._kill_lane(j)
                self._harvest(
                    j, FAILED,
                    "pool pressure: job shed by the degradation ladder "
                    "(raise experimental.event_capacity for this sweep)",
                )
                return "job_shed"
        return None

    def _pressure_relieve_memory(self, step: int):
        """Memory rungs, fleet-shaped: forced downshift when every lane's
        occupancy fits the smaller gear (the fleet has no spill tier to
        park overflow), else evict the heaviest lane — the freed lane
        shrinks the resident working set and admission holds."""
        pc = self._pressure()
        pol = pc.policy
        if not self._pressure_reshape_ok:
            # mid-optimistic-attempt: the rollback snapshot pins both the
            # compiled shapes AND the lane rows (an eviction's row clear
            # would be overwritten by the attempt's state) — no safe rung;
            # the supervisor's drain + recovery path takes over
            return None
        if pol.allow_downshift and self._gear > self._ladder[0].level:
            target = self._ladder[self._gear - 1]
            if int(self._lane_occupancies().max(initial=0)) <= target.fill:
                self._shift_gear(target.level)
                pc.hold_gear = True
                return "downshift"
        if pol.allow_lane_eviction and self._pressure_evict_lane():
            return "lane_eviction"
        return None

    def _heaviest_lane(self) -> int | None:
        occ = self._lane_occupancies()
        best, best_occ = None, -1
        for j in range(self.lanes):
            if self.sched.lane_job[j] is None:
                continue
            if int(occ[j]) > best_occ:
                best, best_occ = j, int(occ[j])
        return best

    def _pressure_evict_lane(self) -> bool:
        """Requeue the heaviest running job (FleetScheduler.requeue — it
        re-admits FIFO at its original position) and clear its lane; the
        eviction hold keeps the freed lane empty for a few handoffs so
        the resident set actually shrinks. The re-run is bit-identical
        (jobs are pure functions of their spec)."""
        j = self._heaviest_lane()
        if j is None:
            return False
        self.sched.requeue(j, reason="pressure eviction")
        self._kill_lane(j)
        self._lane_faults[j] = _LaneFaults.empty()
        self._evict_hold = max(
            self._evict_hold,
            self._pressure().policy.eviction_hold_dispatches,
        )
        return True

    def pressure_stats(self) -> dict:
        """The `pressure.*` metrics namespace (schema v8); {} until a
        pressure signal engaged."""
        pc = self.pressure
        return pc.stats() if pc is not None else {}

    def _reclaim_expired(self) -> bool:
        """Free lanes whose job blew its wall-clock deadline NOW — before
        the next dispatch would ride the dead job along — and hand each
        freed lane straight to the admission queue (`lane_reclaims`)."""
        changed = False
        for j in range(self.lanes):
            rec = self.sched.lane_job[j]
            if rec is None or not rec.deadline_exceeded():
                continue
            self._kill_lane(j)
            self._harvest(
                j, TIMEOUT,
                f"wall deadline {rec.spec.deadline_s}s exceeded",
            )
            self.sched.lane_reclaims += 1
            self._admit_next(j)
            changed = True
        return changed

    # ------------------------------------------------------------------
    # telemetry session + per-lane trace rows
    # ------------------------------------------------------------------

    def attach_obs(self, session) -> None:
        """Attach an ObsSession (metrics + optional Chrome tracer). Lanes
        already occupied at attach time get their thread rows named and
        an `admit` marker immediately, so a session attached right after
        build still renders every job's full residency."""
        self.obs_session = session
        tr = session.tracer if session is not None else None
        if tr is not None:
            tr.thread_name(0, "driver")
            for j, rec in enumerate(self.sched.lane_job):
                if rec is not None:
                    self._trace_admit(j, rec)

    def _trace_admit(self, lane: int, rec: JobRecord) -> None:
        obs = self.obs_session
        if obs is None or obs.tracer is None:
            return
        tid = lane + 1
        obs.tracer.thread_name(tid, f"lane {lane}")
        rec._trace_ts0 = obs.tracer._now_us()
        obs.tracer.instant("admit", tid=tid, job=rec.name, lane=lane)

    def _trace_harvest(self, lane: int, rec: JobRecord) -> None:
        obs = self.obs_session
        if obs is None or obs.tracer is None:
            return
        tid = lane + 1
        now = obs.tracer._now_us()
        t0 = getattr(rec, "_trace_ts0", None)
        if t0 is not None:
            # one complete event per job residency on the lane's row
            obs.tracer.complete(
                rec.name, t0, now - t0, cat="job", tid=tid,
                status=rec.status,
                events_committed=int(rec.events_committed),
            )
        obs.tracer.instant(
            "harvest", tid=tid, job=rec.name, status=rec.status
        )

    def counters(self) -> dict[str, int]:
        """Engine counters summed across every lane (fleet-wide progress;
        per-job counters are harvested per lane)."""
        c = jax.device_get(self.state.counters)
        return {
            f.name: int(np.sum(np.asarray(getattr(c, f.name))))
            for f in dataclasses.fields(c)
        }

    # ------------------------------------------------------------------
    # lane lifecycle
    # ------------------------------------------------------------------

    def _lane_min_times(self) -> np.ndarray:
        mn = jnp.min(self.state.pool.time, axis=-1)
        return np.asarray(jax.device_get(mn)).reshape(
            self.lanes, -1
        ).min(axis=1)

    def _bump_lane_win(self, lane: int, idx: int, n: int = 1) -> None:
        if self.state.obs is None or n == 0:
            return
        w = self.state.obs.win
        if w.ndim == 3:  # islands lanes: [L, S, NUM_WIN]; shard 0 carries
            w = w.at[lane, 0, idx].add(n)
        else:
            w = w.at[lane, idx].add(n)
        self.state = self.state.replace(
            obs=self.state.obs.replace(win=w)
        )

    def _harvest(self, lane: int, status: str = DONE,
                 reason: str = "") -> JobRecord:
        """Read one finished lane's results (counters, obs slice,
        frontier) at the handoff boundary and free the lane."""
        lane_state = state_mod.slice_lane(self.state, lane)
        rec = self.sched.release(lane, status, reason)
        c = jax.device_get(lane_state.counters)
        rec.counters = {
            f.name: int(np.sum(np.asarray(getattr(c, f.name))))
            for f in dataclasses.fields(c)
        }
        rec.events_committed = rec.counters["events_committed"]
        snap = obs_mod.snapshot(lane_state)
        if snap:
            rec.windows = snap["win"]["windows_run"]
            hl = snap["host_last_t"]
            rec.frontier_ns = int(hl.max()) if hl.size else -1
            rec.obs = {
                "win": snap["win"],
                "vtime": obs_mod.vtime_stats(hl),
            }
            if "host_digest" in snap:
                # the job's determinism-audit chain (obs/audit.py):
                # lane slices are solo-layout, so this equals the same
                # scenario's solo-run chain bit-for-bit (schema v5
                # fleet.jobs[*].audit)
                rec.audit = {
                    "chain": audit_mod.combine(snap["host_digest"]),
                }
        rec.faults = dict(self._lane_faults[lane].stats)
        if self.keep_final_subs:
            rec.subs = jax.device_get(lane_state.subs)
        self._lane_faults[lane] = _LaneFaults.empty()
        if status == DONE:
            # fold the observed event count into the packing estimator's
            # rate EWMA (fleet/scheduler.calibrate)
            self.sched.calibrate(rec)
        self._trace_harvest(lane, rec)
        return rec

    def _admit_next(self, lane: int) -> bool:
        """Swap the next queued job into a freed lane: build its solo
        state, clear the admission gate (upshifting the fleet gear if the
        job's initial rows demand it), and write the lane slice. The
        compiled kernel is untouched — compile once, reuse the lane."""
        if self._admission_paused:
            # backend drain in progress: no new work enters until the
            # supervisor's recovery reopens admission (_rebind_kernels)
            return False
        if self._evict_hold > 0:
            # pressure eviction in effect: the freed lane stays empty so
            # the resident working set actually shrinks (core/pressure.py)
            return False
        # predicted-load packing / lane stealing (self-balancing plane):
        # under "load" packing the freed lane takes the heaviest pending
        # job instead of the FIFO head (fleet/scheduler.pick)
        rec = self.sched.pick(lane)
        if rec is None:
            return False
        sim = _build_solo(rec.spec)
        self._check_compat(sim)
        want = FleetScheduler.admission_gear(
            self._ladder, self._occupancy_of(sim), self._gear
        )
        if want > self._gear:
            self.sched.admission_upshifts += 1
            self._shift_gear(want)
        _align_gear(sim, self._gear)
        try:
            def _swap():
                st = state_mod.set_lane(self.state, lane, sim.state)
                pr = state_mod.set_lane(self.params, lane, sim.params)
                return st, pr

            self.state, self.params = self._sv("lane_swap", _swap)
        except ValueError as e:
            raise FleetError(f"job {rec.name!r}: {e}") from e
        self._runahead[lane] = sim.runahead
        self._stop[lane] = sim.stop_time
        if self._async:
            self._async_runahead[lane] = np.asarray(
                jax.device_get(sim._async_runahead))
            self._async_look[lane] = np.asarray(
                jax.device_get(sim._async_look_in))
            self._async_spread[lane] = int(sim._async_spread)
            self._look_in_cache = None
        self._lane_faults[lane] = self._resolve_faults(sim)
        self.sched.admit(lane, rec)
        self.sched.lane_swaps += 1
        self._trace_admit(lane, rec)
        return True

    def _kill_lane(self, lane: int) -> None:
        """Drop every pending event of a lane (timeout / pressure kill):
        the lane's frontier jumps to NEVER and its fused-loop cond goes
        false — a dead lane is indistinguishable from a finished one."""
        t = self.state.pool.time
        self.state = self.state.replace(
            pool=self.state.pool.replace(
                time=t.at[lane].set(jnp.full_like(t[lane], NEVER))
            )
        )

    def _drain_lane_dead(self, lane: int) -> int:
        """Cancel pool rows destined to the lane's quarantined hosts —
        THIS lane only (the job-scoped crashed-host semantic). Recurring:
        late emissions and islands exchange-deferred rows are caught at
        every subsequent handoff."""
        lf = self._lane_faults[lane]
        if not lf.dead:
            return 0
        pool = self.state.pool
        tl, dl = pool.time[lane], pool.dst[lane]
        mask = jnp.isin(dl, jnp.asarray(sorted(lf.dead), dl.dtype)) \
            & (tl != NEVER)
        n = int(jnp.sum(mask))
        if n:
            self.state = self.state.replace(pool=pool.replace(
                time=pool.time.at[lane].set(jnp.where(mask, NEVER, tl))
            ))
            lf.stats["events_drained"] = lf.stats.get("events_drained", 0) + n
            self._bump_lane_win(lane, obs_mod.WIN_FAULTS)
        return n

    def _fault_marks(self) -> np.ndarray:
        """Per-lane earliest unfired injection time (NEVER if none): the
        conservative driver clamps each lane's dispatch stop here, so an
        injection executes at a handoff whose committed frontier sits
        exactly at its mark — the solo drivers' _fault_mark clamp,
        lane-scoped. Without the clamp a fused multi-window dispatch
        would sail past the mark and the injection timing would degrade
        to dispatch granularity."""
        marks = np.full(self.lanes, int(NEVER), np.int64)
        for j in range(self.lanes):
            lf = self._lane_faults[j]
            if lf.pending and self.sched.lane_job[j] is not None:
                marks[j] = lf.pending[0][0]
        return marks

    def _fault_tick(self, mn: np.ndarray) -> bool:
        """Fire due job-scoped injections + recurring drains at the
        handoff boundary. Returns True if any lane's pool changed."""
        changed = False
        for j in range(self.lanes):
            if self.sched.lane_job[j] is None:
                continue
            lf = self._lane_faults[j]
            while lf.pending and lf.pending[0][0] <= mn[j]:
                _, op, payload = lf.pending.pop(0)
                lf.stats["injections_fired"] = \
                    lf.stats.get("injections_fired", 0) + 1
                obs = self.obs_session
                if op == "skew_hosts":
                    ids, factor = payload
                    n = self._skew_lane(j, ids, factor)
                    lf.stats["events_skewed"] = \
                        lf.stats.get("events_skewed", 0) + n
                    changed = True
                    if obs is not None and obs.tracer is not None:
                        obs.tracer.fault(
                            "skew_hosts", tid=j + 1, lane=j,
                            hosts=len(ids), factor=factor, injected=n,
                        )
                elif payload not in lf.dead:
                    lf.dead.add(payload)
                    lf.stats["hosts_quarantined"] = \
                        lf.stats.get("hosts_quarantined", 0) + 1
                    if obs is not None and obs.tracer is not None:
                        obs.tracer.fault(
                            "kill_host", tid=j + 1, host=payload, lane=j
                        )
            if lf.dead and self._drain_lane_dead(j):
                changed = True
        return changed

    def _skew_lane(self, lane: int, ids: list[int], factor: int) -> int:
        """Apply one skew_hosts injection to a single lane's pool slice
        (faults/injector.skew_pool_np — the solo engines' replication,
        lane-scoped). The per-lane dispatch clamp (_fault_marks) pinned
        this lane's frontiers at or below the injection time, so copies
        (which inherit pending-event times) are frontier-safe. The fleet
        has no spill tier: copies that do not fit the lane's pool are
        counted dropped (`skew_overflow_dropped`) — deterministic, so
        chain-parity arms see identical drops."""
        from shadow_tpu.faults import injector as inj_mod

        lf = self._lane_faults[lane]
        pool = self.state.pool
        cols = [
            np.array(jax.device_get(c[lane])) for c in (
                pool.time, pool.dst, pool.src, pool.seq, pool.kind,
                pool.payload,
            )
        ]
        flat = cols[0].ndim == 1  # global lanes [C] vs islands [S, C]
        if flat:
            cols = [c[None] for c in cols]
        out, made, overflow = inj_mod.skew_pool_np(
            cols, ids, factor, dead=lf.dead
        )
        t, d, s, q, k, p = (
            (c[0] for c in out) if flat else out
        )
        self.state = self.state.replace(pool=pool.replace(
            time=pool.time.at[lane].set(jnp.asarray(t)),
            dst=pool.dst.at[lane].set(jnp.asarray(d)),
            src=pool.src.at[lane].set(jnp.asarray(s)),
            seq=pool.seq.at[lane].set(jnp.asarray(q)),
            kind=pool.kind.at[lane].set(jnp.asarray(k)),
            payload=pool.payload.at[lane].set(jnp.asarray(p)),
        ))
        dropped = sum(
            rows[0].shape[0] for _, rows in sorted(overflow.items())
        )
        if dropped:
            lf.stats["skew_overflow_dropped"] = \
                lf.stats.get("skew_overflow_dropped", 0) + dropped
        self._bump_lane_win(lane, obs_mod.WIN_FAULTS)
        return made

    def _handoff(self, mn: np.ndarray, press: np.ndarray) -> bool:
        """Everything the host does between dispatches: job-scoped fault
        injections, harvest of finished lanes, lane swaps, wall-clock
        deadlines, pressure kills, checkpoint marks. Returns True when
        any scheduler-visible action happened (the stall guard's
        signal)."""
        if self._evict_hold > 0:
            self._evict_hold -= 1
        changed = self._fault_tick(mn)
        if changed:
            mn[:] = self._lane_min_times()  # a drain may move frontiers
        for j in range(self.lanes):
            rec = self.sched.lane_job[j]
            if rec is None:
                continue
            if mn[j] >= self._stop[j]:
                self._harvest(j, DONE)
                changed = True
            elif rec.deadline_exceeded():
                self._kill_lane(j)
                self._harvest(
                    j, TIMEOUT,
                    f"wall deadline {rec.spec.deadline_s}s exceeded",
                )
                # the lane goes straight to the admission queue below —
                # never parked until another harvest pass
                self.sched.lane_reclaims += 1
                changed = True
            elif press[j] and self._gear >= self._ladder[-1].level:
                # red zone at the top gear with no spill tier: the lane
                # cannot place one window's inflow — fail THIS job, not
                # the fleet
                self._kill_lane(j)
                self._harvest(
                    j, FAILED,
                    "pool pressure at top gear (raise "
                    "experimental.event_capacity for this sweep)",
                )
                changed = True
            if self.sched.lane_job[j] is None and self._admit_next(j):
                changed = True
        if changed:
            mn[:] = self._lane_min_times()
        self._checkpoint_tick(mn)
        return changed

    def _checkpoint_tick(self, mn: np.ndarray) -> None:
        if not (self.checkpoint_dir and self.checkpoint_every_ns):
            return
        active = [
            mn[j] for j in range(self.lanes)
            if self.sched.lane_job[j] is not None
        ]
        if not active:
            return
        frontier = int(min(min(active), max(self._stop)))
        if frontier >= self._ckpt_next_t:
            from shadow_tpu.fleet import checkpoint as fleet_ckpt

            fleet_ckpt.save_fleet(self, self.checkpoint_dir)
            self._ckpt_next_t = (
                frontier // self.checkpoint_every_ns + 1
            ) * self.checkpoint_every_ns

    # ------------------------------------------------------------------
    # drivers
    # ------------------------------------------------------------------

    def _run_to_halves(self, eff_stop: np.ndarray, wpd: int):
        """(issue_fn, fetch_fn) halves of one fused fleet dispatch.
        issue enqueues the vmapped per-lane window loops (async —
        futures); fetch performs every blocking host read. Supervised
        retries re-run both halves against the bound kernels."""

        def issue(eff_stop=eff_stop, wpd=wpd):
            if self._async:
                return self._run_to(
                    self.state, self.params,
                    jnp.asarray(self._async_runahead),
                    jnp.asarray(self._async_look),
                    jnp.asarray(self._async_spread),
                    jnp.asarray(eff_stop), wpd,
                )
            return self._run_to(
                self.state, self.params,
                jnp.asarray(self._runahead),
                jnp.asarray(eff_stop), wpd,
            )

        def fetch(out):
            extra = None
            if self._async:
                # frontier [L, S] + fleet-summed async counters + the
                # per-(lane, shard) deltas the profiling plane keeps
                stp_v = np.asarray(jax.device_get(out[7])).reshape(
                    self.lanes, -1)
                yld_v = np.asarray(jax.device_get(out[8])).reshape(
                    self.lanes, -1)
                blk_v = np.asarray(jax.device_get(out[9])).reshape(
                    self.lanes, -1)
                extra = (
                    np.asarray(jax.device_get(out[5])).reshape(
                        self.lanes, -1),
                    int(np.max(np.asarray(jax.device_get(out[6])))),
                    int(stp_v.sum()),
                    int(yld_v.sum()),
                    int(blk_v.sum()),
                    int(np.max(np.asarray(jax.device_get(out[4])))),
                    np.stack([stp_v, yld_v, blk_v]).astype(np.int64),
                )
            return (
                out[0],
                np.asarray(jax.device_get(out[1])).reshape(
                    self.lanes, -1).min(axis=1),
                np.asarray(jax.device_get(out[2])).reshape(
                    self.lanes, -1).any(axis=1),
                int(np.max(np.asarray(jax.device_get(out[3])))),
                extra,
            )

        return issue, fetch

    def run(self, windows_per_dispatch: int | None = None,
            max_dispatches: int | None = None) -> int:
        """Conservative fleet run: fused per-lane window loops in one
        vmapped dispatch, scheduler work at every handoff boundary.
        Returns the dispatch count.

        Pipelined (core/pipeline.py): dispatch N+1 is issued before
        window N's scheduler work runs — only across quiet boundaries
        (no harvest/admission/injection/deadline/checkpoint due), and
        the issue is recomputed whenever the handoff took any scheduler
        action (`changed`), shifted the gear, or mutated fleet state."""
        wpd = windows_per_dispatch or self.windows_per_dispatch
        dispatches = 0
        last_sig = None
        obs = self.obs_session
        pipe = self._pipeline()
        pending = None
        try:
            while not self.sched.all_terminal():
                if max_dispatches is not None \
                        and dispatches >= max_dispatches:
                    break
                # expired-deadline lanes free up BEFORE the dispatch — a
                # dead job never rides another dispatch holding its lane
                if self._reclaim_expired() and pipe is not None:
                    pipe.discard()
                if self.sched.all_terminal():
                    break
                eff_stop = np.minimum(
                    np.minimum(self._stop, self._fault_marks()),
                    self._backend_fault_mark(),
                )
                pending = (
                    pipe.take(self.state,
                              (eff_stop.tobytes(), wpd))
                    if pipe is not None else None
                )
                if pending is None:
                    with metrics_mod.span(obs, "dispatch", windows=wpd):
                        p = self._sv_issue(
                            "run_to", *self._run_to_halves(eff_stop, wpd)
                        )
                        self.state, mn, press, occ, ainfo = \
                            self._sv_await(p)
                else:
                    with metrics_mod.span(obs, "await", windows=wpd):
                        self.state, mn, press, occ, ainfo = \
                            self._sv_await(pending)
                    pending = None
                # two-slot pipeline: issue dispatch N+1 while the host
                # runs this boundary's scheduler work
                if pipe is not None and not self.sched.all_terminal():
                    if (not press.any() and self._handoff_quiet(mn)
                            and not self._sv_disrupted()):
                        nxt = np.minimum(
                            np.minimum(self._stop, self._fault_marks()),
                            self._backend_fault_mark(),
                        )
                        with metrics_mod.span(obs, "issue", windows=wpd):
                            pipe.put(
                                self._sv_issue(
                                    "run_to",
                                    *self._run_to_halves(nxt, wpd),
                                ),
                                self.state, (nxt.tobytes(), wpd),
                            )
                    else:
                        pipe.forced_drain()
                with metrics_mod.span(obs, "host_drain"):
                    if ainfo is not None:
                        c = self._async_counters
                        c["dispatches"] += 1
                        c["supersteps"] += ainfo[5]
                        c["shard_windows"] += ainfo[2]
                        c["yields"] += ainfo[3]
                        c["blocked_on_neighbor"] += ainfo[4]
                        self._async_spread_max = max(
                            self._async_spread_max, ainfo[1]
                        )
                        self._async_frontier = ainfo[0]
                        if len(ainfo) > 6 and ainfo[6] is not None:
                            st6 = self._async_shard_stats
                            if st6 is None or st6.shape != ainfo[6].shape:
                                st6 = np.zeros_like(ainfo[6])
                            self._async_shard_stats = st6 + ainfo[6]
                    dispatches += 1
                    if obs is not None:
                        obs.round_done(self, int(mn.min()))
                    self._backend_fault_tick(mn)
                    changed = self._handoff(mn, press)
                    if self._shifter is not None and not (
                        self.pressure is not None
                        and self.pressure.hold_gear
                    ):
                        new = self._shifter.observe(
                            self._gear, occ, press=bool(press.any())
                        )
                        if new is not None:
                            self._shift_gear(new)
                            changed = True
                    # handoff hooks: sharded ones fan out per lane
                    # across the host plane's pinned drain workers,
                    # inside this host_drain span — i.e. inside the
                    # pipeline's issue->await overlap window
                    self._run_handoff_hooks(mn)
                if pipe is not None:
                    if changed or self._sv_disrupted():
                        pipe.discard()
                    else:
                        pipe.invalidate(self.state)
                sig = (tuple(mn),
                       tuple(r.status for r in self.sched.records),
                       tuple(len(lf.pending) for lf in self._lane_faults),
                       self._gear)
                if not changed and sig == last_sig:
                    cap = self._ladder[self._gear].capacity
                    if self._pressure_stall(
                        window=int(mn.min()), occupancy=occ,
                        capacity=cap,
                    ):
                        last_sig = None  # a ladder rung reshaped the fleet
                        continue
                    raise self._pool_exhausted(
                        "fleet cannot make progress: no lane advanced and "
                        "no scheduler action fired (pool occupancy leaves "
                        "too little headroom for even one window's "
                        "emissions); raise experimental.event_capacity",
                        window=int(mn.min()), occupancy=occ,
                        capacity=cap,
                    )
                elif self.pressure is not None:
                    self.pressure.note_progress()
                last_sig = sig
        finally:
            if pipe is not None:
                pipe.close()
        return dispatches

    def _reset_done_t(self) -> None:
        d = self.state.host.done_t
        self.state = self.state.replace(
            host=self.state.host.replace(done_t=jnp.full_like(d, -1))
        )

    def _attempt_round(self, base, ws: np.ndarray, we: np.ndarray):
        """One optimistic attempt over all lanes from the snapshot
        `base`: per-lane windows [ws, we) processed to completion.
        Returns (state, mn[L], viol[L]). Global engine: one fused
        dispatch (vmapped attempt kernel). Islands: host-driven sub-steps
        (vmap-of-jobs over vmap-of-shards), mirroring the solo islands
        attempt loop — every lane gets at least one sub-step, so a lane
        parked on an exchange-deferred frontier retries its exchange (the
        solo driver's null-window stall)."""
        ws_d, we_d = jnp.asarray(ws), jnp.asarray(we)
        obs = self.obs_session
        if not self._islands:
            with metrics_mod.span(obs, "dispatch"):

                def _dispatch():
                    st, mn, viol = self._attempt(
                        base, self.params, ws_d, we_d
                    )
                    return (
                        st,
                        np.array(jax.device_get(mn), np.int64),
                        np.array(jax.device_get(viol), np.int64),
                    )

                return self._sv("attempt", _dispatch)
        st = base
        mn = ws.copy()
        viol = np.full(self.lanes, int(NEVER), np.int64)
        k = 0
        while True:
            with metrics_mod.span(obs, "dispatch"):

                def _substep(st=st, lo=jnp.asarray(np.maximum(mn, ws))):
                    s2, mn_d, viol_d = self._attempt(
                        st, self.params, lo, we_d
                    )
                    return (
                        s2,
                        np.asarray(jax.device_get(mn_d)),
                        np.asarray(jax.device_get(viol_d)),
                    )

                st, mn_d, viol_d = self._sv("attempt", _substep)
            mn = mn_d.reshape(self.lanes, -1).min(axis=1)
            viol = np.minimum(
                viol, viol_d.reshape(self.lanes, -1).min(axis=1)
            )
            k += 1
            need = (mn < we) & (viol >= int(NEVER))
            if not need.any():
                return st, mn, viol
            if k >= _MAX_SUBSTEPS:
                if (need & (mn <= ws)).any():
                    # mid-attempt: the snapshot pins the compiled shapes,
                    # so no reshaping rung is safe — typed exhaustion
                    j = int(np.argmax(need & (mn <= ws)))
                    raise self._pool_exhausted(
                        "optimistic fleet attempt cannot make progress "
                        "(pool-headroom stall); raise "
                        "experimental.event_capacity",
                        window=int(ws[j]),
                        occupancy=int(self._lane_occupancies()[j]),
                        capacity=self._ladder[self._gear].capacity,
                    )
                # genuinely enormous window: report the reached frontier;
                # the caller shrinks those lanes and retries from base
                return st, mn, viol

    def run_optimistic(
        self,
        window_factor: int = 8,
        adaptive: bool = True,
        max_rounds: int | None = None,
    ) -> tuple[int, int]:
        """Per-lane speculative windows (the Time-Warp shape of the solo
        run_optimistic, vectorized over jobs): every lane speculates its
        own [ws, ws + factor·runahead) window each round; a lane whose
        attempt reports a violation shrinks ITS window and the round
        retries from the snapshot (clean lanes recompute identical
        results — pure functions). The per-lane adaptive factor follows
        Simulation.adapt_window_factor. Returns (rounds, rollbacks)."""
        self._ensure_attempt()
        L = self.lanes
        factor = np.full(L, int(window_factor), np.int64)
        streak = np.zeros(L, np.int64)
        rounds = rollbacks = 0
        never = int(NEVER)
        self._reset_done_t()
        mn = self._lane_min_times()
        last_sig = None
        while not self.sched.all_terminal():
            if max_rounds is not None and rounds >= max_rounds:
                break
            if self._reclaim_expired():
                mn = self._lane_min_times()
                if self.sched.all_terminal():
                    break
            cons = self._runahead
            stop = self._stop
            ws = mn.copy()
            if self._islands:
                clamp = np.asarray(jax.device_get(jnp.min(
                    self.state.exch_deferred_min.reshape(L, -1), axis=-1
                )), np.int64)
                floor = np.minimum(ws + cons, clamp)
            else:
                floor = ws + cons
            we = np.minimum(
                np.maximum(np.minimum(ws + factor * cons, stop), floor),
                stop,
            )
            # finished/idle lanes attempt nothing: ws == we == frontier
            idle = mn >= stop
            we = np.where(idle, np.maximum(ws, stop), we)
            # in-transit deferred row parked AT a lane's frontier: that
            # lane gets a null-window round (we == ws) so its first
            # sub-step retries the exchange — the solo islands driver's
            # null-window stall, lane-scoped
            stalled = (~idle) & (floor <= ws)
            we = np.where(stalled, ws, we)
            base = self.state
            rb_round = np.zeros(L, np.int64)
            # reshaping pressure rungs are unsafe while `base` pins the
            # compiled shapes (core/pressure.py)
            self._pressure_reshape_ok = False
            while True:
                st, mn_a, viol = self._attempt_round(base, ws, we)
                bad = (viol < never) & ~idle
                guard = bad & (we <= floor)
                if guard.any():
                    j = int(np.argmax(guard))
                    # A floor-width window is violation-free BY
                    # CONSTRUCTION; a violation here means the
                    # conservative-width invariant itself is broken —
                    # refuse to commit (ADVICE r5 #1, fleet-scoped).
                    raise RuntimeError(
                        f"speculation violation at t={int(viol[j])} inside "
                        f"a floor-width window [{int(ws[j])}, {int(we[j])}) "
                        f"on lane {j}: the conservative-width invariant is "
                        f"broken (runahead exceeds a real path latency, or "
                        f"a handler emitted into the past); refusing to "
                        f"commit"
                    )
                incomplete = (viol >= never) & (mn_a < we) & ~idle
                if incomplete.any():
                    # sub-step ceiling hit: shrink to the reached frontier
                    we = np.where(incomplete, np.maximum(mn_a, floor), we)
                    rb_round += incomplete  # counted as shrinks
                    continue
                if not bad.any():
                    break
                rb_round += bad
                we = np.where(
                    bad, np.minimum(np.maximum(viol, floor), stop), we
                )
            self._pressure_reshape_ok = True
            rollbacks += int(rb_round.sum())
            self.state = st
            for j in np.flatnonzero(rb_round):
                self._bump_lane_win(int(j), obs_mod.WIN_ROLLBACKS,
                                    int(rb_round[j]))
                self._bump_lane_win(int(j), obs_mod.WIN_SHRINKS,
                                    int(rb_round[j]))
            self._reset_done_t()
            mn = mn_a
            rounds += 1
            if self.obs_session is not None:
                self.obs_session.round_done(self, int(mn.min()))
            self._backend_fault_tick(mn)
            if adaptive:
                for j in range(L):
                    if not idle[j]:
                        f, s = engine_mod.Simulation.adapt_window_factor(
                            int(factor[j]), int(streak[j]),
                            bool(rb_round[j]), int(window_factor),
                        )
                        factor[j], streak[j] = f, s
            before = [self.sched.lane_job[j] for j in range(L)]
            changed = self._handoff(mn, np.zeros(L, bool))
            for j in range(L):
                if self.sched.lane_job[j] is not before[j]:
                    # a fresh job entered lane j: it speculates from the
                    # full factor with a clean streak, like a solo run
                    factor[j] = int(window_factor)
                    streak[j] = 0
            if changed:
                mn = self._lane_min_times()
            sig = (tuple(mn), tuple(r.status for r in self.sched.records))
            if not changed and not (mn > ws).any() and sig == last_sig:
                cap = self._ladder[self._gear].capacity
                if self._pressure_stall(
                    window=int(mn.min()),
                    occupancy=int(self._lane_occupancies().max(initial=0)),
                    capacity=cap,
                ):
                    last_sig = None  # a ladder rung reshaped the fleet
                    continue
                raise self._pool_exhausted(
                    "optimistic fleet cannot make progress; raise "
                    "experimental.event_capacity",
                    window=int(mn.min()),
                    occupancy=int(self._lane_occupancies().max(initial=0)),
                    capacity=cap,
                )
            elif self.pressure is not None:
                self.pressure.note_progress()
            last_sig = sig
        return rounds, rollbacks

    # ------------------------------------------------------------------
    # results / telemetry
    # ------------------------------------------------------------------

    def results(self) -> list[dict]:
        """Per-job result rows (metrics schema v4 `fleet.jobs[*]`), in
        job DECLARATION order — stable across checkpoint/resume even
        though a resumed fleet's internal records list is rebuilt
        running-jobs-first (each record carries its original
        submit_idx)."""
        return [r.summary() for r in self.records()]

    def records(self) -> list[JobRecord]:
        return sorted(self.sched.records, key=lambda r: r.submit_idx)

    def fleet_stats(self) -> dict:
        spec = self._ladder[self._gear]
        st = self.sched.stats()
        st.update({
            "kernel_traces": self.kernel_traces,
            "gear_level": self._gear,
            "gear_capacity": spec.capacity,
            "gear_shifts": self.gear_shifts,
            "islands": self._islands,
        })
        return st

    def async_stats(self) -> dict[str, int] | None:
        """Fleet-summed async-sync counters (schema v9 `async.*`); None
        for barrier or non-islands fleets."""
        if not self._async:
            return None
        return dict(self._async_counters)

    def async_shard_profile(self) -> dict | None:
        """Per-shard async profile for the profiling plane (obs/prof.py
        critical-path attribution, schema v18). Lanes are folded: the
        cumulative steps/yields/blocked sum over lanes, the frontier is
        the lane-min per shard (the bound conservative sync enforces),
        and the in-edge lookahead matrix comes from lane 0 (identical
        across lanes — one topology per fleet). None for barrier fleets
        or before the first async dispatch."""
        if not self._async or self._async_shard_stats is None:
            return None
        st = self._async_shard_stats  # [3, L, S]
        prof = {
            "shards": int(st.shape[-1]),
            "lanes": self.lanes,
            "steps": [int(x) for x in st[0].sum(axis=0)],
            "yields": [int(x) for x in st[1].sum(axis=0)],
            "blocked": [int(x) for x in st[2].sum(axis=0)],
        }
        if self._async_frontier is not None:
            f = np.asarray(self._async_frontier)
            prof["frontier_ns"] = [int(x) for x in f.min(axis=0)]
        if self._look_in_cache is None:
            self._look_in_cache = [
                [int(x) for x in row] for row in self._async_look[0]
            ]
        prof["lookahead_in"] = self._look_in_cache
        return prof

    def async_gauges(self) -> dict[str, int] | None:
        if not self._async:
            return None
        g = {
            "spread_bound_ns": int(np.max(self._async_spread)),
            "frontier_spread_max_ns": int(self._async_spread_max),
        }
        if self._async_frontier is not None:
            g["frontier_min_ns"] = int(self._async_frontier.min())
            g["frontier_max_ns"] = int(self._async_frontier.max())
        return g

    def async_posture(self) -> dict:
        """Operator-facing async posture for the serve daemon's /healthz
        (docs/serving.md): the live frontier spread and WHICH (lane,
        shard) is the laggard — the hot-shard signal `shadowctl status`
        surfaces without grepping metrics JSON. {} for barrier fleets or
        before the first async dispatch."""
        if not self._async or self._async_frontier is None:
            return {}
        f = np.asarray(self._async_frontier)
        lane, shard = np.unravel_index(int(np.argmin(f)), f.shape)
        return {
            "frontier_spread_ns": int(f.max() - f.min()),
            "frontier_spread_max_ns": int(self._async_spread_max),
            "laggard_lane": int(lane),
            "laggard_shard": int(shard),
        }

    def mesh_posture(self) -> dict:
        """Operator-facing mesh posture for the serve daemon's /healthz
        and `shadowctl status` (schema v12): chips up/total, the
        partition shape, the exchange-schedule rebuild count, and —
        when an elastic runner is attached — the last relayout record.
        {} for non-islands fleets (no mesh keys on non-mesh runs)."""
        if not self._islands:
            return {}
        t = self.template
        total = int(t.num_shards) + len(getattr(t, "exclude_chips", ()))
        p = {
            "chips_up": int(t.num_shards),
            "chips_total": total,
            "shard_map": int(getattr(t, "mode", "") == "shard_map"),
            "chips_down": sorted(getattr(t, "exclude_chips", ())),
            "exchange_rebuilds": int(
                getattr(t, "_exchange_rebuilds", 0)
            ),
        }
        el = getattr(self, "elastic", None)
        if el is not None:
            p.update(el.posture())
        return p

    def balance_stats(self) -> dict[str, int] | None:
        """Fleet-side balance plane (schema v10 `balance.*`): the
        scheduler's predicted-load packing + lane-steal tallies; None
        under plain FIFO with no decisions taken (solo sweeps carry no
        balance keys)."""
        s = self.sched
        if s.packing == "fifo" and not s.pack_decisions:
            return None
        return {
            "pack_decisions": int(s.pack_decisions),
            "lane_steals": int(s.lane_steals),
        }

    def balance_gauges(self) -> dict | None:
        s = self.sched
        if s.packing == "fifo" and not s.pack_decisions:
            return None
        return {
            "packing_load": int(s.packing == "load"),
            "calibrated_rate": float(s.rate_ewma or 0.0),
        }

    def ok(self) -> bool:
        return all(r.status == DONE for r in self.sched.records)


def build_fleet(
    jobs: list[JobSpec],
    lanes: int | None = None,
    **kw,
) -> FleetSimulation:
    """Build a FleetSimulation from a validated job list (fleet/sweep.py
    expand_sweep / load_job_list output)."""
    return FleetSimulation(jobs, lanes=lanes, **kw)
