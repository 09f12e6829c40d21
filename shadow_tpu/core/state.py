"""Device-side simulation state: struct-of-arrays over fixed capacities.

The reference keeps pointer-rich per-host objects (Host owns interfaces,
router, processes; events live in per-host locked priority queues —
src/main/host/host.c:49-95, scheduler_policy_host_single.c:18-54). The TPU
design inverts this: ALL simulation state is flat arrays indexed by host /
pool-slot / socket, registered as pytrees, and a window step is a pure
function over them.

Capacities are static (compiled into the kernel):
    C  event-pool slots per shard
    K  max events extracted per host per window
    B  self-inbox slots (intra-window self-emitted events, e.g. short timers)
    O  outbox slots per host per window (emissions buffered until merge)
    P  payload words per event (packet header fields)
Overflow never corrupts the sim: inbox/outbox pressure DEFERS work to later
windows (backpressure stalls the host, nothing is lost); only event-pool
capacity overflow drops, and that is counted in `Counters` and asserted
zero by the benchmarks.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
import numpy as np
from flax import struct

from shadow_tpu.core import simtime

# Payload words per event. Layout is defined by shadow_tpu.net.packet.
PAYLOAD_WORDS = 12

# Event kinds. Handlers register against these (engine.HandlerRegistry).
KIND_NONE = 0
KIND_APP_MSG = 1  # app-level message delivery (engine-v1 path, PHOLD)
KIND_APP_TIMER = 2  # app-defined timer
KIND_PKT_DELIVER = 3  # packet arrives at dst host's upstream router
KIND_NIC_REFILL = 4  # token-bucket refill retry (network_interface.c:127-193)
KIND_TCP_TIMER = 5  # TCP retransmit timeout
KIND_PROC_SYSCALL = 6  # CPU-plane syscall completion injection
NUM_KINDS = 7


@struct.dataclass
class EventPool:
    """Pending events, one row per slot; time == NEVER marks a free slot.

    The deterministic total order (event.c:109-152) is the tuple
    (time, dst, src, seq); seq is assigned from the emitting host's counter
    like the reference's per-source event ID.

    Payload words are stored PACKED, two i32 words per i64 column
    (core.soa.pack_words), halving the box-write traffic; the engine's
    row gather moves them as 32-bit words either way (engine._take_rows).
    Handlers always see the unpacked [H, P] i32 view via EventView.
    """

    time: jnp.ndarray  # [C] i64 ns
    dst: jnp.ndarray  # [C] i32 global host index
    src: jnp.ndarray  # [C] i32
    seq: jnp.ndarray  # [C] i32
    kind: jnp.ndarray  # [C] i32
    payload: jnp.ndarray  # [C, ceil(P/2)] i64 PACKED (soa.pack_words)

    @classmethod
    def empty(cls, capacity: int,
              payload_words: int = PAYLOAD_WORDS) -> "EventPool":
        # payload_words is sizable per simulation: network sims need the
        # full packet-header layout (12 words, net/packet.py); pure-PDES
        # models like PHOLD carry 2 — payload columns are a dominant
        # per-window sort cost on TPU, so right-sizing is a direct speedup.
        from shadow_tpu.core import soa

        return cls(
            time=jnp.full((capacity,), simtime.NEVER, dtype=jnp.int64),
            dst=jnp.zeros((capacity,), dtype=jnp.int32),
            src=jnp.zeros((capacity,), dtype=jnp.int32),
            seq=jnp.zeros((capacity,), dtype=jnp.int32),
            kind=jnp.zeros((capacity,), dtype=jnp.int32),
            payload=jnp.zeros(
                (capacity, soa.packed_words(payload_words)), dtype=jnp.int64
            ),
        )

    @property
    def capacity(self) -> int:
        return self.time.shape[0]


@struct.dataclass
class Counters:
    """Device-side observability counters (reference: tracker.c, counter.rs).

    All [()] i64 scalars summed across the mesh at fetch time.
    """

    events_committed: jnp.ndarray
    events_emitted: jnp.ndarray
    packets_sent: jnp.ndarray
    packets_delivered: jnp.ndarray
    packets_dropped_loss: jnp.ndarray  # reliability roll failures (worker.c:539)
    packets_dropped_unreachable: jnp.ndarray
    pool_overflow_dropped: jnp.ndarray
    outbox_overflow_dropped: jnp.ndarray  # structurally 0 (backpressure)
    inbox_overflow_deferred: jnp.ndarray
    # iterations a host sat out because its outbox couldn't absorb one
    # iteration's worst-case emissions; the work defers, never drops
    outbox_stall_deferred: jnp.ndarray
    # engine-loop iterations executed (profiling: events_committed /
    # (micro_steps * H) = lane utilization; the per-iteration fixed cost
    # of the full handler suite is the throughput ceiling)
    micro_steps: jnp.ndarray
    bytes_sent: jnp.ndarray
    bytes_delivered: jnp.ndarray
    # matrix-path safety: count of bulk-kind emissions that targeted SELF
    # below win_end — forbidden by the bulk contract (engine.make_window_step
    # docstring); nonzero means the fast path may have corrupted event
    # order. Asserted zero by tests; always-on (the check is elementwise).
    bulk_contract_violations: jnp.ndarray
    # total ns of CPU-model execution deferral applied to device events
    # (tracker_addVirtualProcessingDelay analog); 0 when the model is off
    cpu_delay_applied: jnp.ndarray
    # islands engine (parallel/islands.py): cross-shard rows shipped
    # through the all_to_all exchange, and rows that missed the bounded
    # exchange window and deferred (retried next window under the
    # exch_deferred_min window-end clamp — late but never lost)
    exchange_sent: jnp.ndarray
    exchange_deferred: jnp.ndarray

    @classmethod
    def zeros(cls) -> "Counters":
        z = lambda: jnp.zeros((), dtype=jnp.int64)  # noqa: E731
        return cls(**{f.name: z() for f in dataclasses.fields(cls)})


@struct.dataclass
class HostState:
    """Per-host scalars the engine itself needs. [H] arrays."""

    seq_next: jnp.ndarray  # i32: next event sequence number for emissions
    rng_counter: jnp.ndarray  # u32: per-host RNG draw counter
    vertex: jnp.ndarray  # i32: used-vertex index in the baked topology
    # GLOBAL host id of each local row. On the global engine this is
    # arange(H); on the islands engine each shard holds the contiguous
    # block [shard*H_local, (shard+1)*H_local). Handlers MUST use this —
    # never jnp.arange(H) — wherever a value means "my host id" (packet
    # src fields, loopback compares, self-addressed timer emissions):
    # under islands arange would alias every shard onto shard 0's ids.
    gid: jnp.ndarray  # i32
    # Max event time processed since the optimistic synchronizer last reset
    # it (-1 = none): the per-host progress clock that speculation
    # violations are judged against. Unused by conservative runs.
    done_t: jnp.ndarray  # i64
    # Device-plane CPU model (host/cpu.c analog, deterministic form):
    # cpu_cost = simulated processing nanoseconds per event (0 = off);
    # cpu_avail = the host CPU's next-free time (timeCPUAvailable). An
    # event at t executes at max(t, cpu_avail) and advances cpu_avail by
    # cpu_cost — a loaded host's events serialize on its virtual CPU.
    cpu_cost: jnp.ndarray  # i64
    cpu_avail: jnp.ndarray  # i64


@struct.dataclass
class NetParams:
    """Immutable baked network model (broadcast to all shards)."""

    latency_vv: jnp.ndarray  # [U, U] i64 ns; NEVER = unreachable
    reliability_vv: jnp.ndarray  # [U, U] f32
    bootstrap_end: jnp.ndarray  # [] i64: no drops before this time
    # (configuration.rs:149-152, worker.c:536-545)
    # GLOBAL host→vertex table, replicated to every shard. Destination
    # host ids are global, so by-dst latency lookups under the islands
    # engine must not index the shard-local host.vertex rows. None on
    # single-vertex topologies (every lookup broadcasts) and legacy tests.
    vertex_g: jnp.ndarray | None = None
    # Islands re-sharding (scheduler_policy_host_steal.c analog): global
    # host id → SLOT in the permuted island layout (shard = slot // H_l,
    # local row = slot % H_l). None = static contiguous blocks (slot is
    # the identity, routing is pure arithmetic). A rebalance permutes host
    # rows across shards and rewrites this table — params are runtime
    # arguments, so no recompilation.
    slot_of: jnp.ndarray | None = None


@struct.dataclass
class SimState:
    """Everything a window step reads and writes."""

    now: jnp.ndarray  # [] i64: current window start
    pool: EventPool
    host: HostState
    counters: Counters
    rng_keys: jnp.ndarray  # [H] per-host PRNG key array (core.rng.host_keys)
    # Earliest cross-host emission time of the LAST window stepped (NEVER if
    # none). The optimistic synchronizer compares it against the window end
    # to detect speculation violations (SURVEY §7.6); conservative windows
    # satisfy xmit_min >= window end by construction.
    xmit_min: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.asarray(simtime.NEVER, jnp.int64)
    )
    # Islands engine: min event time among cross-shard rows that missed the
    # bounded exchange this window (NEVER if none). The driver clamps the
    # next window's END to this so the destination shard cannot process
    # past an in-transit event — the conservative invariant survives
    # exchange backpressure (see parallel/islands.py). Always NEVER on the
    # global engine.
    exch_deferred_min: jnp.ndarray = struct.field(
        default_factory=lambda: jnp.asarray(simtime.NEVER, jnp.int64)
    )
    # Subsystem states keyed by name ("nic", "udp", "tcp", app models...).
    # A plain dict is a pytree node; handlers look up their own slice.
    subs: dict[str, Any] = struct.field(default_factory=dict)
    # Device telemetry counter block (shadow_tpu.obs.counters.ObsBlock):
    # window-plane counters + per-host committed-event/virtual-time rows,
    # updated inside the jitted step with fused adds and read only at
    # handoff boundaries. None compiles every update out (the bench's
    # obs-overhead control arm; experimental.obs_counters).
    obs: Any = None
    # Flight recorder (shadow_tpu.obs.flight.FlightRing): opt-in per-host
    # ring of the last R committed event records, written in-kernel by
    # masked one-hot updates and flushed to a binary spool at handoff
    # boundaries (experimental.flight_recorder). Rides the pytree like
    # obs: rollbacks discard speculated records, checkpoints capture the
    # ring, the fleet stacks it per lane. None compiles it out.
    flight: Any = None

    def with_sub(self, key: str, value) -> "SimState":
        """Functional sub-state update (dict copy; the pytree structure is
        unchanged so jit caches stay valid)."""
        subs = dict(self.subs)
        subs[key] = value
        return self.replace(subs=subs)


# ---------------------------------------------------------------------------
# Fleet batch axis (shadow_tpu/fleet): F independent jobs stacked along a
# NEW leading axis over every state/params leaf. The window kernel is
# vmapped over it — per-job halt comes from per-lane (runahead, stop)
# window bounds, so a finished job's lane freezes (its fused-loop cond is
# false) without mutating any other lane. These helpers are the only
# sanctioned way to build/read/replace a lane: they preserve pytree
# structure exactly, so the compiled fleet kernel never retraces on a
# lane swap.
# ---------------------------------------------------------------------------


def stack_pytrees(trees: list):
    """Stack identically-structured pytrees along a new leading axis.
    Leaf shape/dtype mismatches raise with the offending key path (the
    fleet's job-compatibility error surface)."""
    import jax

    flat0, treedef = jax.tree_util.tree_flatten_with_path(trees[0])
    cols = [[leaf for _, leaf in flat0]]
    for t in trees[1:]:
        flat, td = jax.tree_util.tree_flatten_with_path(t)
        if td != treedef:
            raise ValueError(
                "fleet jobs carry different state structures (subsystem "
                "or telemetry config differs); jobs sharing one kernel "
                "must be built from compatible configs"
            )
        for (path, a), b in zip(flat0, (leaf for _, leaf in flat)):
            ja, jb = jnp.asarray(a), jnp.asarray(b)
            if ja.shape != jb.shape or ja.dtype != jb.dtype:
                raise ValueError(
                    f"fleet leaf {jax.tree_util.keystr(path)}: "
                    f"{jb.shape}/{jb.dtype} vs template {ja.shape}/"
                    f"{ja.dtype} — jobs sharing one kernel must compile "
                    f"identical shapes"
                )
        cols.append([leaf for _, leaf in flat])
    stacked = [jnp.stack(col) for col in zip(*cols)]
    return jax.tree_util.tree_unflatten(treedef, stacked)


def slice_lane(tree, lane: int):
    """Read one job's slice out of a stacked fleet pytree (device-side
    views; the solo layout the lane was admitted with)."""
    import jax

    return jax.tree.map(lambda x: x[lane], tree)


def set_lane(tree, lane: int, solo):
    """Replace lane `lane` of a stacked fleet pytree with a solo-layout
    pytree (the lane-swap write). Structure must match the stack."""
    import jax

    return jax.tree.map(lambda s, n: s.at[lane].set(n), tree, solo)


def make_host_state(
    num_hosts: int, host_vertex: np.ndarray, cpu_cost: np.ndarray | None = None
) -> HostState:
    return HostState(
        seq_next=jnp.zeros((num_hosts,), dtype=jnp.int32),
        rng_counter=jnp.zeros((num_hosts,), dtype=jnp.uint32),
        vertex=jnp.asarray(host_vertex, dtype=jnp.int32),
        gid=jnp.arange(num_hosts, dtype=jnp.int32),
        done_t=jnp.full((num_hosts,), -1, dtype=jnp.int64),
        cpu_cost=(
            jnp.asarray(cpu_cost, dtype=jnp.int64)
            if cpu_cost is not None
            else jnp.zeros((num_hosts,), dtype=jnp.int64)
        ),
        cpu_avail=jnp.zeros((num_hosts,), dtype=jnp.int64),
    )
