"""Top-level simulation builder: Config → runnable Simulation.

Plays the reference's controller/manager setup sequence
(src/main/core/controller.c:79-338: load topology, register hosts via DNS +
topology attach, create scheduler, compute runahead windows) and hands back a
`Simulation` whose window kernel runs on device.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from shadow_tpu.core import simtime, units
from shadow_tpu.core.config import Config, load_config
from shadow_tpu.core.engine import Simulation
from shadow_tpu.core.state import NetParams
from shadow_tpu.net.apps import (
    PholdApp,
    TcpBulkApp,
    UdpEchoApp,
    UdpFloodApp,
    locality_targets,
)
from shadow_tpu.net.stack import NetStack
from shadow_tpu.routing.dns import Dns
from shadow_tpu.routing.topology import BakedPaths, Topology


class BuildError(ValueError):
    pass


def _qdisc_discipline(cfg: Config, H: int):
    """Resolve the `qdisc:` section (plus the legacy
    experimental.interface_qdisc string) to a Discipline instance."""
    from shadow_tpu.net import qdisc as qdisc_mod

    qopt = cfg.qdisc
    # an explicit qdisc section wins; `fifo` (the default) defers to the
    # legacy string so pre-qdisc configs build the exact same stack
    eff = (
        qopt.discipline
        if qopt.discipline != "fifo"
        else cfg.experimental.interface_qdisc
    )
    if eff not in ("pifo", "eiffel"):
        return qdisc_mod.make_discipline(eff)

    from shadow_tpu.net.qdisc import drops as qdrops
    from shadow_tpu.net.qdisc import ranks as qranks
    from shadow_tpu.net.qdisc.eiffel import EiffelDiscipline
    from shadow_tpu.net.qdisc.pifo import PifoDiscipline

    ranker = qranks.make_ranker(
        qopt.rank, classes=qopt.classes, weights=qopt.weights,
        shaping=qopt.shaping,
    )
    red = (
        qdrops.RedConfig(
            qopt.queue_slots, qopt.red_min_frac, qopt.red_max_frac,
            qopt.red_max_p,
        )
        if qopt.drop == "red"
        else None
    )
    host_class = None
    if qopt.overrides:
        # host names are quantity-expanded and sorted by the config
        # loader, so prefix pins hit every replica of a host block
        host_class = np.full(H, -1, dtype=np.int32)
        for i, h in enumerate(cfg.hosts):
            for prefix, c in qopt.overrides.items():
                if h.name.startswith(prefix):
                    host_class[i] = c
    kw = dict(
        queue_slots=qopt.queue_slots, ranker=ranker, drop=qopt.drop,
        red=red, host_class=host_class,
    )
    if eff == "eiffel":
        return EiffelDiscipline(
            buckets=qopt.buckets, bucket_width=qopt.bucket_width, **kw
        )
    return PifoDiscipline(**kw)


def _start_offsets(clients: list[int], target, H: int, spread: int,
                   seed: int) -> np.ndarray:
    """[H] ns after start_time at which each host starts: client j (in
    host order) of the n clients at slot_j * spread // n. `slot` is a
    permutation of 0..n-1 drawn from the seed (numpy's default_rng of seed
    mod 2^64) and stratified by target: for each target in order, its m
    clients (in host order) draw keys (rng.permutation(m) + rng.random(m))
    / m, and slot is a client's rank by key. Each target's clients so take
    evenly spread slots and the targets interleave in a seeded order: a
    seed changes which client sends when, never the load a target sees in
    any stretch of the interval. Spread 0 starts every client at
    start_time."""
    out = np.zeros(H, np.int64)
    n = len(clients)
    if spread and n:
        rng = np.random.default_rng(seed % 2**64)
        clients = np.asarray(clients)
        tgt = np.asarray(target)[clients]
        key = np.empty(n)
        for t in np.unique(tgt):
            mine = np.flatnonzero(tgt == t)
            m = len(mine)
            key[mine] = (rng.permutation(m) + rng.random(m)) / m
        slot = np.argsort(np.argsort(key, kind="stable"), kind="stable")
        out[clients] = slot.astype(np.int64) * spread // n
    return out


def build_simulation(source) -> Simulation:
    """Build from a Config, YAML path/string, or dict."""
    cfg = source if isinstance(source, Config) else load_config(source)
    if not cfg.hosts:
        raise BuildError("no hosts configured")

    topo = Topology.from_gml(cfg.graph_gml(), cfg.network.use_shortest_path)
    dns = Dns()
    for i, h in enumerate(cfg.hosts):
        topo.attach_host(
            i,
            ip_address_hint=h.ip_address_hint,
            city_code_hint=h.city_code_hint,
            country_code_hint=h.country_code_hint,
            network_node_id=h.network_node_id,
        )
        dns.register(i, h.name, h.ip_address_hint)
    baked: BakedPaths = topo.bake()

    params = NetParams(
        latency_vv=jnp.asarray(baked.latency_vv),
        reliability_vv=jnp.asarray(baked.reliability_vv),
        bootstrap_end=jnp.int64(cfg.general.bootstrap_end_time),
        # replicated GLOBAL host→vertex table for by-dst path lookups
        # (required under islands, where host.vertex is shard-local);
        # single-vertex graphs broadcast instead and skip the gather
        vertex_g=(
            jnp.asarray(baked.host_vertex, dtype=jnp.int32)
            if np.asarray(baked.latency_vv).shape[0] > 1
            else None
        ),
    )
    runahead = cfg.experimental.runahead or baked.min_latency_ns
    if runahead > baked.min_latency_ns:
        # Reference semantics (configuration.rs:288-291): an explicit runahead
        # overrides the computed minimum. Windows longer than the min path
        # latency trade accuracy for speed: sub-window cross-host deliveries
        # are processed one window late. Surface that choice loudly.
        import warnings

        warnings.warn(
            f"runahead {runahead}ns exceeds min topology latency "
            f"{baked.min_latency_ns}ns: cross-host events inside a window "
            f"may be processed one window late (accuracy/speed tradeoff)",
            stacklevel=2,
        )

    # --- device-side app models ---
    handlers: dict = {}
    subs: dict = {}
    initial_events: list = []
    bulk_kinds: dict | None = None
    matrix_handlers: dict | None = None
    bulk_gate = None
    bulk_self_excluded = False
    payload_words = 12  # net/packet.py layout; pure-PDES apps shrink it
    H = len(cfg.hosts)
    app_names = {h.app_model for h in cfg.hosts if h.app_model}
    # hosts whose group gives them a router ring of their own depth
    deep = {i: h.router_queue_slots for i, h in enumerate(cfg.hosts)
            if h.router_queue_slots is not None}
    if deep and not app_names & {"udp_flood", "udp_echo", "tcp_bulk"}:
        raise BuildError(
            "hosts.<group>.router_queue_slots needs a network-stack app "
            "model (udp_flood, udp_echo, tcp_bulk)"
        )
    if len(set(deep.values())) > 1:
        raise BuildError(
            "every host group that sets router_queue_slots must set the "
            f"same depth, got {sorted(set(deep.values()))}"
        )
    if deep and cfg.experimental.num_shards > 1:
        raise BuildError(
            "the islands and mesh layouts do not carry the deep router "
            "ring: hosts.<group>.router_queue_slots needs num_shards 1"
        )
    if "phold" in app_names:
        phold_hosts = [h for h in cfg.hosts if h.app_model == "phold"]
        if len(phold_hosts) != H:
            raise BuildError(
                "phold app model currently requires every host to run it"
            )
        distinct = {tuple(sorted(h.app_options.items())) for h in phold_hosts}
        if len(distinct) > 1:
            raise BuildError(
                "phold app_options must be identical across all hosts "
                "(per-host options are not supported yet)"
            )
        opts = phold_hosts[0].app_options
        app = PholdApp(
            H,
            msgload=int(opts.get("msgload", 1)),
            size_bytes=int(opts.get("size", 64)),
            start_time=units.parse_time_ns(opts.get("start_time", 1)),
            runtime=units.parse_time_ns(opts.get("runtime", 5)),
            hot_frac=float(opts.get("hot_frac", 0.0)),
            hot_share=float(opts.get("hot_share", 0.0)),
            local_span=int(opts.get("local_span", 0)),
        )
        handlers.update(app.handlers())
        subs[PholdApp.SUB] = app.init_sub()
        initial_events.extend(app.initial_events())
        bulk_kinds = app.bulk_kinds()
        payload_words = PholdApp.PAYLOAD_WORDS
        # The matrix fast path's draw-offset arithmetic assumes every
        # destination is reachable (two draws per send, see
        # PholdApp.handle_msg_matrix); register it only when that holds.
        if not np.any(np.asarray(baked.latency_vv) == simtime.NEVER):
            matrix_handlers = app.matrix_handlers()

    stack_apps = app_names & {"udp_flood", "udp_echo", "tcp_bulk"}
    if stack_apps:
        if len(stack_apps) > 1 or "phold" in app_names:
            raise BuildError("only one app model per simulation for now")
        name = next(iter(stack_apps))
        roles = {}
        client_opts = None
        for i, h in enumerate(cfg.hosts):
            if h.app_model != name:
                raise BuildError(f"{name} requires every host to run it")
            roles[i] = str(h.app_options.get("role", "client"))
            if roles[i] == "client":
                o = {k: v for k, v in h.app_options.items() if k != "role"}
                if client_opts is None:
                    client_opts = o
                elif client_opts != o:
                    raise BuildError(
                        f"{name} client app_options must be identical"
                    )
        servers = [i for i, r in roles.items() if r == "server"]
        if not servers:
            raise BuildError(f"{name} needs at least one role: server host")
        client_opts = client_opts or {}

        # per-host bandwidths: host override, else attachment vertex's
        bw_up = np.zeros(H, dtype=np.int64)
        bw_down = np.zeros(H, dtype=np.int64)
        for i, h in enumerate(cfg.hosts):
            v = baked.host_vertex[i]
            bw_up[i] = h.bandwidth_up or baked.vertex_bw_up_bits[v]
            bw_down[i] = h.bandwidth_down or baked.vertex_bw_down_bits[v]
            if bw_up[i] <= 0 or bw_down[i] <= 0:
                raise BuildError(
                    f"host {h.name}: no bandwidth configured (host or graph "
                    f"vertex must set bandwidth_up/down)"
                )
        if cfg.experimental.packet_trails:
            from shadow_tpu.net import packet as pkt_mod

            payload_words = pkt_mod.TRAILED_PAYLOAD_WORDS
        stack = NetStack(
            H,
            jnp.asarray(bw_up),
            jnp.asarray(bw_down),
            sockets_per_host=cfg.experimental.sockets_per_host,
            router_queue_slots=cfg.experimental.router_queue_slots,
            router_variant=cfg.experimental.router_queue_variant,
            with_tcp=(name == "tcp_bulk"),
            discipline=_qdisc_discipline(cfg, H),
            payload_words=payload_words,
            deep_hosts=sorted(deep),
            deep_queue_slots=next(iter(deep.values()), 0),
        )
        interval = units.parse_time_ns(
            client_opts.get("interval", "100 ms"), default_unit="ms"
        )
        start = units.parse_time_ns(client_opts.get("start_time", 1))
        spread = units.parse_time_ns(client_opts.get("start_spread", 0))
        if spread and name != "udp_flood":
            raise BuildError(f"{name} has no start_spread option")
        stop_send = (
            units.parse_time_ns(client_opts["runtime"]) + start
            if "runtime" in client_opts
            else None
        )
        if name == "udp_flood":
            local_span = int(client_opts.get("local_span", 0))
            app = UdpFloodApp(
                H, servers, interval,
                size_bytes=int(client_opts.get("size", 1024)),
                start_time=start, stop_sending=stop_send,
                local_span=local_span,
                start_offsets=_start_offsets(
                    [i for i, r in roles.items() if r == "client"],
                    locality_targets(H, servers, local_span), H, spread,
                    cfg.general.seed),
            )
        elif name == "tcp_bulk":
            app = TcpBulkApp(
                H, servers,
                total_bytes=units.parse_bytes(client_opts.get("total", "1 MiB")),
                start_time=start,
            )
        else:
            if len(servers) != 1:
                raise BuildError("udp_echo supports exactly one server host")
            app = UdpEchoApp(
                H, servers[0], interval,
                size_bytes=int(client_opts.get("size", 512)),
                start_time=start, stop_sending=stop_send,
            )
        app.attach(stack)
        if hasattr(app, "on_receive"):
            stack.on_receive(app.on_receive)
        handlers.update(stack.handlers())
        handlers.update(app.handlers())
        subs.update(stack.init_subs())
        subs[app.SUB] = app.init_sub()
        initial_events.extend(app.initial_events())
        # gated arrival batching: a host consumes a whole burst of
        # same-window arrivals in one micro-step when provably safe
        bulk_kinds = stack.bulk_kinds()
        bulk_gate = stack.bulk_gate if bulk_kinds else None
        bulk_self_excluded = bulk_kinds is not None
        if cfg.experimental.packet_trails:
            from shadow_tpu.net import pds as pds_mod

            subs[pds_mod.SUB] = pds_mod.init(H)

    unknown = app_names - {"phold", "udp_flood", "udp_echo", "tcp_bulk"}
    if unknown:
        raise BuildError(f"unknown app model(s): {sorted(unknown)}")

    cpu_cost = np.array([h.cpu_ns_per_event for h in cfg.hosts], dtype=np.int64)
    sim_cls = Simulation
    sim_kw = {}
    if cfg.experimental.num_shards > 1:
        from shadow_tpu.parallel.islands import IslandSimulation

        sim_cls = IslandSimulation
        balancer_policy = None
        if cfg.experimental.balancer:
            from shadow_tpu.parallel.balancer import BalancerPolicy

            balancer_policy = BalancerPolicy(
                hot_ratio=cfg.experimental.balance_hot_ratio,
                streak=cfg.experimental.balance_streak,
                cooldown=cfg.experimental.balance_cooldown,
                max_moves=cfg.experimental.balance_max_moves,
            )
        sim_kw = dict(
            num_shards=cfg.experimental.num_shards,
            exchange_slots=cfg.experimental.exchange_slots,
            mode=cfg.experimental.island_mode,
            rebalance=cfg.experimental.rebalance,
            balancer=cfg.experimental.balancer,
            balancer_policy=balancer_policy,
            async_sync=cfg.experimental.async_islands,
            async_spread=cfg.experimental.async_spread,
            exchange=cfg.experimental.mesh_exchange,
            placement=cfg.experimental.placement,
            exclude_chips=cfg.experimental.exclude_chips,
            # matrix-capable sims pin the matrix path: under vmap a
            # lax.cond with a batched predicate executes BOTH branches
            force_path="matrix" if matrix_handlers else None,
        )
    sim = sim_cls(
        **sim_kw,
        num_hosts=H,
        handlers=handlers,
        params=params,
        host_vertex=baked.host_vertex,
        cpu_ns_per_event=cpu_cost if cpu_cost.any() else None,
        seed=cfg.general.seed,
        stop_time=cfg.general.stop_time,
        runahead=runahead,
        event_capacity=cfg.experimental.event_capacity,
        K=cfg.experimental.events_per_host_per_window,
        B=cfg.experimental.inbox_slots,
        O=cfg.experimental.outbox_slots,
        subs=subs,
        initial_events=initial_events,
        bulk_kinds=bulk_kinds,
        matrix_handlers=matrix_handlers,
        payload_words=payload_words,
        bulk_gate=bulk_gate,
        bulk_self_excluded=bulk_self_excluded,
        obs_counters=cfg.experimental.obs_counters,
        pool_gears=cfg.experimental.pool_gears,
        audit_digest=cfg.experimental.audit_digest,
        flight_capacity=cfg.experimental.flight_recorder,
        pipelined_dispatch=cfg.experimental.pipelined_dispatch,
        host_workers=cfg.experimental.host_workers,
    )
    # attach build artifacts for inspection/observability
    sim.config = cfg
    sim.topology = topo
    sim.dns = dns
    sim.baked = baked
    return sim
