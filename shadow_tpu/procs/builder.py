"""Config → ProcessDriver: the managed-process plane wired to the topology.

Plays the reference's controller host-registration sequence
(src/main/core/controller.c:227-336: for each configured host, register with
DNS, attach to a topology vertex, then add its processes) for simulations
whose hosts run real binaries. Path latency/reliability lookups come from the
baked topology matrices — the same arrays the device engine uses — so both
planes see one network model (topology.c:1995,2007 analogs).
"""

from __future__ import annotations

import pathlib

from shadow_tpu.core.config import Config, load_config
from shadow_tpu.procs.driver import ProcessDriver
from shadow_tpu.routing.dns import Dns
from shadow_tpu.routing.topology import Topology


class ProcessBuildError(ValueError):
    pass


def build_process_driver(
    source, data_root: str | pathlib.Path | None = None
) -> ProcessDriver:
    """Build a ProcessDriver from a Config (or YAML path/string/dict).

    If ``data_root`` is given, per-host working directories are created under
    ``<data_root>/hosts/<hostname>/`` and process stdout/stderr are written to
    ``<exe>.<n>.stdout`` / ``.stderr`` files there, mirroring the reference's
    shadow.data layout (manager.c:352-432, process.c:468-481).
    """
    cfg = source if isinstance(source, Config) else load_config(source)
    if not cfg.hosts:
        raise ProcessBuildError("no hosts with processes configured")
    bad = [h.name for h in cfg.hosts if not h.processes]
    if bad:
        raise ProcessBuildError(f"hosts without processes: {bad}")
    deep = [h.name for h in cfg.hosts if h.router_queue_slots is not None]
    if deep:
        raise ProcessBuildError(
            f"hosts {deep}: the managed-process plane has no deep router "
            f"ring (hosts.<group>.router_queue_slots)"
        )
    hosts = cfg.hosts

    topo = Topology.from_gml(cfg.graph_gml(), cfg.network.use_shortest_path)
    dns = Dns()
    for i, h in enumerate(hosts):
        topo.attach_host(
            i,
            ip_address_hint=h.ip_address_hint,
            city_code_hint=h.city_code_hint,
            country_code_hint=h.country_code_hint,
            network_node_id=h.network_node_id,
        )
    # Path model: dense baked matrices below the threshold, lazy per-source
    # Dijkstra + row cache above it (no dense [U, U] allocation — the
    # reference's strategy for Tor-scale maps, topology.c:1144-1259). The
    # device-network bridge needs the dense arrays on device either way.
    n_used = len(set(topo._attached_vertex))
    lazy = cfg.experimental.lazy_paths
    if lazy is None:
        lazy = (
            n_used > cfg.experimental.lazy_paths_threshold
            and not cfg.experimental.use_device_network
        )
    if lazy and cfg.experimental.use_device_network:
        raise ProcessBuildError(
            "experimental.lazy_paths is incompatible with "
            "use_device_network (device lookups need baked arrays)"
        )
    baked = topo.bake_lazy() if lazy else topo.bake()

    driver = ProcessDriver(
        stop_time=cfg.general.stop_time,
        seed=cfg.general.seed,
        host_workers=cfg.experimental.host_workers,
    )
    driver.dns = dns
    driver.bootstrap_end = cfg.general.bootstrap_end_time
    driver.use_seccomp = cfg.experimental.use_seccomp
    driver.socket_send_buffer = cfg.experimental.socket_send_buffer
    driver.use_perf_timers = cfg.experimental.use_perf_timers
    driver.log_stamp = cfg.experimental.use_shim_log_stamps
    driver.cpu_ns_per_syscall = cfg.experimental.cpu_ns_per_syscall
    driver.cpu_threshold_ns = cfg.experimental.max_unapplied_cpu_latency
    # fault-tolerance plane (shadow_tpu/faults): recovery policy + armed
    # injections; corrupt_file globs resolve against the data directory
    driver.on_proc_failure = cfg.faults.on_proc_failure
    driver.ipc_timeout_retries = cfg.faults.ipc_timeout_retries
    faults = cfg.faults.load_faults()
    if faults:
        from shadow_tpu.faults import FaultInjector

        driver.fault_injector = FaultInjector(faults)
    if data_root is not None:
        driver.fault_dir = str(data_root)

    # Register hinted hosts first so a sequential allocation for an
    # unhinted host can never claim another host's requested address
    # (the sequential allocator starts at 11.0.0.1 — exactly the range
    # users pick hints from).
    for i, h in enumerate(hosts):
        if h.ip_address_hint is not None:
            dns.register(i, h.name, h.ip_address_hint)

    ip_to_vertex: dict[int, int] = {}
    for i, h in enumerate(hosts):
        ip = (
            dns.resolve_name(h.name)
            if h.ip_address_hint is not None
            else dns.register(i, h.name)
        )
        sim_host = driver.add_host(h.name, ip)
        ip_to_vertex[ip] = int(baked.host_vertex[i])

        host_dir = None
        if data_root is not None:
            host_dir = pathlib.Path(data_root) / "hosts" / h.name
            host_dir.mkdir(parents=True, exist_ok=True)
        if h.pcap_directory is not None:
            # relative paths land under the host's data dir, like the
            # reference (configuration.rs:412-415)
            p = pathlib.Path(h.pcap_directory)
            if not p.is_absolute():
                p = (host_dir or pathlib.Path(".")) / p
            sim_host.pcap_dir = str(p)

        n = 0
        for popt in h.processes:
            for _ in range(max(1, popt.quantity)):
                out_path = err_path = None
                if host_dir is not None:
                    stem = f"{pathlib.Path(popt.path).name}.{n}"
                    out_path = str(host_dir / f"{stem}.stdout")
                    err_path = str(host_dir / f"{stem}.stderr")
                driver.add_process(
                    sim_host,
                    [popt.path, *popt.args],
                    start_time=popt.start_time,
                    stop_time=popt.stop_time,
                    env=dict(popt.environment),
                    cwd=str(host_dir) if host_dir is not None else None,
                    stdout_path=out_path,
                    stderr_path=err_path,
                )
                n += 1

    if lazy:
        lat_at = baked.latency_ns
        rel_at = baked.reliability
    else:
        lat_vv = baked.latency_vv
        rel_vv = baked.reliability_vv
        lat_at = lambda sv, dv: int(lat_vv[sv, dv])  # noqa: E731
        rel_at = lambda sv, dv: float(rel_vv[sv, dv])  # noqa: E731

    # Unknown destination IPs (apps sending to addresses that are not sim
    # hosts) fall back to defaults; the packet then vanishes at delivery
    # time like any datagram with no listener.
    def latency_fn(src_ip: int, dst_ip: int) -> int:
        sv = ip_to_vertex.get(src_ip)
        dv = ip_to_vertex.get(dst_ip)
        if sv is None or dv is None:
            return driver.latency_ns
        return lat_at(sv, dv)

    def reliability_fn(src_ip: int, dst_ip: int) -> float:
        sv = ip_to_vertex.get(src_ip)
        dv = ip_to_vertex.get(dst_ip)
        if sv is None or dv is None:
            return 1.0
        return rel_at(sv, dv)

    driver.set_latency_fn(latency_fn)
    driver.set_reliability_fn(reliability_fn)

    if cfg.experimental.use_device_network:
        # the CPU↔TPU seam: UDP rides the device-stepped network
        import numpy as np

        from shadow_tpu.procs.bridge import DeviceNetBridge

        H = len(hosts)
        bw_up = np.zeros(H, dtype=np.int64)
        bw_down = np.zeros(H, dtype=np.int64)
        for i, h in enumerate(hosts):
            v = baked.host_vertex[i]
            bw_up[i] = h.bandwidth_up or baked.vertex_bw_up_bits[v] or 10**9
            bw_down[i] = (
                h.bandwidth_down or baked.vertex_bw_down_bits[v] or 10**9
            )
        driver.bridge = DeviceNetBridge(
            baked=baked,
            bw_up_bits=bw_up,
            bw_down_bits=bw_down,
            host_vertex=baked.host_vertex,
            seed=cfg.general.seed,
            stop_time=cfg.general.stop_time,
            bootstrap_end=cfg.general.bootstrap_end_time,
            sockets_per_host=cfg.experimental.sockets_per_host,
            event_capacity=cfg.experimental.event_capacity,
            K=cfg.experimental.events_per_host_per_window,
            router_queue_slots=cfg.experimental.router_queue_slots,
            router_variant=cfg.experimental.router_queue_variant,
            with_tcp=cfg.experimental.use_device_tcp,
        )

    driver.config = cfg
    driver.topology = topo
    driver.baked = baked
    return driver
