"""Daemon composition root.

Role of the reference's openr/Main.cpp:161-636: parse+validate the config,
create the replicated queues, start every module in order (watchdog ->
config-store -> monitor -> kvstore -> prefix-manager -> prefix-allocator ->
spark -> link-monitor -> decision -> fib -> ctrl server, ref Main.cpp
start order), run until a stop signal, then tear down in reverse
(ref Main.cpp:592-599).

Interface provisioning: the reference discovers system interfaces over
netlink (a kernel boundary). This daemon takes static interface
declarations — `--interface name[=bind_addr:port]` — served by
UdpIoProvider on loopback/UDP; the netlink-backed provider slots in behind
the same IoProvider seam when running with kernel access.

Run:  python -m openr_tpu.main --config node1.conf --interface if0
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import signal
import sys
import time

from openr_tpu.config import Config
from openr_tpu.prefix_manager import OriginatedPrefix
from openr_tpu.runtime.lifecycle import boot_tracer
from openr_tpu.runtime.monitor import Monitor, Watchdog
from openr_tpu.runtime.openr_wrapper import OpenrWrapper
from openr_tpu.runtime.persistent_store import PersistentStore
from openr_tpu.spark.io_provider import UdpIoProvider
from openr_tpu.types import InterfaceInfo

log = logging.getLogger("openr_tpu.main")


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="openr_tpu daemon")
    p.add_argument("--config", required=True, help="JSON config file path")
    p.add_argument(
        "--interface",
        action="append",
        default=[],
        metavar="NAME[=ADDR:PORT]",
        help="static interface declaration (repeatable)",
    )
    p.add_argument(
        "--peer",
        action="append",
        default=[],
        metavar="IFACE=ADDR:PORT",
        help="discovery peer endpoint for an interface (repeatable; "
        "loopback stand-in for multicast membership)",
    )
    p.add_argument("--ctrl-port", type=int, default=None)
    p.add_argument(
        "--fib-service",
        default=None,
        metavar="HOST:PORT",
        help="program routes through an out-of-process platform agent "
        "(openr_tpu.platform.main) instead of the in-memory service; "
        "startup blocks until the agent answers aliveSince "
        "(ref waitForFibService, openr/Main.cpp:92-120)",
    )
    p.add_argument(
        "--override_drain_state",
        choices=["drained", "undrained"],
        default=None,
    )
    p.add_argument("--verbose", action="store_true")
    return p.parse_args(argv)


def _build_policy_manager(oc):
    """Config policies dict -> PolicyManager (ref PolicyManager built
    from config areaPolicies, Main.cpp plugin args)."""
    if not oc.policies:
        return None
    from openr_tpu.policy import Policy, PolicyManager
    from openr_tpu.serde import from_plain

    return PolicyManager(
        {
            name: from_plain(p, Policy) if isinstance(p, dict) else p
            for name, p in oc.policies.items()
        }
    )


def device_identity() -> dict:
    """Which device the solver will run on, as jax reports it. Raises
    what jax raises when the backend cannot initialize."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "devices": len(devs),
    }


async def run_daemon(args) -> None:
    # boot lifecycle (runtime/lifecycle.py): t0 is taken BEFORE config
    # load and backdated into begin() once the node name is known, so
    # the span tree covers the whole cold start
    t_boot = time.monotonic()
    cfg = Config.from_file(args.config)
    oc = cfg.raw
    node_name = oc.node_name
    boot_tracer.begin(node_name, start=t_boot)
    boot_tracer.phase_mark("config_load", node=node_name, path=args.config)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    log.info("starting openr_tpu node %s", node_name)

    # -- thread-ownership sentinel (debug; env var seeds the default) -----
    if oc.runtime_config.affinity_checks:
        from openr_tpu.runtime import affinity

        affinity.set_enabled(True)
        log.info("runtime affinity checks enabled")

    # -- fault injection: arm config-declared chaos schedules -------------
    from openr_tpu.runtime.faults import registry as fault_registry

    fault_registry.configure(oc.fault_injection_config)

    # -- device plane: backend init + persistent jit cache (boot phases) --
    backend = oc.decision_config.solver_backend
    if backend != "cpu":
        with boot_tracer.phase(
            "device_init", node=node_name, backend=backend
        ) as ph:
            # a device backend that cannot initialize fails the boot:
            # booting on would put the CPU under a device label
            ph.update(device_identity())
        with boot_tracer.phase("jit_cache_attach", node=node_name) as ph:
            from openr_tpu.ops.xla_cache import enable_compilation_cache

            # same resolution the solver applies later (idempotent) —
            # attaching here folds the cache-load cost into its own
            # boot phase instead of the first solve's
            ph["cache_dir"] = enable_compilation_cache(
                oc.decision_config.xla_cache_dir or None
            )
        with boot_tracer.phase("aot_load", node=node_name) as ph:
            from openr_tpu.ops.xla_cache import configure_aot

            # deserialize previously compiled executables now, in this
            # attributed phase, so prewarm/first-solve install instead
            # of compiling (ISSUE 20)
            _aot = configure_aot(
                oc.decision_config.aot_cache_dir,
                keep=oc.decision_config.aot_cache_keep,
            )
            ph["cache_dir"] = _aot.dir or None
            if _aot.enabled:
                ph.update(_aot.preload())
            else:
                ph["skipped"] = True
    else:
        boot_tracer.phase_mark(
            "device_init", node=node_name, backend=backend, skipped=True
        )
        boot_tracer.phase_mark("jit_cache_attach", node=node_name, skipped=True)
        boot_tracer.phase_mark("aot_load", node=node_name, skipped=True)

    # prewarm happens offline (tools/prewarm.py); the phase attributes
    # what the bake paid per the perf ledger so the boot report shows
    # whether this start benefits from baked executables
    from openr_tpu.runtime.perf_ledger import configure as configure_perf_ledger

    _perf_ledger = configure_perf_ledger(oc.monitor_config.perf_ledger_dir)
    _pw = _perf_ledger.prewarm_summary()
    boot_tracer.phase_mark(
        "prewarm",
        node=node_name,
        baked_ms=_pw["baked_ms"] or None,
        namespaces=len(_pw["namespaces"]) or None,
    )

    # -- persistent store (ref config-store start, Main.cpp:340) ----------
    store = (
        PersistentStore(oc.persistent_store_path)
        if oc.persistent_store_path
        else None
    )

    # -- spark I/O: UDP provider with static interfaces -------------------
    io = UdpIoProvider(oc.spark_config.neighbor_discovery_port)
    iface_specs = []
    for spec in args.interface:
        name, _, addr = spec.partition("=")
        bind_addr, bind_port = "127.0.0.1", None
        if addr:
            bind_addr, _, port_s = addr.rpartition(":")
            bind_port = int(port_s)
        iface_specs.append((name, bind_addr, bind_port))

    # -- FibService: out-of-process platform agent, if configured ---------
    fib_service = None
    if args.fib_service:
        from openr_tpu.platform import RemoteFibService, wait_for_fib_service

        host, _, port_s = args.fib_service.rpartition(":")
        fib_service = RemoteFibService(host or "127.0.0.1", int(port_s))
        log.info("waiting for FibService at %s ...", args.fib_service)
        await wait_for_fib_service(fib_service)
        log.info("FibService is up")

    kv_ports: dict[str, int] = {}
    originated = [
        OriginatedPrefix(**op) if isinstance(op, dict) else op
        for op in oc.originated_prefixes
    ]
    node = OpenrWrapper(
        node_name,
        io,
        kv_ports,
        areas=[a.area_id for a in oc.areas],
        spark_config=oc.spark_config,
        kvstore_config=oc.kvstore_config,
        decision_config=oc.decision_config,
        fib_config=oc.fib_config,
        fib_service=fib_service,
        lm_config=oc.link_monitor_config,
        originated_prefixes=originated,
        solver_backend=oc.decision_config.solver_backend,
        enable_ctrl=True,
        ctrl_port=(
            args.ctrl_port if args.ctrl_port is not None else oc.openr_ctrl_port
        ),
        persistent_store=store,
        # neighbors publish their kvstore port in the spark handshake;
        # the ADDRESS is kernel truth — the UDP source the handshake
        # arrived from (falls back to loopback for same-host emulation)
        kvstore_port_of=lambda ev: (
            ev.neighbor_addr_v4 or ev.neighbor_addr_v6 or "127.0.0.1",
            ev.kvstore_port,
        ),
        node_label=oc.segment_routing_config.node_segment_label,
        policy_manager=_build_policy_manager(oc),
        origination_policy=oc.origination_policy,
        plugins=oc.plugins,
        running_config=cfg,
        # Spark area negotiation from the per-area regex matchers
        # (ref Config.h:34-110 + Spark area resolution)
        resolve_area=cfg.match_neighbor_area,
        # per-destination-area import policies (ref areaToPolicy_)
        area_policies={
            a.area_id: a.import_policy_name
            for a in oc.areas
            if a.import_policy_name
        },
        # peers connect to the kvstore from OTHER hosts/namespaces —
        # bind the configured listen address. Fail closed: without
        # peer-plane TLS the default stays loopback (an any-address
        # plaintext peer plane invites LSDB injection); an explicit
        # kvstore_config.listen_addr overrides consciously.
        kv_listen_addr=(
            oc.kvstore_config.listen_addr
            or (
                oc.listen_addr
                if oc.kvstore_config.enable_secure_peers
                else "127.0.0.1"
            )
        ),
    )
    def _is_loopback(addr: str) -> bool:
        if addr == "localhost":
            return True
        try:
            import ipaddress as _ip

            return _ip.ip_address(addr).is_loopback
        except ValueError:
            return False

    if (
        oc.kvstore_config.listen_addr
        and not _is_loopback(oc.kvstore_config.listen_addr)
        and not oc.kvstore_config.enable_secure_peers
    ):
        log.warning(
            "kvstore peer plane bound to %s WITHOUT TLS — any on-path "
            "host can inject LSDB state (set enable_secure_peers)",
            oc.kvstore_config.listen_addr,
        )

    # -- bring up interfaces ----------------------------------------------
    iface_infos = []
    for name, bind_addr, bind_port in iface_specs:
        addr = await io.add_interface(name, bind_addr, bind_port)
        log.info("interface %s bound at %s:%d", name, *addr)
        iface_infos.append(InterfaceInfo(if_name=name, is_up=True))
    # kernel interface discovery: rtnetlink dump + live events feed
    # LinkMonitor directly (ref LinkMonitor's netlink subscription,
    # NetlinkProtocolSocket.h:29-31); static --interface stays as the
    # loopback/emulation seam
    iface_mon = None
    if oc.link_monitor_config.enable_netlink_interfaces:
        from openr_tpu.platform.iface_monitor import NetlinkInterfaceMonitor

        iface_mon = NetlinkInterfaceMonitor(
            on_interface=lambda info: node.link_monitor.update_interface(
                info
            ),
            include_regexes=oc.link_monitor_config.include_interface_regexes,
            exclude_regexes=oc.link_monitor_config.exclude_interface_regexes,
        )
    peers_by_iface: dict[str, list[tuple[str, int]]] = {}
    for spec in args.peer:
        iface, _, endpoint = spec.partition("=")
        host, _, port_s = endpoint.rpartition(":")
        peers_by_iface.setdefault(iface, []).append((host, int(port_s)))
    for iface, peers in peers_by_iface.items():
        io.set_peers(iface, peers)

    # -- watchdog + monitor (ref Main.cpp:274-281, :352) ------------------
    watchdog = (
        Watchdog(node_name, oc.watchdog_config) if oc.enable_watchdog else None
    )
    monitor = Monitor(
        node_name,
        oc.monitor_config,
        node.log_sample_queue.get_reader("monitor"),
    )
    node.set_monitor(monitor)  # also wires kvstore for fleet health
    if watchdog is not None:
        monitor.attach_fleet_sources(watchdog=watchdog)

    # -- start (ref start order Main.cpp) ---------------------------------
    if watchdog is not None:
        await watchdog.start()
    await monitor.start()
    await node.start(*[name for name, _, _ in iface_specs])
    for info in iface_infos:
        node.link_monitor.update_interface(info)
    if iface_mon is not None:
        await iface_mon.start()
        log.info(
            "netlink interface discovery: %s",
            ", ".join(sorted(iface_mon.interfaces())) or "(none match)",
        )

    # -- prefix allocator (ref Main.cpp prefix-allocator start) -----------
    allocator = None
    pac = oc.prefix_allocation_config
    if pac is not None:
        from openr_tpu.allocators import (
            PrefixAllocator,
            StaticPrefixAllocator,
        )

        alloc_reader = node.kvstore_updates_queue.get_reader(
            "prefix-allocator"
        )
        common = dict(
            loopback_iface=pac.loopback_interface,
            set_loopback_address=pac.set_loopback_address,
        )
        if pac.prefix_allocation_mode == "STATIC":
            allocator = StaticPrefixAllocator(
                node_name, node.kvstore, alloc_reader,
                node.prefix_updates_queue, **common,
            )
        else:
            allocator = PrefixAllocator(
                node_name, node.kvstore, alloc_reader,
                node.prefix_updates_queue,
                seed_prefix=pac.seed_prefix,
                allocate_prefix_len=pac.allocate_prefix_len,
                **common,
            )
        await allocator.start()
        log.info(
            "prefix allocator started (%s mode)", pac.prefix_allocation_mode
        )
    if args.override_drain_state is not None:
        await node.link_monitor.set_node_overload(
            args.override_drain_state == "drained"
        )
    elif oc.assume_drained:
        await node.link_monitor.set_node_overload(True)

    if watchdog is not None:
        for actor in (
            node.kvstore,
            node.spark,
            node.link_monitor,
            node.decision,
            node.fib,
            node.prefix_manager,
            monitor,
        ):
            watchdog.watch_actor(actor)
        for q in (
            node.kvstore_updates_queue,
            node.route_updates_queue,
            node.fib_updates_queue,
            node.neighbor_updates_queue,
        ):
            watchdog.watch_queue(q)

    log.info(
        "node %s up: ctrl port %d, kvstore port %d",
        node_name,
        node.ctrl.port,
        node.kvstore.port,
    )
    print(f"READY ctrl={node.ctrl.port} kvstore={node.kvstore.port}", flush=True)

    # -- run until signal (ref mainEvb loop + EventBaseStopSignalHandler) -
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()

    # graceful restart announcement, then reverse teardown
    log.info("stopping node %s", node_name)
    if allocator is not None:
        await allocator.stop()
    if iface_mon is not None:
        iface_mon.close()
    await node.spark.send_restarting_hellos()
    await node.stop()
    await monitor.stop()
    if watchdog is not None:
        await watchdog.stop()
    if store is not None:
        store.close()
    io.close()
    log.info("node %s stopped", node_name)


def main(argv=None) -> None:
    args = parse_args(argv)
    try:
        asyncio.run(run_daemon(args))
    except KeyboardInterrupt:
        sys.exit(130)


if __name__ == "__main__":
    main()
