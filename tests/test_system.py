"""N-node single-process system tests — the end-to-end slice.

Role of the reference's openr/tests/OpenrSystemTest.cpp: multiple complete
node stacks (OpenrWrapper) share a MockIoMesh, forming an emulated network
in one process with sped-up timers; tests assert end-to-end route
convergence (ref RingTopologyMultiPathTest :243; 4-node mesh = BASELINE
config #1's example_openr.conf topology).
"""

import asyncio
import itertools

from openr_tpu.kvstore.wrapper import wait_until
from openr_tpu.runtime.openr_wrapper import OpenrWrapper
from openr_tpu.spark import MockIoMesh
from tests.conftest import run_async

CONVERGENCE_S = 20.0  # generous bound; typ. < 3s (ref kMaxOpenrSyncTime)


async def start_mesh(names, links):
    """links: list of (node_a, if_a, node_b, if_b)."""
    mesh = MockIoMesh()
    kv_ports: dict[str, int] = {}
    nodes = {n: OpenrWrapper(n, mesh.provider(n), kv_ports) for n in names}
    for a, if_a, b, if_b in links:
        mesh.connect(a, if_a, b, if_b)
    ifaces = {n: [] for n in names}
    for a, if_a, b, if_b in links:
        ifaces[a].append(if_a)
        ifaces[b].append(if_b)
    for n, w in nodes.items():
        await w.start(*ifaces[n])
    return mesh, nodes


async def stop_all(nodes):
    for w in nodes.values():
        await w.stop()


def loopback(i: int) -> str:
    return f"10.0.0.{i + 1}/32"


class TestFourNodeMesh:
    """BASELINE config #1: 4-node full mesh, every node originates its
    loopback; every node must program routes to the other three."""

    @run_async
    async def test_full_mesh_converges(self):
        names = [f"node-{i}" for i in range(4)]
        links = [
            (a, f"if-{a}-{b}", b, f"if-{b}-{a}")
            for a, b in itertools.combinations(names, 2)
        ]
        mesh, nodes = await start_mesh(names, links)
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))

            def converged():
                # converged = every loopback reached over its direct
                # single-hop link; a table that is complete while one
                # adjacency is still forming (two-hop ECMP) is not yet it
                for i, n in enumerate(names):
                    fib = nodes[n].fib_routes
                    if set(fib) != {loopback(j) for j in range(4) if j != i}:
                        return False
                    for j, m in enumerate(names):
                        if i != j and {
                            nh.neighbor_node_name
                            for nh in fib[loopback(j)].nexthops
                        } != {m}:
                            return False
                return True

            await wait_until(converged, timeout_s=CONVERGENCE_S)
        finally:
            await stop_all(nodes)

    @run_async
    async def test_node_failure_reroutes(self):
        """Ring 0-1-2-3-0: kill the 0-1 link; 0 must reach 1's loopback
        the long way (via 3)."""
        names = [f"node-{i}" for i in range(4)]
        links = [
            ("node-0", "if-01", "node-1", "if-10"),
            ("node-1", "if-12", "node-2", "if-21"),
            ("node-2", "if-23", "node-3", "if-32"),
            ("node-3", "if-30", "node-0", "if-03"),
        ]
        mesh, nodes = await start_mesh(names, links)
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(loopback(i))
            await wait_until(
                lambda: loopback(1) in nodes["node-0"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
            entry = nodes["node-0"].fib_routes[loopback(1)]
            assert {nh.neighbor_node_name for nh in entry.nexthops} == {
                "node-1"
            }
            # cut the direct link (both the wire and the hellos)
            mesh.disconnect("node-0", "if-01", "node-1", "if-10")

            def rerouted():
                entry = nodes["node-0"].fib_routes.get(loopback(1))
                if entry is None:
                    return False
                return {nh.neighbor_node_name for nh in entry.nexthops} == {
                    "node-3"
                }

            await wait_until(rerouted, timeout_s=CONVERGENCE_S)
        finally:
            await stop_all(nodes)

    @run_async
    async def test_prefix_withdrawal_propagates(self):
        names = ["node-0", "node-1", "node-2"]
        links = [
            ("node-0", "if-01", "node-1", "if-10"),
            ("node-1", "if-12", "node-2", "if-21"),
        ]
        mesh, nodes = await start_mesh(names, links)
        try:
            nodes["node-2"].advertise_prefix("10.9.0.0/24")
            await wait_until(
                lambda: "10.9.0.0/24" in nodes["node-0"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
            # multihop: node-0 reaches it via node-1
            entry = nodes["node-0"].fib_routes["10.9.0.0/24"]
            assert {nh.neighbor_node_name for nh in entry.nexthops} == {
                "node-1"
            }
            nodes["node-2"].withdraw_prefix("10.9.0.0/24")
            await wait_until(
                lambda: "10.9.0.0/24" not in nodes["node-0"].fib_routes,
                timeout_s=CONVERGENCE_S,
            )
        finally:
            await stop_all(nodes)


class TestEcmpSystem:
    @run_async
    async def test_diamond_ecmp_end_to_end(self):
        """0-1-3 / 0-2-3 diamond: 0's route to 3's loopback carries both
        next hops all the way into the programmed FIB."""
        names = [f"node-{i}" for i in range(4)]
        links = [
            ("node-0", "if-01", "node-1", "if-10"),
            ("node-0", "if-02", "node-2", "if-20"),
            ("node-1", "if-13", "node-3", "if-31"),
            ("node-2", "if-23", "node-3", "if-32"),
        ]
        mesh, nodes = await start_mesh(names, links)
        try:
            nodes["node-3"].advertise_prefix(loopback(3))

            def has_ecmp():
                entry = nodes["node-0"].fib_routes.get(loopback(3))
                if entry is None:
                    return False
                return {nh.neighbor_node_name for nh in entry.nexthops} == {
                    "node-1",
                    "node-2",
                }

            await wait_until(has_ecmp, timeout_s=CONVERGENCE_S)
        finally:
            await stop_all(nodes)


class TestMultiAreaRedistribution:
    """left --(area1)-- center --(area2)-- right (ref lab 201_areas):
    a prefix originated in area1 must cross the area boundary via
    center's RIB redistribution and land in right's FIB, with
    provenance on the area stack."""

    @run_async
    async def test_prefix_crosses_areas_through_center(self):
        mesh = MockIoMesh()
        kv_ports: dict[str, int] = {}

        def center_area(node, iface):
            return "area1" if iface == "if-c-l" else "area2"

        left = OpenrWrapper(
            "left", mesh.provider("left"), kv_ports, areas=["area1"]
        )
        center = OpenrWrapper(
            "center", mesh.provider("center"), kv_ports,
            areas=["area1", "area2"], resolve_area=center_area,
        )
        right = OpenrWrapper(
            "right", mesh.provider("right"), kv_ports, areas=["area2"]
        )
        mesh.connect("left", "if-l-c", "center", "if-c-l")
        mesh.connect("center", "if-c-r", "right", "if-r-c")
        await left.start("if-l-c")
        await center.start("if-c-l", "if-c-r")
        await right.start("if-r-c")
        try:
            left.advertise_prefix("10.31.0.0/24", dest_areas=("area1",))
            right.advertise_prefix("10.32.0.0/24", dest_areas=("area2",))

            # center programs both originals
            await wait_until(
                lambda: {"10.31.0.0/24", "10.32.0.0/24"}
                <= set(center.fib_routes),
                timeout_s=CONVERGENCE_S,
            )
            # the redistributed copies cross the boundary into the
            # opposite side's kernel-facing FIB
            await wait_until(
                lambda: "10.31.0.0/24" in right.fib_routes,
                timeout_s=CONVERGENCE_S,
            )
            await wait_until(
                lambda: "10.32.0.0/24" in left.fib_routes,
                timeout_s=CONVERGENCE_S,
            )
            # provenance: right sees center's RIB-type re-advertisement
            # with area1 on the stack and a bumped distance
            vals = await right.kvstore.dump_all("area2")
            from openr_tpu.serde import deserialize
            from openr_tpu.types import PrefixDatabase, PrefixType

            key = [
                k for k in vals
                if "center" in k and "10.31.0.0/24" in k
            ]
            assert key, sorted(vals)
            db = deserialize(vals[key[0]].value, PrefixDatabase)
            e = db.prefix_entries[0]
            assert e.type == PrefixType.RIB
            assert e.area_stack == ("area1",)
            assert e.metrics.distance >= 1

            # withdrawal propagates all the way back out
            left.withdraw_prefix("10.31.0.0/24")
            await wait_until(
                lambda: "10.31.0.0/24" not in right.fib_routes,
                timeout_s=CONVERGENCE_S,
            )
        finally:
            await stop_all({"l": left, "c": center, "r": right})


class TestRingPartitionSoak:
    """Randomized partition/heal soak on a 6-node ring (ref
    OpenrSystemTest RingTopology tests, scaled): every round cuts one
    ring link, asserts traffic reroutes the long way for every
    affected loopback, heals it, and asserts the short paths return.
    Exercises Spark hold-timer loss detection, KvStore re-peering +
    full sync after heal, and Decision/Fib reconvergence repeatedly in
    one process."""

    @run_async
    async def test_partition_heal_rounds(self):
        import random

        rng = random.Random(7)
        n = 6
        names = [f"node-{i}" for i in range(n)]
        links = [
            (
                names[i], f"if-{i}{(i + 1) % n}",
                names[(i + 1) % n], f"if-{(i + 1) % n}{i}",
            )
            for i in range(n)
        ]
        mesh, nodes = await start_mesh(names, links)
        try:
            for i, name in enumerate(names):
                nodes[name].advertise_prefix(loopback(i))

            def all_reach_all():
                return all(
                    loopback(j) in nodes[nm].fib_routes
                    for nm in names
                    for j in range(n)
                    if names[j] != nm
                )

            await wait_until(all_reach_all, timeout_s=CONVERGENCE_S)

            for round_no in range(3):
                i = rng.randrange(n)
                a, if_a, b, if_b = links[i]
                lb_a, lb_b = loopback(i), loopback((i + 1) % n)
                mesh.disconnect(a, if_a, b, if_b)

                # first wait for the loss to be DETECTED ON BOTH SIDES
                # (stale direct routes satisfy reachability until the
                # hold timer fires): each endpoint must reroute the
                # other's loopback away from the cut link
                def rerouted(src, dst, lb):
                    e = nodes[src].fib_routes.get(lb)
                    return e is not None and all(
                        nh.neighbor_node_name != dst for nh in e.nexthops
                    )

                await wait_until(
                    lambda: rerouted(a, b, lb_b) and rerouted(b, a, lb_a),
                    timeout_s=CONVERGENCE_S,
                )
                # the ring minus one link is a line: everyone still
                # reaches everyone, now the long way around
                await wait_until(all_reach_all, timeout_s=CONVERGENCE_S)

                mesh.connect(a, if_a, b, if_b)
                # heal: the direct adjacency must come back and win
                # again on both sides
                def direct_again(src, dst, lb):
                    e = nodes[src].fib_routes.get(lb)
                    return e is not None and {
                        nh.neighbor_node_name for nh in e.nexthops
                    } == {dst}

                await wait_until(
                    lambda: direct_again(a, b, lb_b)
                    and direct_again(b, a, lb_a),
                    timeout_s=CONVERGENCE_S,
                )
                await wait_until(all_reach_all, timeout_s=CONVERGENCE_S)
        finally:
            await stop_all(nodes)
