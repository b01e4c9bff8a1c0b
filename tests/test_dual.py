"""DUAL flood-topology tests (ref openr/kvstore/tests/DualTest.cpp).

Unit level: Dual state machines wired through an in-process message
pump — tree formation, reconvergence through the diffusing (ACTIVE)
path, unreachable-root fallback. Integration level: real KvStore
instances over TCP with flood optimization on — publications reach
every node over the spanning tree, and the flood fan-out is measurably
tree-sized instead of mesh-sized.
"""

import asyncio

from openr_tpu.config import KvstoreConfig
from openr_tpu.kvstore.dual import INF, Dual, DualState
from openr_tpu.kvstore.wrapper import KvStoreWrapper, wait_until
from openr_tpu.runtime.counters import counters
from tests.conftest import run_async


class Net:
    """Synchronous delivery fabric for Dual unit tests."""

    def __init__(self):
        self.nodes: dict[str, Dual] = {}
        self.queue: list = []

    def add(self, name: str, is_root: bool = False) -> Dual:
        d = Dual(
            name,
            send=lambda peer, msg, me=name: self.queue.append(
                (me, peer, msg)
            ),
            is_root=is_root,
        )
        self.nodes[name] = d
        return d

    def connect(self, a: str, b: str) -> None:
        self.nodes[a].peer_up(b)
        self.nodes[b].peer_up(a)
        self.pump()

    def disconnect(self, a: str, b: str) -> None:
        self.nodes[a].peer_down(b)
        self.nodes[b].peer_down(a)
        self.pump()

    def pump(self, limit: int = 10_000) -> None:
        n = 0
        while self.queue:
            src, dst, msg = self.queue.pop(0)
            node = self.nodes.get(dst)
            if node is not None and src in node.peers:
                node.handle_message(src, msg)
            n += 1
            assert n < limit, "message storm: DUAL not converging"


def tree_of(net: Net, root: str) -> dict:
    return {
        name: d.roots[root].successor
        for name, d in net.nodes.items()
        if root in d.roots
    }


class TestDualUnit:
    def test_line_tree_formation(self):
        net = Net()
        net.add("a", is_root=True)
        net.add("b")
        net.add("c")
        net.connect("a", "b")
        net.connect("b", "c")
        assert tree_of(net, "a") == {"a": None, "b": "a", "c": "b"}
        assert net.nodes["a"].roots["a"].children == {"b"}
        assert net.nodes["b"].roots["a"].children == {"c"}
        assert net.nodes["a"].flood_peers() == {"b"}
        assert net.nodes["b"].flood_peers() == {"a", "c"}
        assert net.nodes["c"].flood_peers() == {"b"}
        for d in net.nodes.values():
            assert d.roots["a"].state is DualState.PASSIVE

    def test_the_end_that_comes_up_second_still_hears_of_the_root(self):
        """A session's two ends come up one after the other (each after
        its own full sync): what the first says is dropped by an end that
        does not track it yet, and the second, knowing no root, has
        nothing to say — so it asks. Every peer of `c` comes up first."""
        net = Net()
        net.add("a", is_root=True)
        net.add("b")
        net.add("c")
        net.connect("a", "b")
        for first in ("a", "b"):
            net.nodes[first].peer_up("c")
            net.pump()
        assert "a" not in net.nodes["c"].roots
        for first in ("a", "b"):
            net.nodes["c"].peer_up(first)
            net.pump()
        assert tree_of(net, "a") == {"a": None, "b": "a", "c": "a"}
        assert net.nodes["a"].roots["a"].children == {"b", "c"}
        assert net.nodes["c"].flood_peers() == {"a"}

    def test_diamond_reconverges_through_active(self):
        #   a (root)
        #  / \
        # b   c      d's successor is b (name tie-break);
        #  \ /       killing b forces d through the diffusing path to c
        #   d
        net = Net()
        net.add("a", is_root=True)
        for n in ("b", "c", "d"):
            net.add(n)
        net.connect("a", "b")
        net.connect("a", "c")
        net.connect("b", "d")
        net.connect("c", "d")
        assert net.nodes["d"].roots["a"].successor == "b"
        net.disconnect("b", "d")
        rs = net.nodes["d"].roots["a"]
        assert rs.state is DualState.PASSIVE
        assert rs.successor == "c"
        assert rs.dist == 2
        assert "d" in net.nodes["c"].roots["a"].children
        assert "d" not in net.nodes["b"].roots["a"].children

    def test_root_loss_falls_back_to_full_mesh(self):
        net = Net()
        net.add("a", is_root=True)
        net.add("b")
        net.add("c")
        net.connect("a", "b")
        net.connect("b", "c")
        net.disconnect("a", "b")
        assert net.nodes["b"].roots["a"].dist >= INF
        assert net.nodes["b"].flood_peers() is None
        assert net.nodes["c"].flood_peers() is None

    def test_two_roots_prefers_lowest_id(self):
        net = Net()
        net.add("r1", is_root=True)
        net.add("r2", is_root=True)
        net.add("x")
        net.connect("r1", "x")
        net.connect("r2", "x")
        assert net.nodes["x"].current_root() == "r1"
        # losing the preferred root falls over to the next
        net.disconnect("r1", "x")
        assert net.nodes["x"].current_root() == "r2"

    def test_partition_rejoin(self):
        net = Net()
        net.add("a", is_root=True)
        net.add("b")
        net.add("c")
        net.connect("a", "b")
        net.connect("b", "c")
        net.disconnect("b", "c")
        assert net.nodes["c"].flood_peers() is None
        net.connect("b", "c")
        assert net.nodes["c"].flood_peers() == {"b"}
        assert net.nodes["b"].roots["a"].children == {"c"}


async def _start(n, root_idx=0):
    wrappers = []
    for i in range(n):
        cfg = KvstoreConfig(
            enable_flood_optimization=True,
            is_flood_root=(i == root_idx),
        )
        wrappers.append(KvStoreWrapper(f"store{i}", config=cfg))
    for w in wrappers:
        await w.start()
    return wrappers


class TestDualKvStoreIntegration:
    @run_async
    async def test_spt_flooding_reaches_all_nodes(self):
        """4-node full mesh, one flood root: the DUAL tree spans every
        node, a publication reaches everyone, and each hop's fan-out is
        tree-sized (SPT flood counter grows, and every flood lands)."""
        wrappers = await _start(4)
        try:
            for i, a in enumerate(wrappers):
                for b in wrappers[i + 1:]:
                    a.add_peer(b)
                    b.add_peer(a)
            await wait_until(
                lambda: all(
                    w.store.areas["0"].dual.flood_peers() is not None
                    for w in wrappers
                ),
                timeout_s=15,
            )
            # tree sanity: every non-root has a parent; parent/child
            # relations are mutual
            for w in wrappers:
                dual = w.store.areas["0"].dual
                rs = dual.roots["store0"]
                if w.node_name != "store0":
                    assert rs.successor is not None
            base = counters.get_counters("kvstore.store1.flood_spt").get(
                "kvstore.store1.flood_spt", 0
            )
            wrappers[1].set_key("k-dual", b"v", version=1)
            for w in wrappers:
                await wait_until(
                    lambda w=w: w.get_key("k-dual") is not None, timeout_s=15
                )
            after = counters.get_counters("kvstore.store1.flood_spt").get(
                "kvstore.store1.flood_spt", 0
            )
            assert after > base  # the originator flooded over the tree
        finally:
            for w in wrappers:
                await w.stop()

    @run_async
    async def test_tree_member_loss_heals(self):
        """Killing a mid-tree node: flooding still reaches the rest
        (fallback + reconvergence + periodic sync)."""
        cfg_fast = [
            KvstoreConfig(
                enable_flood_optimization=True,
                is_flood_root=(i == 0),
                sync_interval_s=0.5,
            )
            for i in range(3)
        ]
        wrappers = [
            KvStoreWrapper(f"store{i}", config=cfg_fast[i]) for i in range(3)
        ]
        for w in wrappers:
            await w.start()
        try:
            # line: 0 - 1 - 2
            wrappers[0].add_peer(wrappers[1])
            wrappers[1].add_peer(wrappers[0])
            wrappers[1].add_peer(wrappers[2])
            wrappers[2].add_peer(wrappers[1])
            await wait_until(
                lambda: all(
                    w.store.areas["0"].dual.flood_peers() is not None
                    for w in wrappers
                ),
                timeout_s=15,
            )
            # drop the 1-2 edge: 2 loses the tree, falls back, and a key
            # set at 0 still reaches 2 once re-peered
            wrappers[1].del_peer("store2")
            wrappers[2].del_peer("store1")
            await wait_until(
                lambda: wrappers[2].store.areas["0"].dual.flood_peers()
                is None,
                timeout_s=15,
            )
            wrappers[1].add_peer(wrappers[2])
            wrappers[2].add_peer(wrappers[1])
            wrappers[0].set_key("k-heal", b"v", version=1)
            await wait_until(
                lambda: wrappers[2].get_key("k-heal") is not None,
                timeout_s=20,
            )
        finally:
            for w in wrappers:
                await w.stop()


class TestDualSystem:
    @run_async
    async def test_full_daemon_stack_with_flood_optimization(self):
        """4-node emulated mesh with DUAL on: end-to-end route
        convergence is unaffected (the tree carries the LSDB)."""
        import itertools

        from openr_tpu.runtime.openr_wrapper import OpenrWrapper
        from openr_tpu.spark import MockIoMesh

        names = [f"node-{i}" for i in range(4)]
        mesh = MockIoMesh()
        kv_ports = {}
        nodes = {
            n: OpenrWrapper(
                n,
                mesh.provider(n),
                kv_ports,
                kvstore_config=KvstoreConfig(
                    enable_flood_optimization=True,
                    is_flood_root=(n == "node-0"),
                ),
            )
            for n in names
        }
        links = [
            (a, f"if-{a}-{b}", b, f"if-{b}-{a}")
            for a, b in itertools.combinations(names, 2)
        ]
        for a, if_a, b, if_b in links:
            mesh.connect(a, if_a, b, if_b)
        ifaces = {n: [] for n in names}
        for a, if_a, b, if_b in links:
            ifaces[a].append(if_a)
            ifaces[b].append(if_b)
        for n, w in nodes.items():
            await w.start(*ifaces[n])
        try:
            for i, n in enumerate(names):
                nodes[n].advertise_prefix(f"10.0.0.{i + 1}/32")
            await wait_until(
                lambda: all(len(nodes[n].fib_routes) == 3 for n in names),
                timeout_s=30,
            )
            # the SPT actually formed
            assert all(
                nodes[n].kvstore.areas["0"].dual.flood_peers() is not None
                for n in names
            )
        finally:
            for w in nodes.values():
                await w.stop()
