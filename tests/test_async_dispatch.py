"""ISSUE 5 differential + lifecycle tests: delta-resident device sync,
bounded executable caches, fused small-area dispatch, and the Decision
actor's async dispatch fiber.

The upload-volume assertions are structural (byte counts, device_put
interception), never timing-based, so they hold on the virtual-CPU JAX
platform exactly as on a real device.
"""

import asyncio

from bench import _flap
from openr_tpu.config import DecisionConfig
from openr_tpu.decision.link_state import LinkState
from openr_tpu.decision.prefix_state import PrefixState
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.faults import registry
from openr_tpu.types import (
    AdjacencyDatabase,
    PrefixDatabase,
    PrefixEntry,
)
from tests.conftest import run_async
from tests.test_decision import (
    DecisionHarness,
    adj,
    prefix_db_kv,
    two_node_mesh,
)
from tests.test_tpu_solver import assert_rib_equal


def _counter(key: str) -> float:
    return counters.get_counter(key) or 0


# -- bounded executable caches ---------------------------------------------


class TestBoundedJitCache:
    def test_bucket_eviction_evicts_all_variants_and_counts(self):
        from openr_tpu.ops.xla_cache import bounded_jit_cache

        calls = []

        @bounded_jit_cache(max_buckets=2)
        def factory(n_cap, flag):
            calls.append((n_cap, flag))
            return object()

        ev0 = _counter("xla_cache.executable_evictions")
        h0 = _counter("xla_cache.factory_hits")
        a = factory(8, False)
        assert factory(8, False) is a  # warm hit
        assert _counter("xla_cache.factory_hits") == h0 + 1
        b = factory(8, True)  # bool flag: variant WITHIN the 8-bucket
        assert factory(8, True) is b
        factory(16, False)
        # third capacity signature: the LRU bucket (8) drops whole —
        # BOTH of its flag variants release at once
        factory(32, False)
        assert _counter("xla_cache.executable_evictions") == ev0 + 2
        a2 = factory(8, False)  # evicted: the factory re-runs
        assert a2 is not a
        assert len(calls) == 5

    def test_cache_clear(self):
        from openr_tpu.ops.xla_cache import bounded_jit_cache

        @bounded_jit_cache()
        def factory(n_cap):
            return object()

        a = factory(8)
        factory.cache_clear()
        assert factory(8) is not a

    def test_solver_factories_are_bounded(self):
        # every shape-keyed jit factory swapped off lru_cache(None) must
        # expose the bounded cache's clear hook
        from openr_tpu.decision import tpu_solver as ts
        from openr_tpu.ops import ksp2, ucmp

        assert set(ts._PIPELINE_CACHES) == {"", "incr", "multichip"}
        for fn in (
            *ts._PIPELINE_CACHES.values(), ts._scatter_jit,
            ksp2._base_sssp_fn, ksp2._masked_rows_fn,
            ksp2._masked_rows_delta_fn, ucmp._ucmp_fn,
        ):
            assert hasattr(fn, "cache_clear"), fn


# -- dispatch/collect split + delta-resident sync --------------------------


class TestDispatchCollectSplit:
    def test_split_equals_oracle_under_churn(self):
        adj_dbs, pfx = topologies.grid(5, node_labels=False)
        states, ps = topologies.build_states(adj_dbs, pfx)
        me = "node-2-2"
        cpu = SpfSolver(me)
        tpu = TpuSpfSolver(me)
        for i in range(3):
            _flap(states, adj_dbs, [1 + i], i)
            pending = tpu.dispatch_route_db(me, states, ps)
            tpu_db = tpu.collect_route_db(pending)
            cpu_db = cpu.build_route_db(me, states, ps)
            assert_rib_equal(cpu_db, tpu_db, f"round {i}")
            # the split is the whole build: bytes flow into last_timing
            assert "bytes_uploaded" in tpu.last_timing

    def test_unchanged_topology_churn_uploads_only_deltas(self, monkeypatch):
        import jax

        adj_dbs, pfx = topologies.grid(5, node_labels=False)
        states, ps = topologies.build_states(adj_dbs, pfx)
        me = "node-0-0"
        tpu = TpuSpfSolver(me)
        tpu.build_route_db(me, states, ps)  # cold: full plan upload
        ad = next(iter(tpu._area_dev.values()))
        full_plan_bytes = (
            ad.plan.deltas.nbytes + ad.plan.shift_w.nbytes
            + ad.plan.res_rows.nbytes + ad.plan.res_nbr.nbytes
            + ad.plan.res_w.nbytes
        )
        plane_bytes = min(ad.plan.shift_w.nbytes, ad.plan.deltas.nbytes)

        put_sizes = []
        real_put = jax.device_put

        def counting_put(x, *a, **kw):
            put_sizes.append(int(getattr(x, "nbytes", 0)))
            return real_put(x, *a, **kw)

        monkeypatch.setattr(jax, "device_put", counting_put)
        # metric flap away from the vantage: same topology, same caps —
        # the changelog path must scatter the dirty slices, not re-put
        # any full plan plane
        _flap(states, adj_dbs, [12], 0)
        tpu.build_route_db(me, states, ps)
        assert all(s < plane_bytes for s in put_sizes), put_sizes
        uploaded = tpu.last_timing["bytes_uploaded"]
        assert 0 < uploaded < full_plan_bytes, uploaded

    def test_same_cap_rebuild_diff_scatters_instead_of_full_put(self):
        """A forced plan rebuild whose capacities are unchanged must
        reconcile the resident buffers by diff scatter: bytes_uploaded
        stays well below a full re-put of the plan arrays. (Needs a
        graph big enough that scatter index+value overhead — ~2x the
        changed words — can't exceed a full re-put.)"""
        adj_dbs, pfx = topologies.grid(10, node_labels=False)
        states, ps = topologies.build_states(adj_dbs, pfx)
        me = "node-0-0"
        area = next(iter(states))
        cpu = SpfSolver(me)
        tpu = TpuSpfSolver(me)
        tpu.build_route_db(me, states, ps)
        ad = next(iter(tpu._area_dev.values()))
        full_plan_bytes = (
            ad.plan.deltas.nbytes + ad.plan.shift_w.nbytes
            + ad.plan.res_rows.nbytes + ad.plan.res_nbr.nbytes
            + ad.plan.res_w.nbytes
        )
        # a node-overload event forces needs_rebuild through the real
        # changelog path (edgeplan folds transit drain into weights)
        victim = adj_dbs[12]
        states[area].update_adjacency_database(
            AdjacencyDatabase(
                this_node_name=victim.this_node_name,
                adjacencies=victim.adjacencies,
                is_overloaded=True,
                area=area,
            )
        )
        tpu_db = tpu.build_route_db(me, states, ps)
        assert ad.plan is not None
        uploaded = tpu.last_timing["bytes_uploaded"]
        # the overload bit legitimately re-uploads the announcer matrix
        # (its flags plane changed); the PLAN planes must reconcile by
        # diff scatter — well under half a full re-put
        p_cap, a_cap = ad.matrix.ann_node.shape
        mbuf_bytes = 6 * p_cap * a_cap * 4
        plan_uploaded = uploaded - mbuf_bytes
        assert plan_uploaded < full_plan_bytes / 2, (
            uploaded, mbuf_bytes, full_plan_bytes
        )
        assert_rib_equal(
            cpu.build_route_db(me, states, ps), tpu_db, "overload rebuild"
        )


# -- fused small-area dispatch ---------------------------------------------


def _dual_area_states():
    """hub sits in two structurally identical areas (4-node rings with 3
    announced loopbacks each) -> identical capacity classes -> the two
    per-area pipelines batch into ONE vmapped dispatch."""
    states = {}
    ps = PrefixState()
    for area, tag in (("a", "a"), ("b", "b")):
        members = ["hub"] + [f"{tag}{i}" for i in range(3)]
        ls = LinkState(area)
        adjs = {m: [] for m in members}
        n = len(members)
        for i in range(n):
            u, v = members[i], members[(i + 1) % n]
            adjs[u].append(adj(u, v))
            adjs[v].append(adj(v, u))
        for m, al in adjs.items():
            ls.update_adjacency_database(
                AdjacencyDatabase(
                    this_node_name=m, adjacencies=tuple(al), area=area
                )
            )
        states[area] = ls
        for i, m in enumerate(members[1:]):
            ps.update_prefix_database(
                PrefixDatabase(
                    this_node_name=m,
                    prefix_entries=(
                        PrefixEntry(prefix=f"fd00:{tag}::{i + 1}/128"),
                    ),
                    area=area,
                )
            )
    return states, ps


class TestFusedDispatch:
    def test_fused_parity_and_counter(self):
        states, ps = _dual_area_states()
        me = "hub"
        cpu_db = SpfSolver(me).build_route_db(me, states, ps)

        d0 = _counter("decision.device.fused_dispatches")
        fused = TpuSpfSolver(me)
        db_f = fused.build_route_db(me, states, ps)
        assert _counter("decision.device.fused_dispatches") == d0 + 1
        assert fused.last_device_stats.get("fused") == 2
        assert_rib_equal(cpu_db, db_f, "fused")

        d1 = _counter("decision.device.fused_dispatches")
        unfused = TpuSpfSolver(me, fuse_small_areas=False)
        db_u = unfused.build_route_db(me, states, ps)
        assert _counter("decision.device.fused_dispatches") == d1
        assert unfused.last_device_stats.get("fused") == 0
        assert_rib_equal(cpu_db, db_u, "unfused")

    def test_fused_churn_stays_in_parity(self):
        states, ps = _dual_area_states()
        me = "hub"
        cpu = SpfSolver(me)
        tpu = TpuSpfSolver(me)
        for metric in (5, 17, 3):
            for area, tag in (("a", "a"), ("b", "b")):
                u, v = f"{tag}0", f"{tag}1"
                ls = states[area]
                ls.update_adjacency_database(
                    AdjacencyDatabase(
                        this_node_name=u,
                        adjacencies=(adj(u, "hub"), adj(u, v, metric)),
                        area=area,
                    )
                )
            assert_rib_equal(
                cpu.build_route_db(me, states, ps),
                tpu.build_route_db(me, states, ps),
                f"metric {metric}",
            )


# -- the async dispatch fiber ----------------------------------------------


class TestAsyncDispatchFiber:
    @run_async
    async def test_async_convergence_and_solve_counter(self):
        cfg = DecisionConfig(
            debounce_min_ms=5, debounce_max_ms=20, async_dispatch=True
        )
        s0 = _counter("decision.dispatch.solves")
        async with DecisionHarness(config=cfg) as h:
            two_node_mesh(h)
            h.synced()
            update = await h.next_route_update()
            assert "10.0.0.2/32" in update.unicast_routes_to_update
            assert _counter("decision.dispatch.solves") >= s0 + 1

    @run_async
    async def test_burst_coalesces_into_fewer_solves(self):
        cfg = DecisionConfig(
            debounce_min_ms=1, debounce_max_ms=5,
            async_dispatch=True, dispatch_coalesce_ms=40,
        )
        async with DecisionHarness(config=cfg) as h:
            two_node_mesh(h)
            h.synced()
            await h.next_route_update()
            s0 = _counter("decision.dispatch.solves")
            want = {f"10.1.0.{i}/32" for i in range(5)}
            for i in range(5):
                h.publish(prefix_db_kv("2", f"10.1.0.{i}/32"))
                await asyncio.sleep(0.002)
            seen: set = set()
            while not want <= seen:
                upd = await h.next_route_update()
                seen |= set(upd.unicast_routes_to_update)
            solves = _counter("decision.dispatch.solves") - s0
            # 5 publications, strictly fewer solves: the coalesce window
            # folded the burst (typically into 1)
            assert 1 <= solves < 5, solves

    @run_async
    async def test_dispatch_fiber_crash_restarts_and_recovers(self):
        cfg = DecisionConfig(
            debounce_min_ms=5, debounce_max_ms=20, async_dispatch=True
        )
        registry.clear()
        try:
            async with DecisionHarness(config=cfg) as h:
                two_node_mesh(h)
                h.synced()
                await h.next_route_update()
                r0 = _counter("runtime.supervisor.restarts")
                registry.arm("solver.dispatch", every_nth=1, max_fires=1)
                h.publish(prefix_db_kv("2", "10.9.9.9/32"))
                # the fault kills the dispatch fiber holding the pending
                # snapshot; the supervisor restarts it and
                # on_fiber_restart forces a full rebuild, so the prefix
                # still converges
                seen: set = set()
                while "10.9.9.9/32" not in seen:
                    upd = await h.next_route_update(timeout=10)
                    seen |= set(upd.unicast_routes_to_update)
                assert _counter("runtime.supervisor.restarts") >= r0 + 1
        finally:
            registry.clear()

    @run_async
    async def test_async_off_keeps_inline_path(self):
        # config-gated: with the default async_dispatch=False no dispatch
        # fiber exists and rebuilds run inline exactly as before
        s0 = _counter("decision.dispatch.solves")
        async with DecisionHarness() as h:
            two_node_mesh(h)
            h.synced()
            update = await h.next_route_update()
            assert "10.0.0.2/32" in update.unicast_routes_to_update
            assert h.decision._solve_q is None
        assert _counter("decision.dispatch.solves") == s0
