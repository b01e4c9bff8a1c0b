"""Fib actor tests against the mock FibService with failure injection
(ref openr/fib/tests/FibTest.cpp + MockNetlinkFibHandler)."""

import asyncio

import pytest

from openr_tpu.config import FibConfig
from openr_tpu.decision.rib import (
    DecisionRouteUpdate,
    NextHop,
    RibMplsEntry,
    RibUnicastEntry,
    RouteUpdateType,
)
from openr_tpu.fib import Fib, FibState, MockFibService
from openr_tpu.kvstore.wrapper import wait_until
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.types import InitializationEvent, PerfEvents
from tests.conftest import run_async


def route(prefix: str, nh: str = "fe80::1") -> RibUnicastEntry:
    return RibUnicastEntry(
        prefix=prefix, nexthops=frozenset({NextHop(address=nh)})
    )


def full_sync(*routes: RibUnicastEntry) -> DecisionRouteUpdate:
    return DecisionRouteUpdate(
        type=RouteUpdateType.FULL_SYNC,
        unicast_routes_to_update={r.prefix: r for r in routes},
        perf_events=PerfEvents(),
    )


def incremental(
    update: list[RibUnicastEntry] = (), delete: list[str] = ()
) -> DecisionRouteUpdate:
    return DecisionRouteUpdate(
        type=RouteUpdateType.INCREMENTAL,
        unicast_routes_to_update={r.prefix: r for r in update},
        unicast_routes_to_delete=list(delete),
    )


class FibHarness:
    def __init__(self, delete_delay_ms: int = 0):
        self.service = MockFibService()
        self.routes_q = ReplicateQueue("routeUpdates")
        self.fib_q = ReplicateQueue("fibRouteUpdates")
        self.fib_reader = self.fib_q.get_reader("test")
        self.fib = Fib(
            "node1",
            FibConfig(route_delete_delay_ms=delete_delay_ms),
            self.service,
            self.routes_q.get_reader(),
            self.fib_q,
            retry_initial_backoff_s=0.02,
            retry_max_backoff_s=0.1,
        )

    async def __aenter__(self):
        await self.fib.start()
        return self

    async def __aexit__(self, *exc):
        self.fib_q.close()
        await self.fib.stop()


class TestFibSync:
    @run_async
    async def test_initial_full_sync(self):
        async with FibHarness() as h:
            h.routes_q.push(full_sync(route("10.0.0.1/32"), route("10.0.0.2/32")))
            await wait_until(lambda: h.fib.synced)
            assert set(h.service.unicast) == {"10.0.0.1/32", "10.0.0.2/32"}
            assert h.service.sync_count == 1
            # FIB-ACK: programmed delta + FIB_SYNCED event published
            seen = []
            while h.fib_reader.size():
                seen.append(await h.fib_reader.get())
            assert InitializationEvent.FIB_SYNCED in seen
            programmed = [
                s for s in seen if isinstance(s, DecisionRouteUpdate)
            ]
            assert programmed and set(
                programmed[0].unicast_routes_to_update
            ) == {"10.0.0.1/32", "10.0.0.2/32"}

    @run_async
    async def test_incremental_ignored_before_full_sync(self):
        async with FibHarness() as h:
            h.routes_q.push(incremental([route("10.0.0.9/32")]))
            await asyncio.sleep(0.1)
            assert h.fib.route_state.state == FibState.AWAITING_UPDATE
            assert not h.service.unicast
            # the route is retained in desired state and programmed by the
            # eventual full sync
            h.routes_q.push(full_sync(route("10.0.0.1/32")))
            await wait_until(lambda: h.fib.synced)
            assert set(h.service.unicast) == {"10.0.0.1/32", "10.0.0.9/32"}

    @run_async
    async def test_incremental_add_and_delete(self):
        async with FibHarness() as h:
            h.routes_q.push(full_sync(route("10.0.0.1/32")))
            await wait_until(lambda: h.fib.synced)
            h.routes_q.push(
                incremental([route("10.0.0.2/32")], ["10.0.0.1/32"])
            )
            await wait_until(
                lambda: set(h.service.unicast) == {"10.0.0.2/32"}
            )

    @run_async
    async def test_mpls_routes(self):
        async with FibHarness() as h:
            upd = full_sync(route("10.0.0.1/32"))
            upd.mpls_routes_to_update = {
                100: RibMplsEntry(
                    100, frozenset({NextHop(address="fe80::2")})
                )
            }
            h.routes_q.push(upd)
            await wait_until(lambda: h.fib.synced)
            assert 100 in h.service.mpls


class TestFibRetry:
    @run_async
    async def test_sync_failure_retries(self):
        async with FibHarness() as h:
            h.service.fail_next("sync_fib", 2)
            h.routes_q.push(full_sync(route("10.0.0.1/32")))
            await wait_until(lambda: h.fib.synced, timeout_s=5)
            assert h.service.sync_count == 1  # third attempt succeeded
            assert "10.0.0.1/32" in h.service.unicast

    @run_async
    async def test_partial_failure_marks_dirty_and_retries(self):
        async with FibHarness() as h:
            h.routes_q.push(full_sync(route("10.0.0.1/32")))
            await wait_until(lambda: h.fib.synced)
            # 10.0.0.2/32 fails individually twice, then recovers
            h.service.fail_prefixes.add("10.0.0.2/32")
            h.routes_q.push(
                incremental([route("10.0.0.2/32"), route("10.0.0.3/32")])
            )
            # the healthy route lands even while the other is dirty
            await wait_until(lambda: "10.0.0.3/32" in h.service.unicast)
            assert "10.0.0.2/32" not in h.service.unicast
            assert not h.fib.synced  # dirty route outstanding
            h.service.fail_prefixes.clear()
            await wait_until(lambda: "10.0.0.2/32" in h.service.unicast)
            await wait_until(lambda: h.fib.synced)

    @run_async
    async def test_agent_restart_triggers_resync(self):
        async with FibHarness() as h:
            h.routes_q.push(full_sync(route("10.0.0.1/32")))
            await wait_until(lambda: h.fib.synced)
            assert h.service.sync_count == 1
            h.service.restart()  # wipes programmed state
            await wait_until(
                lambda: h.service.sync_count >= 2
                and "10.0.0.1/32" in h.service.unicast,
                timeout_s=5,
            )


class TestFibDelayedDelete:
    @run_async
    async def test_delete_is_delayed(self):
        async with FibHarness(delete_delay_ms=200) as h:
            h.routes_q.push(full_sync(route("10.0.0.1/32")))
            await wait_until(lambda: h.fib.synced)
            h.routes_q.push(incremental(delete=["10.0.0.1/32"]))
            await asyncio.sleep(0.1)
            assert "10.0.0.1/32" in h.service.unicast  # still installed
            await wait_until(
                lambda: "10.0.0.1/32" not in h.service.unicast, timeout_s=3
            )

    @run_async
    async def test_readd_cancels_delayed_delete(self):
        async with FibHarness(delete_delay_ms=150) as h:
            h.routes_q.push(full_sync(route("10.0.0.1/32")))
            await wait_until(lambda: h.fib.synced)
            h.routes_q.push(incremental(delete=["10.0.0.1/32"]))
            await asyncio.sleep(0.02)
            h.routes_q.push(incremental([route("10.0.0.1/32", nh="fe80::9")]))
            await asyncio.sleep(0.4)
            assert "10.0.0.1/32" in h.service.unicast
            (nh,) = h.service.unicast["10.0.0.1/32"].nexthops
            assert nh.address == "fe80::9"


class TestFibPerf:
    @run_async
    async def test_perf_events_recorded(self):
        async with FibHarness() as h:
            h.routes_q.push(full_sync(route("10.0.0.1/32")))
            await wait_until(lambda: h.fib.synced)
            perf_db = await h.fib.get_perf_db()
            assert perf_db
            descrs = [e.event_descr for e in perf_db[0].events]
            assert "FIB_RECEIVED" in descrs
            assert "FIB_PROGRAMMED" in descrs


class _ColumnsService(MockFibService):
    """Takes the full sync as packed columns, so that no route object is
    built for it and the crib behind the table stays unmaterialized."""

    supports_columns = True

    async def sync_fib_columns(self, client_id, batch) -> None:
        self._note("sync_fib_columns", batch.route_count())
        self.sync_count += 1
        self.unicast = dict.fromkeys(batch.prefix_set())


class TestFibFullResult:
    """A full result's delta (thousands of routes, ISSUE 45) through
    process_decision_route_update and _program_dirty_routes."""

    @pytest.mark.parametrize("service", [MockFibService, _ColumnsService],
                             ids=["entries", "columns"])
    def test_programs_the_brute_force_set_and_builds_no_row_singly(
        self, monkeypatch, service
    ):
        import numpy as np

        import openr_tpu.decision.tpu_solver as ts
        from openr_tpu.decision.columnar_rib import ColumnarRib
        from openr_tpu.decision.rib import DecisionRouteDb
        from openr_tpu.decision.tpu_solver import TpuSpfSolver
        from openr_tpu.models import topologies
        from openr_tpu.types import Adjacency, AdjacencyDatabase

        monkeypatch.setattr(ts, "_DELTA_BUDGET", 64)
        adj_dbs, prefix_dbs = topologies.grid(48, node_labels=False)
        states, ps = topologies.build_states(adj_dbs, prefix_dbs)
        me = "node-24-24"
        tpu = TpuSpfSolver(me)
        db = tpu.build_route_db(me, states, ps)
        crib = db.unicast_routes.segments[0].crib
        built = []
        real_build = ColumnarRib._build_rows_into

        def counted(self, cols, rows, routes):
            built.append(len(rows))
            return real_build(self, cols, rows, routes)

        monkeypatch.setattr(ColumnarRib, "_build_rows_into", counted)

        @run_async
        async def drive():
            nonlocal db
            h = FibHarness()
            h.service = h.fib.service = service()
            h.fib._retry_signal = asyncio.Event()
            first = DecisionRouteDb().calculate_update(db)
            first.type = RouteUpdateType.FULL_SYNC
            await h.fib.process_decision_route_update(first)
            assert h.fib.route_state.state == FibState.SYNCED
            assert crib.materialized == (service is MockFibService)
            del built[:]  # the first sync's own build, where it made one
            # the vantage's neighbour to the north takes dear links, but
            # the one to the vantage: half the grid's routes move
            victim = next(d for d in adj_dbs if d.this_node_name == "node-23-24")
            states["0"].update_adjacency_database(AdjacencyDatabase(
                this_node_name=victim.this_node_name,
                adjacencies=tuple(
                    a if a.other_node_name == me
                    else Adjacency(**{**a.__dict__, "metric": 9})
                    for a in victim.adjacencies
                ),
                area="0",
            ))
            new_db = tpu.build_route_db(me, states, ps)
            assert tpu.last_device_stats["full_pull"]
            assert new_db.unicast_routes.segments[0].crib is crib
            # the landing's bulk patch, where the crib held every entry
            n_changed = tpu.last_device_stats["full_changed_rows"]
            assert n_changed > 500
            assert built == (
                [n_changed] if service is MockFibService else [])
            del built[:]
            upd = db.calculate_update(new_db)
            upd.type = RouteUpdateType.INCREMENTAL
            assert upd.columns is not None
            await h.fib.process_decision_route_update(upd)
            await h.fib._program_dirty_routes()
            by_fib = list(built)
            # the oracle's tables last: they build every row
            return (dict(db.unicast_routes), dict(new_db.unicast_routes),
                    h, upd, by_fib)

        old_mat, new_mat, h, upd, built = drive()
        brute = {
            p: e for p, e in new_mat.items()
            if p not in old_mat or old_mat[p] != e
        }
        assert len(brute) > 500 and len(brute) == len(
            upd.unicast_routes_to_update)
        assert ("add_unicast", len(brute)) in h.service.call_log
        assert not any(op == "del_unicast" for op, _ in h.service.call_log)
        programmed = {
            p: e for p, e in h.service.unicast.items() if e is not None
        }
        if service is MockFibService:
            assert programmed == new_mat
            assert built == []  # every entry found in the patched cache
        else:
            assert programmed == brute
            assert built == [len(brute)]  # what it lacked, in one call
        assert 1 not in built
        assert not h.fib.route_state.dirty_prefixes
        ack = None
        while h.fib_reader.size():
            item = h.fib_reader.try_get()[1]
            if isinstance(item, DecisionRouteUpdate):
                ack = item
        assert ack is not None
        assert ack.unicast_routes_to_update == brute


@pytest.mark.parametrize("lfa", [False, True], ids=["plain", "lfa"])
def test_make_before_break_ledger_is_the_cpu_oracles(lfa):
    """Each epoch's delta programmed into two scripted netlink
    dataplanes — one fed by the incremental device solve's column delta,
    one by the CPU oracle's per-entry delta — with failures injected on
    the old-metric cleanups of make-before-break and a withdrawal:
    `_metric`, the `_stale` ledger, the failed sets and every prefix's
    kernel op sequence stay the same throughout."""
    import errno

    from openr_tpu.decision.rib import DecisionRouteDb
    from openr_tpu.decision.spf_solver import SpfSolver
    from openr_tpu.decision.tpu_solver import TpuSpfSolver
    from openr_tpu.serde import to_plain
    from tests.test_column_spine import (
        _per_prefix_ops,
        _scripted_dataplane,
        _ScriptedNetlink,
    )
    from tests.test_incremental_spf import ME, _Churn, _grid

    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    solvers = (
        TpuSpfSolver(ME, incremental_spf=True, enable_lfa=lfa),
        SpfSolver(ME, enable_lfa=lfa),
    )
    fakes = (_ScriptedNetlink(), _ScriptedNetlink())
    planes = tuple(_scripted_dataplane(fake) for fake in fakes)
    dbs = [DecisionRouteDb(), DecisionRouteDb()]

    async def program(dp, fake, upd, fail):
        fake.fail = dict(fail)
        if upd.columns is not None:
            failed = await dp.add_unicast_columns(upd.columns.to_batch())
        else:
            failed = await dp.add_unicast({
                p: to_plain(e)
                for p, e in dict(upd.unicast_routes_to_update).items()
            })
        if upd.unicast_routes_to_delete:
            failed += await dp.delete_unicast(
                list(upd.unicast_routes_to_delete)
            )
        return sorted(set(failed))

    def step(ctx, fail=()):
        failed, columns = [], []
        for i, solver in enumerate(solvers):
            new = solver.build_route_db(ME, states, ps)
            upd = dbs[i].calculate_update(new)
            dbs[i] = new
            columns.append(upd.columns is not None)
            failed.append(asyncio.run(program(planes[i], fakes[i], upd, fail)))
        assert failed[0] == failed[1], ctx
        assert planes[0]._metric == planes[1]._metric, ctx
        assert planes[0]._stale == planes[1]._stale, ctx
        assert _per_prefix_ops(fakes[0]) == _per_prefix_ops(fakes[1]), ctx
        return columns

    step("the first table")
    # the one short way to the grid's east edge: every step of its
    # metric moves routes, and each is a make-before-break transition
    edge = ("node-2-3", "node-2-4")
    churn.set_metric(*edge, 2)
    assert step("metrics move") == [True, False]
    assert solvers[0].last_timing["incremental"]
    # the cleanups of the old metric fail: the prefixes wait in _stale
    cleanups = {
        ("del", p, m): errno.EBUSY for p, m in planes[1]._metric.items()
    }
    churn.set_metric(*edge, 3)
    step("cleanups fail", cleanups)
    assert planes[0]._stale
    churn.set_metric(*edge, 1)
    step("a clean round clears them")
    assert not planes[0]._stale
    saved = [churn.dbs[n] for n in ("node-0-0", "node-0-1", "node-1-0")]
    churn.link_down("node-0-0", "node-0-1")
    churn.link_down("node-0-0", "node-1-0")
    step("a corner is cut off")
    for adj_db in saved:
        churn._put(adj_db)
    step("and comes back")
