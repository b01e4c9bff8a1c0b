"""Streaming churn-to-FIB pipeline (ISSUE 16) — parity + fence drills.

The streamed epoch fuses incremental relax, best-route selection, and
the on-device column diff into one dispatch and downloads ONLY the
compacted changed rows (ops/stream.py). Its promises, each pinned here:

  parity      the streamed solve is bit-identical to the CPU oracle and
              to the streaming_pipeline=off device path on every churn
              step (randomized metric/link churn, withdrawals included);
  exact diff  the device-computed changed-row set drives
              fast_unicast_column_diff's exact-journal lane and yields
              the SAME RIB delta (updates, deletes, materialized
              entries) as the host column compare — so the dataplane's
              make-before-break _metric/_stale ledgers evolve
              identically under injected kernel failures;
  standstill  an idle epoch downloads exactly one within-budget payload
              with zero changed rows — bytes stand still, they do not
              scale with n;
  no retrace  warm churn re-enters the baked stream-namespace
              executable: zero post-warmup retraces;
  fence       a dispatch-fiber crash mid-overlap orphans the deferred
              epoch finish; the epoch fence must discard it (never
              programming the stale batch) and recover via a forced
              full rebuild, with solve epochs staying monotonic.
"""

import asyncio
import errno

import numpy as np
import pytest

from openr_tpu.config import DecisionConfig
from openr_tpu.decision.spf_solver import SpfSolver
from openr_tpu.decision.tpu_solver import TpuSpfSolver
from openr_tpu.models import topologies
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.faults import registry
from openr_tpu.serde import to_plain
from tests.conftest import run_async
from tests.test_compact_rows import Recorder, drive_randomized_churn
from tests.test_column_spine import (
    _per_prefix_ops,
    _scripted_dataplane,
    _ScriptedNetlink,
)
from tests.test_decision import DecisionHarness, adj, adj_db_kv, two_node_mesh
from tests.test_incremental_spf import ME, _Churn, _grid
from tests.test_tpu_solver import assert_rib_equal


def _cnt(key):
    return int(counters.get_counter(key) or 0)


def _retraces():
    return sum(counters.get_counters("xla_cache.retraces.").values())


def _stream_info(solver):
    return getattr(solver, "last_timing", {}).get("stream") or {}


# -- solver parity ---------------------------------------------------------


def test_randomized_churn_stream_parity():
    """Randomized metric inc/dec + link down/up: the streamed solve must
    match the oracle AND the streaming_pipeline=off device path exactly
    on every step, and must actually stream (not fall back) on most."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    cpu = SpfSolver(ME)
    host = TpuSpfSolver(ME, streaming_pipeline=False)
    strm = TpuSpfSolver(ME, streaming_pipeline=True)

    def solve(ctx):
        cpu_db = cpu.build_route_db(ME, states, ps)
        host_db = host.build_route_db(ME, states, ps)
        strm_db = strm.build_route_db(ME, states, ps)
        assert_rib_equal(cpu_db, strm_db, f"{ctx}: stream vs oracle")
        # bit-identical promise vs the off-knob (PR 12) device path
        assert strm_db.unicast_routes == host_db.unicast_routes, ctx
        assert strm_db.mpls_routes == host_db.mpls_routes, ctx

    solve("round0")  # cold: full pull, no stream epoch yet

    rng = np.random.default_rng(23)
    metrics = (1, 3, 50, 100000)
    edges = churn.edges()
    engaged = 0
    down = None
    for i in range(10):
        if down is not None and rng.integers(3) == 0:
            u, v, su, sv = down
            churn.link_up(u, v, su, sv)
            ctx = f"round{i + 1}: up {u}<->{v}"
            down = None
        elif down is None and rng.integers(4) == 0:
            while True:
                u, v = edges[rng.integers(len(edges))]
                if ME not in (u, v):
                    break
            down = (u, v, churn.dbs[u], churn.dbs[v])
            churn.link_down(u, v)
            ctx = f"round{i + 1}: down {u}<->{v}"
        else:
            u, v = edges[rng.integers(len(edges))]
            m = int(metrics[rng.integers(len(metrics))])
            churn.set_metric(u, v, m)
            ctx = f"round{i + 1}: metric {u}<->{v}={m}"
        solve(ctx)
        if _stream_info(strm).get("epochs"):
            engaged += 1
    # the sequence must exercise the streamed lane, not fall back on
    # every round (root-link churn legitimately falls back)
    assert engaged >= 5, engaged


@pytest.mark.parametrize("seed", [23, 41])
def test_randomized_churn_stream_payload_is_the_parents(monkeypatch, seed):
    """The streamed payload bit for bit: every dispatch of randomized
    churn replayed through the parent commit's pipeline (`jnp.nonzero`
    in both compactions, the cold pull built in every epoch) — the delta
    payload equal in every epoch, the cold pull equal where the host
    reads it and zeros where it does not (tests/test_compact_rows.py)."""
    adj_dbs, states, ps = _grid()
    rec = drive_randomized_churn(
        monkeypatch, adj_dbs, states, ps, ME, "streaming", seed, steps=10
    )
    streamed = [e for e in rec.epochs if e[0].stream]
    assert len(streamed) >= 5, rec.epochs
    assert not any(cold for *_, cold in streamed), rec.epochs


def test_stream_overflow_finds_its_full_pull_in_the_same_dispatch(
    monkeypatch
):
    """A column of links changes at once: 72 rows against the 64-row
    floor. The device's own count takes the cold half in that dispatch
    (want_full 0), the host reads the table it built — no second round
    trip — and the budget grows; the same change inside the grown
    budget streams, and builds no table."""
    adj_dbs, prefix_dbs = topologies.grid(12, node_labels=False)
    states, ps = topologies.build_states(adj_dbs, prefix_dbs)
    churn = _Churn(adj_dbs, states)
    cpu = SpfSolver(ME)
    strm = TpuSpfSolver(ME, streaming_pipeline=True)
    rec = Recorder(monkeypatch, strm)
    strm.build_route_db(ME, states, ps)
    assert rec.epochs[-1][1:] == (1, rec.epochs[-1][2], True)
    seen = []
    for metric in (5, 1, 7, 7, 1):
        for row in range(12):
            churn.set_metric(f"node-{row}-5", f"node-{row}-6", metric)
        runs0 = len(rec.epochs)
        got = strm.build_route_db(ME, states, ps)
        assert_rib_equal(cpu.build_route_db(ME, states, ps), got,
                         f"column at {metric}")
        assert len(rec.epochs) == runs0 + 1  # one dispatch an epoch
        variant, want_full, count, cold = rec.epochs[-1]
        info = _stream_info(strm)
        assert want_full == 0 and bool(info.get("overflows")) == cold
        seen.append((variant.stream, count, cold))
    assert seen == [
        (64, 72, True), (256, 72, False), (256, 72, False),
        (256, 0, False), (64, 72, True),
    ], seen


def test_device_diff_matches_host_column_diff_with_withdrawals():
    """The compacted device diff feeds the journal's exact lane; the
    resulting RIB delta (update set, materialized entries, deletes)
    must equal the host column-compare path's — including the ok->False
    withdrawal lane when a node drops off the graph entirely."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    strm = TpuSpfSolver(ME, streaming_pipeline=True)
    host = TpuSpfSolver(ME, streaming_pipeline=False)
    s_db = strm.build_route_db(ME, states, ps)
    h_db = host.build_route_db(ME, states, ps)

    def step(ctx):
        nonlocal s_db, h_db
        s_new = strm.build_route_db(ME, states, ps)
        h_new = host.build_route_db(ME, states, ps)
        s_upd = s_db.calculate_update(s_new)
        h_upd = h_db.calculate_update(h_new)
        assert set(s_upd.unicast_routes_to_update) == set(
            h_upd.unicast_routes_to_update
        ), ctx
        assert dict(s_upd.unicast_routes_to_update) == dict(
            h_upd.unicast_routes_to_update
        ), ctx
        assert sorted(s_upd.unicast_routes_to_delete) == sorted(
            h_upd.unicast_routes_to_delete
        ), ctx
        s_db, h_db = s_new, h_new
        return s_upd

    churn.set_metric("node-0-1", "node-1-1", 40)
    upd = step("metric-inc")
    assert _stream_info(strm).get("epochs"), strm.last_timing
    assert upd.unicast_routes_to_update, "metric change produced no delta"

    # withdrawal: isolate a far corner — its loopback leaves the RIB
    # through the device diff's ok-transition delete lane
    corner = "node-0-0"
    saved = (
        churn.dbs[corner],
        churn.dbs["node-0-1"],
        churn.dbs["node-1-0"],
    )
    churn.link_down(corner, "node-0-1")
    churn.link_down(corner, "node-1-0")
    upd = step("withdraw-corner")
    assert upd.unicast_routes_to_delete, "isolation produced no deletes"

    # restore: the withdrawn loopback comes back through the update lane
    for db in saved:
        churn._put(db)
    upd = step("restore-corner")
    assert upd.unicast_routes_to_update, "restore produced no delta"


def test_mbb_stale_ledger_parity_streamed_vs_host():
    """Program each epoch's delta batch into two scripted netlink
    dataplanes — one fed by the streamed diff, one by the host diff —
    with injected failures on old-metric make-before-break cleanups and
    a withdrawal. _metric, the _stale ledger, the failed sets, and the
    per-prefix kernel op sequences must stay identical throughout."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    strm = TpuSpfSolver(ME, streaming_pipeline=True)
    host = TpuSpfSolver(ME, streaming_pipeline=False)
    fake_s, fake_h = _ScriptedNetlink(), _ScriptedNetlink()
    dp_s, dp_h = _scripted_dataplane(fake_s), _scripted_dataplane(fake_h)

    async def program(dp, fake, upd, fail):
        fake.fail = dict(fail)
        failed = []
        if upd.columns is not None:
            failed += await dp.add_unicast_columns(upd.columns.to_batch())
        else:
            failed += await dp.add_unicast({
                p: to_plain(e)
                for p, e in dict(upd.unicast_routes_to_update).items()
            })
        if upd.unicast_routes_to_delete:
            failed += await dp.delete_unicast(
                list(upd.unicast_routes_to_delete)
            )
        return failed

    s_db = strm.build_route_db(ME, states, ps)
    h_db = host.build_route_db(ME, states, ps)

    def step(ctx, fail=()):
        nonlocal s_db, h_db
        s_new = strm.build_route_db(ME, states, ps)
        h_new = host.build_route_db(ME, states, ps)
        s_upd = s_db.calculate_update(s_new)
        h_upd = h_db.calculate_update(h_new)
        f_s = asyncio.run(program(dp_s, fake_s, s_upd, fail))
        f_h = asyncio.run(program(dp_h, fake_h, h_upd, fail))
        s_db, h_db = s_new, h_new
        assert sorted(set(f_s)) == sorted(set(f_h)), ctx
        assert dp_s._metric == dp_h._metric, ctx
        assert dp_s._stale == dp_h._stale, ctx
        assert _per_prefix_ops(fake_s) == _per_prefix_ops(fake_h), ctx

    # cold: full-table program seeds both _metric ledgers
    from openr_tpu.decision.rib import DecisionRouteDb

    cold_s = DecisionRouteDb().calculate_update(s_db)
    cold_h = DecisionRouteDb().calculate_update(h_db)
    asyncio.run(program(dp_s, fake_s, cold_s, ()))
    asyncio.run(program(dp_h, fake_h, cold_h, ()))
    assert dp_s._metric == dp_h._metric, "cold"

    # metric churn: every changed row is a make-before-break transition
    churn.set_metric("node-0-1", "node-1-1", 30)
    step("mbb-clean")

    # fail one old-metric cleanup delete: the prefix parks in _stale on
    # BOTH dataplanes and reports failed
    churn.set_metric("node-0-1", "node-1-1", 44)
    victim = next(
        p for p, m in dp_h._metric.items()
        if m == 30 or dp_h._stale.get(p)
    ) if any(m == 30 for m in dp_h._metric.values()) else None
    fail = {}
    # build the injected failure from the CURRENT ledger so both sides
    # see the same (op, prefix, metric) key
    for p, m in dp_h._metric.items():
        if m == 30:
            fail[("del", p, 30)] = errno.EBUSY
    step("mbb-cleanup-fails", fail)
    if fail:
        assert dp_s._stale, "injected cleanup failure left no stale entry"

    # retry round (no injected failures): the stale duplicates clear
    churn.set_metric("node-0-1", "node-1-1", 51)
    step("mbb-retry-clears")

    # withdrawal: isolation drives the delete lane, which must also
    # sweep any _stale residue identically
    saved = (
        churn.dbs["node-0-0"],
        churn.dbs["node-0-1"],
        churn.dbs["node-1-0"],
    )
    churn.link_down("node-0-0", "node-0-1")
    churn.link_down("node-0-0", "node-1-0")
    step("withdraw")
    for db in saved:
        churn._put(db)
    step("restore")


# -- standstill + retrace accounting ---------------------------------------


def test_idle_epoch_download_standstill():
    """An epoch in which zero rows changed still ships exactly one
    within-budget streaming payload: bytes_downloaded is identical to a
    within-budget churn epoch's (the payload is budget-shaped, not
    row-count-shaped) and changed_rows reports 0."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    strm = TpuSpfSolver(ME, streaming_pipeline=True)
    strm.build_route_db(ME, states, ps)  # cold full pull

    churn.set_metric("node-0-1", "node-1-1", 9)
    strm.build_route_db(ME, states, ps)
    st = _stream_info(strm)
    assert st.get("epochs") == 1, strm.last_timing
    assert st.get("changed_rows", 0) > 0, st
    warm_bytes = strm.last_timing["bytes_downloaded"]
    assert warm_bytes > 0

    for i in range(2):  # idle epochs: nothing changed since last solve
        strm.build_route_db(ME, states, ps)
        st = _stream_info(strm)
        assert st.get("epochs") == 1, (i, strm.last_timing)
        assert st.get("changed_rows") == 0, (i, st)
        assert strm.last_timing["bytes_downloaded"] == warm_bytes, (
            i, warm_bytes, strm.last_timing,
        )


def test_warm_stream_churn_has_zero_retraces():
    """After the streamed epoch kernel is baked (one warm epoch), churn
    re-entering the same budget class must report zero retraces across
    ALL executable namespaces, the new stream namespace included."""
    adj_dbs, states, ps = _grid()
    churn = _Churn(adj_dbs, states)
    strm = TpuSpfSolver(ME, streaming_pipeline=True)
    strm.build_route_db(ME, states, ps)  # cold
    churn.set_metric("node-0-1", "node-1-1", 7)
    strm.build_route_db(ME, states, ps)  # warmup: bakes the stream exec
    r0 = _retraces()
    for i, m in enumerate((12, 19, 4, 88, 2)):
        churn.set_metric("node-0-1", "node-1-1", m)
        strm.build_route_db(ME, states, ps)
        assert _stream_info(strm).get("epochs"), (i, strm.last_timing)
    assert _retraces() - r0 == 0


# -- epoch fence (chaos drill) ---------------------------------------------


async def _wait(cond, timeout_s=10.0, interval=0.005):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout_s
    while not cond():
        if loop.time() > deadline:
            raise AssertionError("timeout waiting for condition")
        await asyncio.sleep(interval)


class TestEpochFence:
    @run_async
    async def test_fiber_crash_mid_overlap_fences_stale_finish(self):
        """Kill the dispatch fiber while an epoch's deferred finish is
        still queued (its FIB program 'in flight' behind a held gate).
        The orphaned finish must discard itself at the fence — its batch
        is never pushed — and the restart's forced full rebuild must
        converge on the post-crash topology with solve epochs strictly
        monotonic across everything that IS pushed."""
        cfg = DecisionConfig(
            debounce_min_ms=5, debounce_max_ms=20,
            async_dispatch=True, streaming_pipeline=True,
        )
        registry.clear()
        try:
            async with DecisionHarness(config=cfg) as h:
                two_node_mesh(h)
                h.synced()
                upd = await h.next_route_update()
                assert upd.solve_epoch is not None
                epochs = [upd.solve_epoch]
                d = h.decision

                # freeze the finish chain: epoch A's finish will queue
                # behind this held task, exactly like a slow netlink
                # program still in flight
                gate = asyncio.Event()
                hold = asyncio.ensure_future(gate.wait())
                d._stream_finish = hold

                f0 = _cnt("decision.stream.fenced")
                r0 = _cnt("runtime.supervisor.restarts")
                g0 = d._fence_gen

                # epoch A: adjacency metric change -> full rebuild;
                # its finish defers behind the gate
                h.publish(
                    adj_db_kv("1", [adj("1", "2", metric=5)], version=2),
                    adj_db_kv("2", [adj("2", "1", metric=5)], version=2),
                )
                await _wait(lambda: d._stream_finish is not hold)

                # epoch B: the dispatch fiber dies holding it; the
                # supervisor restart bumps the fence over epoch A
                registry.arm("solver.dispatch", every_nth=1, max_fires=1)
                h.publish(
                    adj_db_kv("1", [adj("1", "2", metric=7)], version=3),
                    adj_db_kv("2", [adj("2", "1", metric=7)], version=3),
                )
                # the supervisor's recovery hook raises the fence BEFORE
                # forcing the full rebuild — only then release the gate,
                # pinning the dangerous ordering: restart first, stale
                # finish after
                await _wait(lambda: d._fence_gen > g0)
                assert _cnt("runtime.supervisor.restarts") >= r0 + 1
                gate.set()

                # recovery: the forced full rebuild programs metric 7
                seen_costs = []
                while True:
                    upd = await h.next_route_update(timeout=10)
                    if upd.solve_epoch is not None:
                        epochs.append(upd.solve_epoch)
                    e = upd.unicast_routes_to_update.get("10.0.0.2/32")
                    if e is not None:
                        seen_costs.append(e.igp_cost)
                        if e.igp_cost == 7:
                            break
                # the fenced epoch (metric 5) never programmed
                assert _cnt("decision.stream.fenced") == f0 + 1
                assert 5 not in seen_costs, seen_costs
                # acks/provenance attribute to the right epoch: strictly
                # monotonic solve epochs on every pushed update
                assert epochs == sorted(set(epochs)), epochs
        finally:
            registry.clear()

    @run_async
    async def test_fenced_requeue_budget_accounts_fence_hold(self):
        """ISSUE 17: a fenced stale finish must close its latency budget
        as exactly ONE requeued epoch whose waterfall carries a non-zero
        ``fence_hold`` component — and the requeued row still conserves
        (components + unattributed == e2e).  The requeue detour is real
        latency the taxonomy must own, not silently drop."""
        from openr_tpu.runtime.latency_budget import latency_budget
        from openr_tpu.runtime.tracing import tracer
        from openr_tpu.types import Publication
        from tests.test_decision import AREA

        cfg = DecisionConfig(
            debounce_min_ms=5, debounce_max_ms=20,
            async_dispatch=True, streaming_pipeline=True,
        )
        registry.clear()
        try:
            async with DecisionHarness(config=cfg) as h:
                two_node_mesh(h)
                h.synced()
                await h.next_route_update()
                d = h.decision

                gate = asyncio.Event()
                hold = asyncio.ensure_future(gate.wait())
                d._stream_finish = hold

                f0 = _cnt("decision.stream.fenced")
                rq0 = _cnt("budget.requeued_epochs")
                g0 = d._fence_gen

                # epoch A rides a convergence trace (as production
                # publications from KvStore._merge_and_flood do), so the
                # budget ledger tracks it end to end
                ctx = tracer.start_trace("convergence", node="1")
                h.kv_q.push(
                    Publication(
                        key_vals=dict([
                            adj_db_kv("1", [adj("1", "2", metric=5)],
                                      version=2),
                            adj_db_kv("2", [adj("2", "1", metric=5)],
                                      version=2),
                        ]),
                        area=AREA,
                    ),
                    trace=ctx,
                )
                await _wait(lambda: d._stream_finish is not hold)

                # epoch B's dispatch-fiber crash restarts the fiber and
                # bumps the fence over epoch A's still-queued finish
                registry.arm("solver.dispatch", every_nth=1, max_fires=1)
                h.publish(
                    adj_db_kv("1", [adj("1", "2", metric=7)], version=3),
                    adj_db_kv("2", [adj("2", "1", metric=7)], version=3),
                )
                await _wait(lambda: d._fence_gen > g0)
                gate.set()

                # recovery converges on metric 7; A's finish has fenced
                while True:
                    upd = await h.next_route_update(timeout=10)
                    e = upd.unicast_routes_to_update.get("10.0.0.2/32")
                    if e is not None and e.igp_cost == 7:
                        break
                await _wait(
                    lambda: _cnt("decision.stream.fenced") == f0 + 1
                )

                # exactly one requeued epoch in the ledger
                assert _cnt("budget.requeued_epochs") == rq0 + 1
                rows = [
                    r for r in latency_budget.last_epochs(64)
                    if r["status"] == "requeued"
                    and r["key"] == str(("trace", ctx.trace_id))
                ]
                assert len(rows) == 1, rows
                row = rows[0]
                # the fence detour is owned by fence_hold, non-zero
                assert row["components"].get("fence_hold", 0.0) > 0.0, row
                # and the requeued row still conserves
                total = (
                    sum(row["components"].values())
                    + row["unattributed_ms"]
                )
                assert abs(total - row["e2e_ms"]) <= 0.05, row
        finally:
            registry.clear()

    @run_async
    async def test_recorded_streaming_session_replays_both_ways(self):
        """ISSUE 18 replay determinism over the parity trio: record one
        randomized churn session through the streaming device pipeline,
        then replay the SAME recording with the streaming pipeline on
        AND off (and on the CPU oracle) — per-epoch RIB digests must be
        bit-identical to the recording every way. The streamed epoch's
        bit-identical parity promise, restated over recorded incident
        data instead of a live side-by-side."""
        from tools.replay import replay_bundle

        cfg = DecisionConfig(
            debounce_min_ms=5, debounce_max_ms=20,
            streaming_pipeline=True,
        )
        async with DecisionHarness(backend="tpu", config=cfg) as h:
            two_node_mesh(h)
            h.synced()
            await h.next_route_update()
            rng = np.random.default_rng(18)
            version = 1
            for _ in range(5):
                version += 1
                m = int(rng.integers(1, 100))
                h.publish(
                    adj_db_kv("1", [adj("1", "2", metric=m)],
                              version=version),
                    adj_db_kv("2", [adj("2", "1", metric=m)],
                              version=version),
                )
                await h.next_route_update()
            annex = h.decision._replay.export()
        assert annex is not None and not annex["gap"], annex
        bundle = {"node": "1", "inputs": annex}
        for solver, streaming in (
            ("tpu", True), ("tpu", False), ("cpu", False),
        ):
            report = replay_bundle(
                bundle, solver=solver, streaming=streaming
            )
            assert report["status"] == "identical", (
                solver, streaming, report,
            )
            assert report["epochs_compared"] >= 4, (solver, report)

    @run_async
    async def test_streaming_off_keeps_inline_finish(self):
        """Config gate: with streaming_pipeline=False (the PR 12 path)
        no finish is ever deferred — the bisection knob documented in
        docs/Operations.md really does disengage the overlap machinery."""
        cfg = DecisionConfig(
            debounce_min_ms=5, debounce_max_ms=20, async_dispatch=True
        )
        async with DecisionHarness(config=cfg) as h:
            two_node_mesh(h)
            h.synced()
            await h.next_route_update()
            assert h.decision._stream_finish is None
