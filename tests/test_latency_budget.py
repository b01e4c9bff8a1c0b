"""Latency-budget ledger unit tests (runtime/latency_budget.py).

The tentpole claim is *conservation*: every closed epoch's attributed
components plus the ``unattributed_ms`` residual equal the measured
end-to-end wall time, regardless of how noisy the externally-measured
splits are.  These tests drive the cursor arithmetic with synthetic
clocks (no sleeps), so the invariant is checked exactly.
"""

import pytest

from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.latency_budget import (
    BUDGET_COMPONENTS,
    CONSERVATION_EPSILON_MS,
    EpochBudget,
    LatencyBudgetLedger,
    latency_budget,
    tail_attribution,
)


@pytest.fixture
def ledger():
    lg = LatencyBudgetLedger()
    counters.erase_prefix("budget.")
    yield lg
    counters.erase_prefix("budget.")


def _conserved(row):
    total = sum(row["components"].values()) + row["unattributed_ms"]
    assert total == pytest.approx(row["e2e_ms"], abs=0.01), row
    return row


class TestCursor:
    def test_advance_attributes_segments_and_conserves(self, ledger):
        bud = ledger.begin("k", start=10.0)
        bud.advance("ingest_wait", now=10.002)     # 2 ms
        bud.advance("host_sync", now=10.010)       # 8 ms
        bud.advance("device_exec", now=10.013)     # 3 ms
        row = _conserved(ledger.close(bud, now=10.013))
        assert row["components"] == {
            "ingest_wait": pytest.approx(2.0),
            "host_sync": pytest.approx(8.0),
            "device_exec": pytest.approx(3.0),
        }
        assert row["e2e_ms"] == pytest.approx(13.0)
        assert row["unattributed_ms"] == 0.0
        assert row["top_component"] == "host_sync"

    def test_stale_now_clamps_to_cursor(self):
        bud = EpochBudget("k", 10.0)
        bud.advance("ingest_wait", now=10.005)
        # a stamp from an out-of-order clock read earlier than the
        # cursor must attribute nothing, never go negative
        assert bud.advance("host_sync", now=10.001) == 0.0
        assert "host_sync" not in bud.components
        assert bud.cursor == 10.005

    def test_advance_split_clips_overclaim_to_segment(self, ledger):
        bud = ledger.begin("k", start=0.0)
        # segment is 10 ms, but the external measurements claim 9 + 8:
        # the second split gets clipped to the 1 ms remainder and the
        # primary gets nothing — conservation survives the over-claim
        bud.advance_split(
            {"device_exec": 9.0, "payload_apply": 8.0},
            primary="collect_block",
            now=0.010,
        )
        row = _conserved(ledger.close(bud, now=0.010))
        assert row["components"]["device_exec"] == pytest.approx(9.0)
        assert row["components"]["payload_apply"] == pytest.approx(1.0)
        assert "collect_block" not in row["components"]

    def test_advance_split_remainder_goes_to_primary(self, ledger):
        bud = ledger.begin("k", start=0.0)
        # splits cover 3 of the 10 ms; primary absorbs the rest, and a
        # None measurement (solver did not report the stage) is 0
        bud.advance_split(
            {"device_exec": 2.0, "payload_apply": None, "program": 1.0},
            primary="collect_block",
            now=0.010,
        )
        row = _conserved(ledger.close(bud, now=0.010))
        assert row["components"]["collect_block"] == pytest.approx(7.0)

    def test_final_component_absorbs_close_tail(self, ledger):
        bud = ledger.begin("k", start=0.0)
        bud.advance("program", now=0.004)
        row = _conserved(
            ledger.close(bud, final_component="ack_rtt", now=0.009)
        )
        assert row["components"]["ack_rtt"] == pytest.approx(5.0)
        assert row["unattributed_ms"] == 0.0

    def test_unstamped_gap_is_unattributed(self, ledger):
        bud = ledger.begin("k", start=0.0)
        bud.advance("program", now=0.004)
        # no final_component: the [cursor, close] tail is exactly the
        # residual the drift SLO pages on
        row = _conserved(ledger.close(bud, now=0.010))
        assert row["unattributed_ms"] == pytest.approx(6.0)


class TestLedgerLifecycle:
    def test_begin_dedups_by_key(self, ledger):
        a = ledger.begin("k", start=0.0)
        b = ledger.begin("k", start=99.0)
        assert a is b

    def test_close_records_stats_for_every_component(self, ledger):
        bud = ledger.begin("k", start=0.0)
        bud.advance("host_sync", now=0.010)
        ledger.close(bud, now=0.010)
        stats = counters.get_statistics("budget.")
        # zeros included: an idle component's p99 of 0 is information
        for comp in BUDGET_COMPONENTS:
            assert f"budget.{comp}_ms" in stats, comp
        assert "budget.e2e_ms" in stats
        assert "budget.unattributed_ms" in stats
        assert counters.get_counter("budget.epochs") == 1

    def test_close_is_idempotent(self, ledger):
        bud = ledger.begin("k", start=0.0)
        assert ledger.close(bud, now=0.001) is not None
        assert ledger.close(bud, now=0.002) is None
        assert counters.get_counter("budget.epochs") == 1

    def test_requeued_status_counts_separately(self, ledger):
        bud = ledger.begin("k", start=0.0)
        bud.advance("coalesce_hold", now=0.003)
        row = ledger.close(bud, status="requeued", now=0.003)
        assert row["status"] == "requeued"
        assert counters.get_counter("budget.requeued_epochs") == 1

    def test_discard_drops_without_stats(self, ledger):
        ledger.begin("k", start=0.0)
        ledger.discard("k")
        assert ledger.of("k") is None
        assert counters.get_counter("budget.discarded") == 1
        assert counters.get_counter("budget.epochs") is None
        assert ledger.last_epochs() == []

    def test_eviction_at_capacity_is_counted(self, ledger):
        from openr_tpu.runtime import latency_budget as mod

        for i in range(mod._MAX_ACTIVE + 3):
            ledger.begin(("leak", i), start=0.0)
        assert counters.get_counter("budget.evicted") == 3
        # the oldest leaked epochs were the ones evicted
        assert ledger.of(("leak", 0)) is None
        assert ledger.of(("leak", 3)) is not None


class TestTraceIntegration:
    def test_begin_for_trace_anchors_at_trace_start(self, ledger):
        from openr_tpu.runtime.tracing import tracer

        tracer.clear()
        ctx = tracer.start_trace("convergence", node="n0")
        try:
            bud = latency_budget.begin_for_trace(ctx)
            assert bud is not None
            # anchored at the trace's own monotonic start, so the first
            # advance() sees the queue wait that preceded the pickup
            assert bud.start == pytest.approx(tracer.trace_start(ctx))
            bud.advance("ingest_wait")
            assert bud.components.get("ingest_wait", 0.0) >= 0.0
            assert latency_budget.of_trace(ctx) is bud
        finally:
            latency_budget.discard_trace(ctx)
            tracer.clear()
            counters.erase_prefix("budget.")

    def test_close_trace_returns_conserved_row(self, ledger):
        from openr_tpu.runtime.tracing import tracer

        tracer.clear()
        ctx = tracer.start_trace("convergence", node="n0")
        try:
            bud = latency_budget.begin_for_trace(ctx)
            bud.advance("host_sync")
            row = latency_budget.close_trace(
                ctx, final_component="ack_rtt"
            )
            assert row is not None
            _conserved(row)
            assert latency_budget.of_trace(ctx) is None
        finally:
            tracer.clear()
            counters.erase_prefix("budget.")


class TestReporting:
    def test_report_shape_and_conservation_block(self, ledger):
        for i in range(4):
            bud = ledger.begin(("e", i), start=0.0)
            bud.advance("host_sync", now=0.002 + i * 0.001)
            ledger.close(bud, final_component="ack_rtt",
                         now=0.004 + i * 0.001)
        rep = ledger.report()
        assert rep["taxonomy"] == list(BUDGET_COMPONENTS)
        assert "host_sync" in rep["components"]
        assert rep["conservation"]["epochs"] == 4
        assert rep["conservation"]["epsilon_ms"] == CONSERVATION_EPSILON_MS
        assert len(rep["last_epochs"]) == 4
        assert rep["tail"]["ranked"], rep["tail"]

    def test_snapshot_compact_annex(self, ledger):
        bud = ledger.begin("k", start=0.0)
        bud.advance("program", now=0.003)
        ledger.close(bud, now=0.003)
        snap = ledger.snapshot()
        assert snap["epochs"] == 1
        assert set(snap["components"]) == set(BUDGET_COMPONENTS)
        assert snap["e2e"].get("count") == 1
        assert len(snap["last_epochs"]) == 1


class TestTailAttribution:
    def test_top2_coverage_ranks_the_moving_components(self):
        # host_sync owns the tail (40 ms of the 41 ms p50->p99 gap),
        # program wiggles by 1 ms, device_exec is flat
        e2e = [10.0] * 9 + [51.0]
        comps = {
            "host_sync": [5.0] * 9 + [45.0],
            "program": [1.0] * 9 + [2.0],
            "device_exec": [4.0] * 10,
        }
        out = tail_attribution(comps, e2e)
        assert out["e2e_gap_ms"] == pytest.approx(41.0)
        assert out["ranked"][0]["component"] == "host_sync"
        assert out["top2_coverage"] == pytest.approx(1.0)

    def test_empty_samples_report_none_coverage(self):
        out = tail_attribution({c: [] for c in BUDGET_COMPONENTS}, [])
        assert out["e2e_gap_ms"] == 0.0
        assert out["ranked"] == []
        assert out["top2_coverage"] is None
