"""Overload-control tests (ISSUE 19): FlapDamper state machine,
OverloadController ladder + admission units (both on injected virtual
clocks — no wall-clock sleeps), and two chaos drills through a live
Decision actor: a single-key flap storm that must suppress-then-release
while undamped keys keep converging, and an injected HBM-pressure
brownout that must walk the downshift ladder and recover with no
stale-route window.

Unit classes are tier-1; the drills are marked slow+chaos like the
rest of test_chaos.py.
"""

import asyncio

import pytest

from openr_tpu.config import DecisionConfig
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.overload import (
    BACKPRESSURE,
    BROWNOUT,
    OK,
    OVERLOAD_COUNTER_FIELDS,
    OVERLOAD_STATES,
    SHEDDING,
    FlapDamper,
    OverloadController,
    get_controller,
    register,
    unregister,
)
from openr_tpu.types import Publication
from tests.conftest import run_async
from tests.test_decision import (
    AREA,
    DecisionHarness,
    adj,
    adj_db_kv,
    prefix_db_kv,
    two_node_mesh,
)


class Clock:
    """Injectable virtual clock."""

    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# FlapDamper state machine
# ---------------------------------------------------------------------------


class TestFlapDamper:
    def test_penalty_accumulates_to_suppression(self):
        clk = Clock()
        d = FlapDamper(half_life_s=60.0, penalty=1.0,
                       suppress_threshold=3.0, reuse_threshold=1.0,
                       clock=clk)
        # two rapid changes: figure 2.0, still under the threshold
        assert d.record_change(AREA, "adj:x") is False
        assert d.record_change(AREA, "adj:x") is False
        assert not d.is_suppressed(AREA, "adj:x")
        # third crosses 3.0 -> suppressed, and this very event is the
        # first one withheld
        assert d.record_change(AREA, "adj:x") is True
        assert d.is_suppressed(AREA, "adj:x")
        assert d.damped_count() == 1
        assert d.suppressed_events == 1
        # an unrelated key is untouched
        assert d.record_change(AREA, "adj:y") is False
        assert not d.is_suppressed(AREA, "adj:y")

    def test_figure_decays_with_half_life(self):
        clk = Clock()
        d = FlapDamper(half_life_s=10.0, suppress_threshold=3.0,
                       reuse_threshold=1.0, clock=clk)
        d.record_change(AREA, "k")
        d.record_change(AREA, "k")
        assert d.figure_of_merit(AREA, "k") == pytest.approx(2.0)
        clk.advance(10.0)  # one half-life
        assert d.figure_of_merit(AREA, "k") == pytest.approx(1.0)
        clk.advance(10.0)
        assert d.figure_of_merit(AREA, "k") == pytest.approx(0.5)

    def test_half_life_release_returns_held_latest_event(self):
        clk = Clock()
        d = FlapDamper(half_life_s=10.0, penalty=1.0,
                       suppress_threshold=3.0, reuse_threshold=1.0,
                       clock=clk)
        for _ in range(3):
            d.record_change(AREA, "k")
        d.hold(AREA, "k", ("kv", 1, "n", b"stale"))
        d.record_change(AREA, "k")
        d.hold(AREA, "k", ("kv", 2, "n", b"latest"))  # latest wins
        # figure is 4.0; needs two half-lives to cross reuse=1.0
        clk.advance(10.0)
        assert d.releasable() == []  # 2.0 > reuse: still suppressed
        assert d.damped_count() == 1
        clk.advance(10.0)
        out = d.releasable()
        assert out == [(AREA, "k", ("kv", 2, "n", b"latest"))]
        assert d.damped_count() == 0
        assert d.released_keys == 1
        # released key forgotten entirely — next change starts fresh
        assert d.record_change(AREA, "k") is False

    def test_hold_ignored_for_unsuppressed_key(self):
        d = FlapDamper(clock=Clock())
        d.record_change(AREA, "k")
        d.hold(AREA, "k", ("kv", 1, "n", b"v"))
        clk_out = d.releasable()
        assert clk_out == []  # never suppressed, nothing to release

    def test_backwards_clock_decays_nothing(self):
        clk = Clock()
        d = FlapDamper(half_life_s=10.0, suppress_threshold=3.0,
                       reuse_threshold=1.0, clock=clk)
        d.record_change(AREA, "k")
        d.record_change(AREA, "k")
        clk.t -= 100.0  # paused-process / clock-reuse pathology
        # monotonicity enforced: figure neither decays nor inflates...
        assert d.figure_of_merit(AREA, "k") == pytest.approx(2.0)
        # ...and the next change still accumulates from the held figure
        assert d.record_change(AREA, "k") is True

    def test_max_penalty_clamps_the_figure(self):
        clk = Clock()
        d = FlapDamper(half_life_s=60.0, penalty=1.0,
                       suppress_threshold=3.0, reuse_threshold=1.0,
                       max_penalty=5.0, clock=clk)
        for _ in range(50):
            d.record_change(AREA, "k")
        assert d.figure_of_merit(AREA, "k") == pytest.approx(5.0)
        # clamp bounds the suppression tail: 5.0 -> 1.0 needs ~2.32
        # half-lives, not 50
        clk.advance(60.0 * 3)
        assert d.releasable() != []

    def test_calm_unsuppressed_keys_are_garbage_collected(self):
        clk = Clock()
        d = FlapDamper(half_life_s=1.0, suppress_threshold=3.0,
                       reuse_threshold=1.0, clock=clk)
        d.record_change(AREA, "k")
        assert d.report()["tracked_keys"] == 1
        clk.advance(20.0)  # decays to ~1e-6 of the penalty
        d.releasable()
        assert d.report()["tracked_keys"] == 0

    def test_threshold_validation(self):
        with pytest.raises(ValueError):
            FlapDamper(suppress_threshold=1.0, reuse_threshold=1.0)
        with pytest.raises(ValueError):
            FlapDamper(suppress_threshold=3.0, reuse_threshold=1.0,
                       max_penalty=2.0)
        with pytest.raises(ValueError):
            FlapDamper(half_life_s=0.0)


# ---------------------------------------------------------------------------
# OverloadController ladder + admission
# ---------------------------------------------------------------------------


def _ctl(clk, **kw):
    kw.setdefault("queue_watermark", 8)
    kw.setdefault("dwell_s", 5.0)
    return OverloadController("t", clock=clk,
                              damper=FlapDamper(clock=clk), **kw)


class TestOverloadLadder:
    def test_upshift_is_immediate_downshift_one_rung_after_dwell(self):
        clk = Clock()
        c = _ctl(clk)
        assert c.observe(queue_depth=0) == OK
        # straight to shedding in one evaluation — pressure is now
        assert c.observe(queue_depth=16) == SHEDDING
        # clearing the signal does NOT clear the state before dwell
        assert c.observe(queue_depth=0) == SHEDDING
        clk.advance(5.1)
        assert c.observe(queue_depth=0) == BROWNOUT  # one rung, not all
        clk.advance(5.1)
        assert c.observe(queue_depth=0) == BACKPRESSURE
        clk.advance(5.1)
        assert c.observe(queue_depth=0) == OK
        assert c.transitions == 4

    def test_queue_hysteresis_band_holds_borderline_load(self):
        clk = Clock()
        c = _ctl(clk)
        c.observe(queue_depth=4)  # wm//2 -> backpressure
        assert c.level == BACKPRESSURE
        clk.advance(6.0)
        # depth 3 >= wm//4: inside the band, the rung holds
        assert c.observe(queue_depth=3) == BACKPRESSURE
        clk.advance(6.0)
        assert c.observe(queue_depth=1) == OK

    def test_memory_pressure_drives_brownout_with_clear_watermark(self):
        clk = Clock()
        c = _ctl(clk, hbm_high_frac=0.9, hbm_clear_frac=0.75)
        assert c.observe(hbm_frac=0.95) == BROWNOUT
        clk.advance(6.0)
        # below high but above clear: hysteresis holds the rung
        assert c.observe(hbm_frac=0.8) == BROWNOUT
        clk.advance(6.0)
        assert c.observe(hbm_frac=0.5) == BACKPRESSURE
        clk.advance(6.0)
        assert c.observe(hbm_frac=0.5) == OK

    def test_rss_watermark_disabled_at_zero(self):
        clk = Clock()
        c = _ctl(clk, rss_high_mb=0.0)
        assert c.observe(rss_mb=10_000.0) == OK
        c2 = _ctl(clk, rss_high_mb=512.0)
        assert c2.observe(rss_mb=600.0) == BROWNOUT

    def test_slo_burn_alone_means_backpressure(self):
        clk = Clock()
        c = _ctl(clk)
        assert c.observe(slo_burning=True) == BACKPRESSURE
        clk.advance(6.0)
        assert c.observe(slo_burning=False) == OK

    def test_transition_hook_receives_every_transition(self):
        clk = Clock()
        seen = []
        c = OverloadController("t", clock=clk, damper=FlapDamper(clock=clk),
                               on_transition=seen.append)
        c.observe(queue_depth=20)
        clk.advance(6.0)
        c.observe(queue_depth=0)
        assert [(e["from"], e["to"]) for e in seen] == [
            ("ok", "shedding"), ("shedding", "brownout"),
        ]
        assert seen[0]["queue_depth"] == 20

    def test_transition_hook_errors_are_contained(self):
        clk = Clock()

        def boom(entry):
            raise RuntimeError("observer down")

        c = OverloadController("t", clock=clk, damper=FlapDamper(clock=clk),
                               on_transition=boom)
        assert c.observe(queue_depth=20) == SHEDDING  # no raise


class TestAdmissionPriorities:
    def test_live_always_admitted(self):
        clk = Clock()
        c = _ctl(clk)
        c.observe(queue_depth=100)
        assert c.state == "shedding"
        assert c.admit("live") is True

    def test_whatif_rejected_from_brownout_up(self):
        clk = Clock()
        c = _ctl(clk)
        assert c.admit("whatif") is True
        c.observe(queue_depth=4)  # backpressure
        assert c.admit("whatif") is True  # only probes defer here
        c.observe(queue_depth=8)  # brownout
        assert c.admit("whatif") is False
        assert c.rejected_whatif == 1

    def test_probe_deferred_from_backpressure_up(self):
        clk = Clock()
        c = _ctl(clk)
        assert c.admit("probe") is True
        c.observe(queue_depth=4)
        assert c.admit("probe") is False
        assert c.deferred_probes == 1

    def test_coalesce_widens_with_level_and_depth_capped(self):
        clk = Clock()
        c = _ctl(clk, coalesce_max_ms=100)
        assert c.coalesce_ms(10) == 10.0  # steady state: the base
        c.observe(queue_depth=8)  # brownout, depth == wm
        # 10 * (1 + 2 + 8/8) = 40
        assert c.coalesce_ms(10) == pytest.approx(40.0)
        c.observe(queue_depth=100)
        assert c.coalesce_ms(10) == 100.0  # capped
        # zero base still widens from the 1 ms seed under pressure
        assert c.coalesce_ms(0) > 0.0

    def test_shed_only_in_shedding_at_watermark(self):
        clk = Clock()
        c = _ctl(clk)
        c.observe(queue_depth=8)  # brownout
        assert c.shed(8) is False
        c.observe(queue_depth=16)  # shedding
        assert c.shed(16) is True
        assert c.shed(3) is False  # queue drained below wm: admit again
        assert c.shed_epochs == 1
        assert c.still_shedding(16) is True
        assert c.shed_epochs == 1  # passive check never counts

    def test_brownout_rungs_and_counter_export(self):
        clk = Clock()
        c = _ctl(clk)
        assert c.admit("whatif") and c.multichip_allowed()
        c.observe(queue_depth=8)
        assert not c.admit("whatif")
        assert c.multichip_allowed()
        c.observe(queue_depth=16)
        assert not c.multichip_allowed()
        assert counters.get_counter("overload.state") == SHEDDING
        assert counters.get_counter("overload.brownout") == 1
        for field in OVERLOAD_COUNTER_FIELDS:
            assert counters.get_counter(f"overload.{field}") is not None

    def test_registry_roundtrip(self):
        clk = Clock()
        c = _ctl(clk)
        try:
            assert register(c) is c
            assert get_controller("t") is c
        finally:
            unregister("t")
        assert get_controller("t") is None

    def test_report_shape(self):
        clk = Clock()
        c = _ctl(clk)
        c.observe(queue_depth=16)
        rep = c.report()
        assert rep["state"] == "shedding"
        assert rep["state"] in OVERLOAD_STATES
        assert rep["history"][-1]["to"] == "shedding"
        assert rep["damper"]["damped_keys"] == 0


# ---------------------------------------------------------------------------
# chaos drills (slow lane, like test_chaos.py)
# ---------------------------------------------------------------------------


def _flap_cfg(**kw):
    kw.setdefault("debounce_min_ms", 5)
    kw.setdefault("debounce_max_ms", 20)
    kw.setdefault("overload_damping_half_life_s", 0.25)
    kw.setdefault("overload_damping_suppress", 3.0)
    kw.setdefault("overload_damping_reuse", 1.0)
    kw.setdefault("overload_damping_max_penalty", 6.0)
    kw.setdefault("overload_tick_s", 0.05)
    kw.setdefault("overload_dwell_s", 0.1)
    return DecisionConfig(**kw)


def _adj_metric(decision, node: str) -> int:
    dbs = decision.area_link_states[AREA].get_adjacency_databases()
    return dbs[node].adjacencies[0].metric


@pytest.mark.slow
@pytest.mark.chaos
class TestFlapStormDamping:
    @run_async
    async def test_storm_suppresses_then_releases_while_others_converge(
        self,
    ):
        """500 ev/s single-key flap storm: the flapping adjacency is
        suppressed (counted, recorded with the replay `suppressed`
        marker), an undamped key converges mid-storm at full speed, and
        after the half-life release the LSDB holds the key's FINAL
        flapped value — no stale-route window."""
        async with DecisionHarness(config=_flap_cfg()) as h:
            two_node_mesh(h)
            h.synced()
            await h.next_route_update()

            key2, _ = adj_db_kv("2", [adj("2", "1")])
            storm_done = asyncio.Event()

            async def storm():
                # ~500 ev/s for ~0.5 s against node 2's adj key:
                # alternate the metric so every event is a real change
                for i in range(250):
                    _, val = adj_db_kv(
                        "2", [adj("2", "1", metric=10 + (i % 2))],
                        version=10 + i,
                    )
                    h.publish((key2, val))
                    await asyncio.sleep(0.002)
                # final state the release must converge to
                _, val = adj_db_kv("2", [adj("2", "1", metric=42)],
                                   version=1000)
                h.publish((key2, val))
                storm_done.set()

            storm_task = asyncio.create_task(storm())
            await asyncio.sleep(0.1)  # storm past the suppress threshold

            # undamped key converges mid-storm: a brand-new prefix on
            # node 2 must produce a route update while adj:2 is damped
            h.publish(prefix_db_kv("2", "10.0.0.22/32"))
            upd = await h.next_route_update(timeout=5.0)
            while "10.0.0.22/32" not in upd.unicast_routes_to_update:
                upd = await h.next_route_update(timeout=5.0)

            rep = await h.decision.overload_report()
            assert rep["enabled"] and rep["damping_enabled"]
            assert rep["damper"]["damped_keys"] == 1, rep["damper"]
            assert rep["damper"]["suppressed_events"] > 0
            # suppressed while the storm rages: the LSDB still holds a
            # pre-suppression metric, not the churning one
            assert _adj_metric(h.decision, "2") in (1, 10, 11)

            await asyncio.wait_for(storm_done.wait(), 10.0)
            await storm_task

            # half-life release: ~0.25 s half-life from a clamped
            # figure of 6.0 needs ~2.6 half-lives to cross reuse=1.0
            async def released():
                while True:
                    r = await h.decision.overload_report()
                    if r["damper"]["damped_keys"] == 0:
                        return r
                    await asyncio.sleep(0.05)

            r = await asyncio.wait_for(released(), 10.0)
            assert r["damper"]["released_keys"] >= 1
            # no stale-route window: the held FINAL value re-ingested
            assert _adj_metric(h.decision, "2") == 42
            # the replay recorder carries the suppression marker so the
            # incident replays bit-identically (suppressed events are
            # never applied — they did not perturb the live RIB)
            st = h.decision._replay.status()
            assert st["suppressed_events"] > 0
            annex = h.decision._replay.export()
            assert annex is not None
            assert any(e["suppressed"] for e in annex["events"])

    @run_async
    async def test_damping_disabled_leaves_storm_unfiltered(self):
        cfg = _flap_cfg(overload_damping=False)
        async with DecisionHarness(config=cfg) as h:
            two_node_mesh(h)
            h.synced()
            await h.next_route_update()
            key2, _ = adj_db_kv("2", [adj("2", "1")])
            for i in range(10):
                _, val = adj_db_kv(
                    "2", [adj("2", "1", metric=10 + i)], version=10 + i
                )
                h.publish((key2, val))
            await asyncio.sleep(0.2)
            rep = await h.decision.overload_report()
            assert rep["damper"]["damped_keys"] == 0
            assert _adj_metric(h.decision, "2") == 19


@pytest.mark.slow
@pytest.mark.chaos
class TestHbmBrownoutDrill:
    @run_async
    async def test_injected_hbm_pressure_downshifts_and_recovers(self):
        """Injected HBM-pressure brownout: the ladder walks up under
        memory pressure (what-if rejected, the mesh surrendered,
        transition history populated) and back down rung by rung after
        the signal clears — while live convergence keeps working the
        whole way through (no stale-route window)."""
        async with DecisionHarness(config=_flap_cfg()) as h:
            two_node_mesh(h)
            h.synced()
            await h.next_route_update()
            ctl = h.decision._overload
            assert ctl is not None and ctl.state == "ok"

            # the Monitor's feed, compressed: worst-device HBM fraction
            # over the high watermark
            assert ctl.observe(hbm_frac=0.95) == BROWNOUT
            assert ctl.admit("whatif") is False
            assert counters.get_counter("overload.brownout") == 1
            # escalate: memory high AND queue at watermark -> shedding
            ctl.observe(queue_depth=8)
            assert ctl.state == "shedding"
            assert not ctl.multichip_allowed()

            # live convergence still runs while browned out
            h.publish(prefix_db_kv("2", "10.0.0.33/32"))
            upd = await h.next_route_update(timeout=5.0)
            while "10.0.0.33/32" not in upd.unicast_routes_to_update:
                upd = await h.next_route_update(timeout=5.0)

            # recovery: signal clears; the tick loop walks the ladder
            # down one rung per dwell, never snapping. (The starting
            # level may already have stepped once during the awaits
            # above — assert the SHAPE of the walk, not its start.)
            ctl.observe(hbm_frac=0.1, queue_depth=0)
            seen = [ctl.level]

            async def drained():
                while ctl.level != OK:
                    await asyncio.sleep(0.02)
                    if ctl.level != seen[-1]:
                        seen.append(ctl.level)

            await asyncio.wait_for(drained(), 10.0)
            assert seen[0] > OK and seen[-1] == OK, seen
            assert all(a - b == 1 for a, b in zip(seen, seen[1:])), seen
            assert ctl.admit("whatif") and ctl.multichip_allowed()
            rep = await h.decision.overload_report()
            assert [t["to"] for t in rep["history"]][-3:] == [
                "brownout", "backpressure", "ok"
            ]

            # routes stayed live across the whole excursion
            h.publish(prefix_db_kv("2", "10.0.0.44/32"))
            upd = await h.next_route_update(timeout=5.0)
            while "10.0.0.44/32" not in upd.unicast_routes_to_update:
                upd = await h.next_route_update(timeout=5.0)


# ---------------------------------------------------------------------------
# decision-level damping units (tier-1: fast, no storms)
# ---------------------------------------------------------------------------


class TestDecisionDampingUnits:
    @run_async
    async def test_damped_publication_counts_and_records_marker(self):
        async with DecisionHarness(config=_flap_cfg()) as h:
            two_node_mesh(h)
            h.synced()
            await h.next_route_update()
            key2, _ = adj_db_kv("2", [adj("2", "1")])
            for i in range(5):
                _, val = adj_db_kv(
                    "2", [adj("2", "1", metric=10 + i)], version=10 + i
                )
                h.decision.process_publication(
                    Publication(key_vals={key2: val}, area=AREA)
                )
            rep = await h.decision.overload_report()
            assert rep["damper"]["damped_keys"] == 1
            # suppressed events are recorded with the marker
            assert h.decision._replay.status()["suppressed_events"] > 0

    @run_async
    async def test_expiry_of_suppressed_key_is_held_not_applied(self):
        async with DecisionHarness(config=_flap_cfg()) as h:
            two_node_mesh(h)
            h.synced()
            await h.next_route_update()
            key2, _ = adj_db_kv("2", [adj("2", "1")])
            for i in range(4):
                _, val = adj_db_kv(
                    "2", [adj("2", "1", metric=10 + i)], version=10 + i
                )
                h.decision.process_publication(
                    Publication(key_vals={key2: val}, area=AREA)
                )
            # the withdrawal is withheld too: node 2 stays in the LSDB
            h.decision.process_publication(
                Publication(expired_keys=[key2], area=AREA)
            )
            dbs = h.decision.area_link_states[
                AREA
            ].get_adjacency_databases()
            assert "2" in dbs

    @run_async
    async def test_overload_disabled_runs_clean(self):
        cfg = DecisionConfig(
            debounce_min_ms=5, debounce_max_ms=20, overload_control=False
        )
        async with DecisionHarness(config=cfg) as h:
            two_node_mesh(h)
            h.synced()
            upd = await h.next_route_update()
            assert "10.0.0.2/32" in upd.unicast_routes_to_update
            rep = await h.decision.overload_report()
            assert rep == {"node": "1", "enabled": False}
