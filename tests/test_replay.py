"""Deterministic incident replay (ISSUE 18) — recorder + digests + harness.

A RIB is a deterministic function of the ordered LSDB event stream plus
config, so the black-box recorder's promise is exact: a recorded session
must replay through the real Decision ingest path to bit-identical
per-epoch RIB digests, an injected divergence must bisect to its first
divergent epoch, and a chaos drill (mid-flight solver failover) must
record a session that STILL replays bit-identically on the CPU oracle —
the digest is over semantic route content, not solver internals. The
flight recorder's on-disk retention (satellite) is pinned here too.
"""

import json

import pytest

from openr_tpu.config import DecisionConfig, MonitorConfig
from openr_tpu.decision.rib import (
    DecisionRouteUpdate,
    NextHop,
    RibUnicastEntry,
)
from openr_tpu.decision.rib_digest import (
    GENESIS,
    as_counter_value,
    delta_digest,
    roll,
)
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.faults import registry
from openr_tpu.runtime.monitor import FlightRecorder
from openr_tpu.types import prefix_key
from tests.conftest import run_async
from tests.test_decision import (
    AREA,
    DecisionHarness,
    adj,
    adj_db_kv,
    prefix_db_kv,
    two_node_mesh,
)
from tools.replay import load_bundle, replay_bundle


def _cnt(key):
    return int(counters.get_counter(key) or 0)


# -- digest unit semantics -------------------------------------------------


def _entry(prefix: str, cost: int, *vias: str) -> RibUnicastEntry:
    return RibUnicastEntry(
        prefix=prefix,
        nexthops=frozenset(
            NextHop(
                address="", if_name=f"if-me-{v}", neighbor_node_name=v
            )
            for v in vias
        ),
        igp_cost=cost,
    )


class TestRibDigest:
    def test_digest_is_order_insensitive_and_content_sensitive(self):
        a = DecisionRouteUpdate(
            unicast_routes_to_update={
                "10.0.0.2/32": _entry("10.0.0.2/32", 3, "b", "c"),
                "10.1.0.0/24": _entry("10.1.0.0/24", 7, "b"),
            },
            unicast_routes_to_delete=["10.9.0.0/24", "10.8.0.0/24"],
        )
        # same content, reversed insertion/delete order: same digest
        b = DecisionRouteUpdate(
            unicast_routes_to_update={
                "10.1.0.0/24": _entry("10.1.0.0/24", 7, "b"),
                "10.0.0.2/32": _entry("10.0.0.2/32", 3, "c", "b"),
            },
            unicast_routes_to_delete=["10.8.0.0/24", "10.9.0.0/24"],
        )
        assert delta_digest(a) == delta_digest(b)
        # a cost change, a nexthop change, and a delete change each move
        # the digest — the divergence signal is content-addressed
        c = DecisionRouteUpdate(
            unicast_routes_to_update={
                "10.0.0.2/32": _entry("10.0.0.2/32", 4, "b", "c"),
                "10.1.0.0/24": _entry("10.1.0.0/24", 7, "b"),
            },
            unicast_routes_to_delete=["10.9.0.0/24", "10.8.0.0/24"],
        )
        assert delta_digest(a) != delta_digest(c)
        d = DecisionRouteUpdate(
            unicast_routes_to_update={
                "10.0.0.2/32": _entry("10.0.0.2/32", 3, "b"),
                "10.1.0.0/24": _entry("10.1.0.0/24", 7, "b"),
            },
            unicast_routes_to_delete=["10.9.0.0/24", "10.8.0.0/24"],
        )
        assert delta_digest(a) != delta_digest(d)
        e = DecisionRouteUpdate(
            unicast_routes_to_update=dict(a.unicast_routes_to_update),
            unicast_routes_to_delete=["10.9.0.0/24"],
        )
        assert delta_digest(a) != delta_digest(e)

    def test_rolling_chain_and_counter_projection(self):
        d1 = delta_digest(DecisionRouteUpdate(
            unicast_routes_to_update={
                "10.0.0.2/32": _entry("10.0.0.2/32", 3, "b")
            },
        ))
        r1 = roll(GENESIS, d1)
        assert r1 != d1 and r1 != GENESIS
        # deterministic and order-dependent: the rolling hash encodes
        # the epoch SEQUENCE, not the multiset of epochs
        assert roll(GENESIS, d1) == r1
        assert roll(r1, d1) != r1
        # the counter projection is gauge-safe: < 2**48 representable
        # exactly in the registry's float64 cells
        v = as_counter_value(d1)
        assert 0 <= v < 2 ** 48
        assert int(float(v)) == v


# -- record -> replay through the real Decision ingest path ----------------


async def _churned_session(h: DecisionHarness, rounds: int = 3):
    """Drive metric flaps + a prefix advertise/withdraw through the
    harness, one awaited route update per epoch; returns the annex."""
    two_node_mesh(h)
    h.synced()
    await h.next_route_update()
    version = 1
    for m in (5, 9, 3)[:rounds]:
        version += 1
        h.publish(
            adj_db_kv("1", [adj("1", "2", metric=m)], version=version),
            adj_db_kv("2", [adj("2", "1", metric=m)], version=version),
        )
        await h.next_route_update()
    h.publish(prefix_db_kv("2", "10.5.0.0/24"))
    await h.next_route_update()
    h.expire(prefix_key("2", AREA, "10.5.0.0/24"))
    await h.next_route_update()
    rec = h.decision._replay
    assert rec is not None, "recorder off despite replay_recorder=True"
    annex = rec.export()
    assert annex is not None and not annex["gap"], annex
    return annex


class TestRecordReplay:
    @run_async
    async def test_recorded_session_replays_bit_identically(self):
        async with DecisionHarness() as h:
            annex = await _churned_session(h)
        # the session stamped digests into the counter fabric
        assert _cnt("decision.rib_digest.epoch") >= 1
        assert _cnt("replay.events") >= 1
        report = replay_bundle({"node": "1", "inputs": annex})
        assert report["status"] == "identical", report
        # anchor epoch is the baseline (not compared); every churn epoch
        # after it is
        assert report["epochs_compared"] >= 4, report

    @run_async
    async def test_a_bundle_of_an_older_build_still_replays(self):
        """A bundle recorded before PR 46 names the streaming pipeline in
        its meta and a `stream` word in every epoch: both are ignored."""
        async with DecisionHarness() as h:
            annex = await _churned_session(h)
        bundle = json.loads(json.dumps({"node": "1", "inputs": annex}))
        bundle["inputs"]["meta"]["streaming_pipeline"] = True
        for ep in bundle["inputs"]["epochs"]:
            ep["stream"] = {"epochs": 1, "changed_rows": 3, "overflows": 0}
        report = replay_bundle(bundle)
        assert report["status"] == "identical", report
        assert report["epochs_compared"] >= 4, report

    @run_async
    async def test_injected_divergence_bisects_to_tampered_epoch(self):
        async with DecisionHarness() as h:
            annex = await _churned_session(h)
        bundle = json.loads(json.dumps({"node": "1", "inputs": annex}))
        comparable = [
            e for e in bundle["inputs"]["epochs"]
            if e["cursor"] > bundle["inputs"]["snapshot"]["cursor"]
        ]
        assert len(comparable) >= 3
        victim = comparable[1]
        victim["digest"] = (
            "f" * 16 if victim["digest"] != "f" * 16 else "0" * 16
        )
        report = replay_bundle(bundle)
        assert report["status"] == "diverged", report
        fd = report["first_divergent"]
        assert fd["epoch"] == victim["epoch"], (fd, victim)
        # the bisection hands triage its context: what solved the epoch
        # and which keys fed it
        assert fd["solver_kind"] and fd["spf_kernel"], fd

    @run_async
    async def test_ring_gap_counts_reanchors_and_gapped_annex_refused(
        self,
    ):
        """A ring too small to hold the window back to the snapshot
        anchor counts replay.ring_gaps and SELF-HEALS by re-anchoring a
        fresh snapshot at the next solve — so the final export is
        replayable again, just over a shorter window. A still-gapped
        annex, were one captured mid-hole, is REFUSED by replay: a hole
        silently replayed would be a false divergence verdict."""
        cfg = DecisionConfig(
            debounce_min_ms=5, debounce_max_ms=20,
            replay_ring=4, replay_snapshot_every_epochs=1024,
        )
        gaps0 = _cnt("replay.ring_gaps")
        async with DecisionHarness(config=cfg) as h:
            two_node_mesh(h)
            h.synced()
            await h.next_route_update()
            version = 1
            for m in (5, 9, 3, 8, 2):
                version += 1
                h.publish(
                    adj_db_kv("1", [adj("1", "2", metric=m)],
                              version=version),
                    adj_db_kv("2", [adj("2", "1", metric=m)],
                              version=version),
                )
                await h.next_route_update()
            annex = h.decision._replay.export()
        assert _cnt("replay.ring_gaps") > gaps0
        # self-healed: re-anchored snapshot, replayable shorter window
        assert annex is not None and not annex["gap"], annex
        report = replay_bundle({"node": "1", "inputs": annex})
        assert report["status"] == "identical", report
        # a mid-hole capture (gap flag up) must be refused outright
        gapped = json.loads(json.dumps({"node": "1", "inputs": annex}))
        gapped["inputs"]["gap"] = True
        refused = replay_bundle(gapped)
        assert refused["status"] == "unreplayable", refused


# -- flight-recorder bundle roundtrip + on-disk retention ------------------


class TestFlightRecorderBundles:
    @run_async
    async def test_bundle_inputs_annex_replays_via_load_bundle(self):
        import tempfile

        async with DecisionHarness() as h:
            annex = await _churned_session(h)
        with tempfile.TemporaryDirectory() as td:
            fr = FlightRecorder("1", MonitorConfig(
                flight_recorder_dir=td,
            ))
            record = fr.trigger(
                "drill", {"test": True}, extra={"inputs": annex},
                force=True,
            )
            assert record is not None
            bundle = load_bundle(record["path"])
            report = replay_bundle(bundle)
            assert report["status"] == "identical", report

    def test_on_disk_retention_prunes_to_keep(self):
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            cfg = MonitorConfig(
                flight_recorder_dir=td, flight_recorder_keep=2,
                flight_recorder_min_interval_s=0.0,
            )
            fr = FlightRecorder("reten", cfg)
            pruned0 = _cnt("monitor.flight_recorder.pruned")
            paths = []
            for i in range(4):
                rec = fr.trigger(f"r{i}", {}, force=True)
                assert rec is not None
                paths.append(rec["path"])
            listing = fr.list_bundles()
            assert listing["keep"] == 2
            assert len(listing["disk"]) == 2, listing
            assert _cnt("monitor.flight_recorder.pruned") == pruned0 + 2
            kept = {b["path"] for b in listing["disk"]}
            # the newest bundle always survives retention
            assert paths[-1] in kept, (paths, kept)
            assert all(b["replayable"] for b in listing["disk"])
            # the in-memory record ring still remembers all four
            assert len(listing["memory"]) == 4

    def test_keep_zero_is_unbounded(self):
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            cfg = MonitorConfig(
                flight_recorder_dir=td, flight_recorder_keep=0,
                flight_recorder_min_interval_s=0.0,
            )
            fr = FlightRecorder("unbnd", cfg)
            for i in range(3):
                assert fr.trigger(f"r{i}", {}, force=True) is not None
            assert len(fr.list_bundles()["disk"]) == 3


# -- chaos drill: failover session replays on the oracle -------------------


@pytest.mark.chaos
class TestFailoverDrillReplay:
    @run_async
    async def test_solver_failover_drill_replays_bit_identically(self):
        """Arm solver.exec so a churn epoch takes the mid-flight
        CPU-failover lane on the TPU backend, keep churning, then
        replay the recorded session on the plain CPU oracle: every
        epoch digest — the failover-cpu one included — must replay
        bit-identically, because the digest fingerprints route CONTENT
        and the failover lane's parity promise says content matches."""
        registry.clear()
        cfg = DecisionConfig(debounce_min_ms=5, debounce_max_ms=20)
        try:
            async with DecisionHarness(backend="tpu", config=cfg) as h:
                two_node_mesh(h)
                h.synced()
                await h.next_route_update()
                registry.arm("solver.exec", every_nth=1, max_fires=1)
                version = 1
                for m in (9, 4, 17):
                    version += 1
                    h.publish(
                        adj_db_kv("1", [adj("1", "2", metric=m)],
                                  version=version),
                        adj_db_kv("2", [adj("2", "1", metric=m)],
                                  version=version),
                    )
                    await h.next_route_update()
                annex = h.decision._replay.export()
        finally:
            registry.clear()
        assert annex is not None and not annex["gap"]
        kinds = {e["solver_kind"] for e in annex["epochs"]}
        assert "failover-cpu" in kinds, kinds
        report = replay_bundle({"node": "1", "inputs": annex})
        assert report["status"] == "identical", report
        assert report["epochs_compared"] >= 2, report
