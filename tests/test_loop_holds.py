"""The tracer's background track (runtime/tracing.py): holds of the event
loop that belong to no event — the collector, KvStore's digest beacon, the
flap damper's sweep — and the heartbeat's lag probe for the ones nobody
named.

The tracer and the gc.callbacks hook are process-global; every test here
leaves both as it found them (`own_track`). Times are lower bounds with
slack: the suite runs six workers wide.
"""

import asyncio
import gc
import threading
import time

import pytest

from openr_tpu.config import DecisionConfig, KvstoreConfig
from openr_tpu.messaging import ReplicateQueue
from openr_tpu.runtime import tracing
from openr_tpu.runtime.actor import Actor
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.tracing import Tracer, tracer
from openr_tpu.types import Value
from tests.conftest import run_async
from tests.test_decision import DecisionHarness

GC_COUNTERS = (
    "runtime.gc.collections", "runtime.gc.pause_ms",
    "runtime.gc.collections.gen2", "runtime.gc.pause_ms.gen2",
)


@pytest.fixture
def own_track():
    """The process-wide tracer with an empty track, and gc.callbacks as
    it was, whatever the test (or an actor it starts) hangs on it."""
    callbacks = list(gc.callbacks)
    enabled = tracer.enabled
    tracer.drain_gc()
    tracer.clear()
    tracer.configure(enabled=True)
    yield tracer
    tracer.drain_gc()
    tracer.clear()
    tracer.configure(enabled=enabled)
    gc.callbacks[:] = callbacks


def longest(holds, name: str) -> float:
    return max(
        (h["duration_ms"] for h in holds if h["name"] == name), default=0.0
    )


class Idler(Actor):
    """An actor with nothing but its heartbeat."""


# -- the ring ---------------------------------------------------------------


class TestHoldRing:
    def test_a_hold_is_kept_as_a_span_of_no_trace(self):
        t = Tracer()
        with t.hold("kvstore.digest", areas=1) as h:
            h.set(keys=7)
        (got,) = t.get_holds()
        assert got["name"] == "kvstore.digest"
        assert got["trace_id"] == 0 and got["parent_id"] is None
        assert got["attributes"] == {"areas": 1, "keys": 7}
        assert got["end"] >= got["start"] and got["duration_ms"] >= 0.0
        assert got["thread"] == threading.current_thread().name
        assert t.get_traces() == []

    def test_the_ring_is_bounded_and_counts_what_it_drops(self, monkeypatch):
        monkeypatch.setattr(tracing, "MAX_HOLDS", 4)
        t = Tracer()
        dropped = counters.get_counter("tracing.holds_dropped") or 0
        for i in range(7):
            t.record_hold("x", float(i), i + 0.5, i=i)
        assert [h["attributes"]["i"] for h in t.get_holds()] == [3, 4, 5, 6]
        assert counters.get_counter("tracing.holds_dropped") == dropped + 3

    @pytest.mark.parametrize("since,until,want", [
        (None, None, [0, 1, 2, 3]),
        (1.2, None, [1, 2, 3]),      # a hold that straddles `since` counts
        (None, 2.0, [0, 1]),         # one that starts at `until` does not
        (1.6, 2.9, [2]),
        (9.0, None, []),
    ])
    def test_get_holds_filters_by_time(self, since, until, want):
        t = Tracer()
        for i in range(4):
            t.record_hold("x", float(i), i + 0.5, i=i)
        got = t.get_holds(since=since, until=until)
        assert [h["attributes"]["i"] for h in got] == want

    def test_a_disabled_tracer_takes_the_null_path(self):
        t = Tracer()
        t.configure(enabled=False)
        with t.hold("kvstore.digest") as h:
            assert h is None
        assert t.record_hold("x", 1.0, 2.0) is None
        t.note_loop_lag("a", 1.0, 2.0)
        assert t.get_holds() == []

    def test_clear_empties_the_track(self):
        t = Tracer()
        t.record_hold("x", 1.0, 2.0)
        t.clear()
        assert t.get_holds() == []

    def test_detach_is_gone(self):
        assert not hasattr(Tracer, "detach")


# -- copies into the traces a hold delayed ------------------------------------


class TestCopyIntoTraces:
    def test_copied_clipped_into_a_trace_active_at_its_end(self):
        t = Tracer()
        t0 = time.monotonic()
        ctx = t.start_trace("convergence", start=t0)
        t.record_hold("runtime.gc", t0 - 0.5, t0 + 0.25, generation=2)
        t.end_trace(ctx)
        (tr,) = t.get_traces()
        root, copy = tr["spans"]
        assert copy["name"] == "runtime.gc"
        assert copy["parent_id"] == root["span_id"]
        assert copy["trace_id"] == root["trace_id"]
        assert copy["attributes"] == {"generation": 2, "hold": True}
        # clipped to the trace's start: the trace is charged what it bore
        assert copy["start"] == t0
        assert copy["duration_ms"] == pytest.approx(250.0)
        # the ring keeps the hold whole
        (whole,) = t.get_holds()
        assert whole["duration_ms"] == pytest.approx(750.0)
        assert "hold" not in whole["attributes"]

    def test_not_copied_into_a_trace_closed_before_it_began(self):
        t = Tracer()
        closed = t.start_trace("convergence")
        t.end_trace(closed)
        time.sleep(0.002)
        with t.hold("decision.damper_sweep"):
            pass
        (tr,) = t.get_traces()
        assert [s["name"] for s in tr["spans"]] == ["convergence"]

    def test_copied_clipped_into_a_trace_that_closed_after_it_began(self):
        """What the lag probe finds, it finds after the trace it delayed
        has closed (Fib acks before the late heartbeat runs)."""
        t = Tracer()
        t0 = time.monotonic()
        ctx = t.start_trace("convergence", start=t0 - 1.0)
        t.end_trace(ctx)
        (closed,) = t.get_traces()
        ended = closed["spans"][0]["end"]
        t.record_hold("runtime.unnamed_hold", t0 - 0.5, ended + 5.0)
        (tr,) = t.get_traces()
        (copy,) = tr["spans"][1:]
        assert copy["name"] == "runtime.unnamed_hold"
        assert copy["attributes"]["hold"] is True
        assert copy["start"] == t0 - 0.5 and copy["end"] == ended

    def test_not_copied_into_a_trace_that_began_after_it_ended(self):
        t = Tracer()
        now = time.monotonic()
        ctx = t.start_trace("convergence", start=now)
        t.record_hold("x", now - 2.0, now - 1.0)
        t.end_trace(ctx)
        assert t.get_traces()[0]["num_spans"] == 1

    def test_copied_into_every_trace_it_overlapped(self):
        t = Tracer()
        a = t.start_trace("convergence")
        b = t.start_trace("convergence")
        with t.hold("kvstore.digest"):
            pass
        t.end_trace(a)
        t.end_trace(b)
        assert [
            [s["name"] for s in tr["spans"]] for tr in t.get_traces()
        ] == [["convergence", "kvstore.digest"]] * 2
        assert len(t.get_holds()) == 1

    def test_a_lingering_trace_takes_no_more_than_its_share(
        self, monkeypatch
    ):
        monkeypatch.setattr(tracing, "MAX_HOLD_COPIES", 3)
        t = Tracer()
        ctx = t.start_trace("convergence")
        for _ in range(5):
            with t.hold("decision.damper_sweep"):
                pass
        t.end_trace(ctx)
        assert t.get_traces()[0]["num_spans"] == 1 + 3
        assert len(t.get_holds()) == 5

    def test_export_chrome_carries_the_lane(self):
        t = Tracer()
        ctx = t.start_trace("convergence", node="n0")
        with t.hold("kvstore.digest", keys=3):
            pass
        t.end_trace(ctx)
        doc = t.export_chrome()
        lanes = {
            e["args"]["name"]: e["pid"] for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert set(lanes) == {"n0", "loop holds"}
        by_lane = {
            pid: [e for e in doc["traceEvents"]
                  if e["ph"] == "X" and e["pid"] == pid]
            for pid in lanes.values()
        }
        (hold,) = by_lane[lanes["loop holds"]]
        assert hold["name"] == "kvstore.digest" and hold["cat"] == "hold"
        assert hold["args"]["keys"] == 3 and hold["args"]["trace_id"] == 0
        # and the trace's own lane shows the copy, marked
        copy = [e for e in by_lane[lanes["n0"]]
                if e["name"] == "kvstore.digest"]
        assert len(copy) == 1 and copy[0]["args"]["hold"] is True

    def test_export_of_one_trace_leaves_out_the_holds_outside_it(self):
        t = Tracer()
        t.record_hold("x", 1.0, 2.0)  # long before
        ctx = t.start_trace("convergence")
        with t.hold("y"):
            pass
        t.end_trace(ctx)
        doc = t.export_chrome(trace_id=ctx.trace_id)
        held = [e["name"] for e in doc["traceEvents"]
                if e["ph"] == "X" and e.get("cat") == "hold"]
        assert held == ["y"]


# -- the collector ------------------------------------------------------------


class TestCollector:
    def test_a_collection_moves_the_four_counters(self, own_track):
        own_track.watch_gc()
        before = [counters.get_counter(k) or 0 for k in GC_COUNTERS]
        gc.collect()  # generation 2
        own_track.drain_gc()
        after = [counters.get_counter(k) or 0 for k in GC_COUNTERS]
        assert after[0] >= before[0] + 1 and after[2] >= before[2] + 1
        assert after[1] > before[1] and after[3] > before[3]
        # the twins count the oldest generation alone
        gc.collect(0)
        own_track.drain_gc()
        again = [counters.get_counter(k) or 0 for k in GC_COUNTERS]
        assert again[0] >= after[0] + 1 and again[2] == after[2]

    def test_a_pause_past_the_constant_leaves_a_span(
        self, own_track, monkeypatch
    ):
        monkeypatch.setattr(tracing, "GC_HOLD_MIN_S", 0.0)
        own_track.watch_gc()
        gc.collect()
        holds = [h for h in own_track.get_holds()
                 if h["name"] == "runtime.gc"]
        assert holds, own_track.get_holds()
        oldest = [h for h in holds if h["attributes"]["generation"] == 2]
        assert oldest and oldest[-1]["attributes"]["collected"] >= 0
        assert oldest[-1]["attributes"]["thread"] == (
            threading.current_thread().name
        )
        assert oldest[-1]["thread"] == threading.current_thread().name

    def test_a_short_pause_is_counted_and_leaves_no_span(
        self, own_track, monkeypatch
    ):
        monkeypatch.setattr(tracing, "GC_HOLD_MIN_S", 3600.0)
        own_track.watch_gc()
        before = counters.get_counter("runtime.gc.collections") or 0
        gc.collect()
        assert own_track.get_holds() == []
        assert counters.get_counter("runtime.gc.collections") > before

    def test_a_pause_inside_a_trace_is_copied_before_the_trace_closes(
        self, own_track, monkeypatch
    ):
        monkeypatch.setattr(tracing, "GC_HOLD_MIN_S", 0.0)
        own_track.watch_gc()
        ctx = own_track.start_trace("convergence")
        gc.collect()
        own_track.end_trace(ctx)  # no heartbeat, no reader in between
        (tr,) = own_track.get_traces(trace_id=ctx.trace_id)
        copies = [s for s in tr["spans"] if s["name"] == "runtime.gc"]
        assert copies and all(s["attributes"]["hold"] for s in copies)

    def test_the_hook_takes_no_lock(self, own_track):
        """A collection can start while this thread holds the tracer's
        lock or the counter registry's; neither is re-entrant."""
        own_track.watch_gc()
        done = []

        def collect_under_the_locks():
            with own_track._lock, counters._lock:
                gc.collect()
            done.append(True)

        worker = threading.Thread(
            target=collect_under_the_locks, daemon=True
        )
        worker.start()
        worker.join(timeout=10.0)
        assert done and not worker.is_alive()

    @run_async
    async def test_the_hook_is_installed_once_however_many_actors_start(
        self, own_track
    ):
        actors = [Idler(f"idler{i}") for i in range(3)]
        for a in actors:
            await a.start()
        try:
            hooks = [cb for cb in gc.callbacks if cb == own_track._on_gc]
            assert len(hooks) == 1
        finally:
            for a in actors:
                await a.stop()

    def test_the_counters_are_known_to_the_name_lint(self):
        from tools.lint import metric_names
        from tools.lint.core import REPO_ROOT, Project

        found_counters, found_stats = metric_names.collect(
            Project(REPO_ROOT, ["openr_tpu/runtime"])
        )
        assert set(GC_COUNTERS) <= set(found_counters)
        assert "tracing.holds_dropped" in found_counters
        assert "runtime.loop_lag_ms" in found_stats
        assert metric_names.run(Project(REPO_ROOT, ["openr_tpu"])) == []


# -- the heartbeat's lag probe --------------------------------------------------


async def block_the_loop(seconds: float) -> None:
    time.sleep(seconds)


class TestLagProbe:
    @run_async
    async def test_every_beat_stamps_the_lag(self, own_track):
        counters.erase("runtime.loop_lag_ms")
        a = Idler("idler")
        await a.start()
        try:
            await asyncio.sleep(0.35)
        finally:
            await a.stop()
        stat = counters.get_statistics("runtime.loop_lag_ms")
        assert stat["runtime.loop_lag_ms"]["60"]["count"] >= 3

    @run_async
    async def test_a_blocked_loop_leaves_an_unnamed_hold(self, own_track):
        a = Idler("idler")
        await a.start()
        try:
            await asyncio.sleep(0.15)  # a beat or two on time
            assert longest(
                own_track.get_holds(), "runtime.unnamed_hold"
            ) == 0.0
            await block_the_loop(0.3)
            await asyncio.sleep(0.15)
        finally:
            await a.stop()
        holds = [h for h in own_track.get_holds()
                 if h["name"] == "runtime.unnamed_hold"]
        assert longest(holds, "runtime.unnamed_hold") >= 150.0
        worst = max(holds, key=lambda h: h["duration_ms"])
        assert worst["attributes"]["actor"] == "idler"
        # the lag is what the beat saw; the hold is the part nothing named
        assert worst["attributes"]["lag_ms"] >= worst["duration_ms"] - 1e-6

    @run_async
    async def test_the_same_block_inside_a_hold_is_named(self, own_track):
        a = Idler("idler")
        await a.start()
        try:
            await asyncio.sleep(0.15)
            with own_track.hold("x"):
                await block_the_loop(0.3)
            await asyncio.sleep(0.15)
        finally:
            await a.stop()
        holds = own_track.get_holds()
        assert longest(holds, "x") >= 290.0
        assert longest(holds, "runtime.unnamed_hold") <= 50.0

    @run_async
    async def test_the_same_block_inside_a_stage_span_is_work(
        self, own_track
    ):
        a = Idler("idler")
        await a.start()
        try:
            await asyncio.sleep(0.15)
            ctx = own_track.start_trace("convergence")
            with own_track.span(ctx, "decision.rib_diff"):
                await block_the_loop(0.3)
            await asyncio.sleep(0.15)
            own_track.end_trace(ctx)
        finally:
            await a.stop()
        assert longest(
            own_track.get_holds(), "runtime.unnamed_hold"
        ) <= 50.0

    @run_async
    async def test_a_just_closed_trace_still_covers(self, own_track):
        a = Idler("idler")
        await a.start()
        try:
            await asyncio.sleep(0.15)
            ctx = own_track.start_trace("convergence")
            with own_track.span(ctx, "decision.rib_diff"):
                await block_the_loop(0.3)
            own_track.end_trace(ctx)  # closed before the late beat runs
            await asyncio.sleep(0.15)
        finally:
            await a.stop()
        assert longest(
            own_track.get_holds(), "runtime.unnamed_hold"
        ) <= 50.0

    @run_async
    async def test_a_wait_span_covers_nothing(self, own_track):
        """decision.debounce times a wait with the loop free: a hold
        that stretches it must not hide in it."""
        a = Idler("idler")
        await a.start()
        try:
            await asyncio.sleep(0.15)
            ctx = own_track.start_trace("convergence")
            t0 = time.monotonic()
            await block_the_loop(0.3)
            own_track.record_span(
                ctx, "decision.debounce", t0, time.monotonic(), wait=True
            )
            await asyncio.sleep(0.15)
            own_track.end_trace(ctx)
        finally:
            await a.stop()
        assert longest(
            own_track.get_holds(), "runtime.unnamed_hold"
        ) >= 150.0
        # and the trace it delayed says so itself
        (tr,) = own_track.get_traces(trace_id=ctx.trace_id)
        copies = [s for s in tr["spans"]
                  if s["name"] == "runtime.unnamed_hold"]
        assert copies and copies[0]["attributes"]["hold"] is True

    @run_async
    async def test_a_span_of_another_thread_covers_nothing(self, own_track):
        a = Idler("idler")
        await a.start()
        try:
            await asyncio.sleep(0.15)
            ctx = own_track.start_trace("convergence")
            t0 = time.monotonic()
            await block_the_loop(0.3)
            t1 = time.monotonic()
            worker = threading.Thread(
                target=own_track.record_span,
                args=(ctx, "tpu.mat", t0, t1), name="rib-mat",
            )
            worker.start()
            worker.join(timeout=10.0)
            await asyncio.sleep(0.15)
            own_track.end_trace(ctx)
        finally:
            await a.stop()
        assert longest(
            own_track.get_holds(), "runtime.unnamed_hold"
        ) >= 150.0

    def test_lingering_traces_cost_the_probe_nothing(self):
        """Traces that never close (no Fib to ack them) stay active with
        all their spans; a late beat passes them over at a glance, or many
        actors on a busy loop would spend the loop on looking."""
        t = Tracer()
        for _ in range(200):
            ctx = t.start_trace("convergence")
            for _ in range(200):
                t.record_span(ctx, "decision.spf", 1.0, 2.0)
        t0 = time.monotonic()
        for i in range(200):
            now = time.monotonic()
            t.note_loop_lag(f"actor{i}", now - 0.05, now)
        assert time.monotonic() - t0 < 1.0  # 8 M span looks would take 2 s
        assert len(t.get_holds()) >= 1

    @run_async
    async def test_three_actors_name_one_hold_once(self, own_track):
        actors = [Idler(f"idler{i}") for i in range(3)]
        for a in actors:
            await a.start()
            await asyncio.sleep(0.02)  # beats out of phase
        try:
            await asyncio.sleep(0.15)
            await block_the_loop(0.3)
            await asyncio.sleep(0.15)
        finally:
            for a in actors:
                await a.stop()
        spans = sorted(
            (h["start"], h["end"]) for h in own_track.get_holds()
            if h["name"] == "runtime.unnamed_hold"
        )
        # each later beat adds only what the earlier ones had not seen
        for (_, end), (start, _) in zip(spans, spans[1:]):
            assert start >= end - 1e-9
        assert sum(e - s for s, e in spans) <= 0.45
        assert sum(e - s for s, e in spans) >= 0.15


# -- the two named holders in the program ----------------------------------------


class TestNamedHolders:
    @run_async
    async def test_a_digest_beat_leaves_kvstore_digest(self, own_track):
        from openr_tpu.kvstore.kvstore import KvStore

        q = {n: ReplicateQueue(n) for n in
             ("peerUpdates", "kvRequests", "kvStoreUpdates", "kvStoreEvents")}
        store = KvStore(
            "n0",
            KvstoreConfig(enable_lsdb_digest=True, digest_interval_s=0.05),
            ["0"], q["peerUpdates"].get_reader(), q["kvRequests"].get_reader(),
            q["kvStoreUpdates"], q["kvStoreEvents"],
        )
        await store.start()
        try:
            await store.set_key_vals("0", {
                f"adj:r{i}": Value(
                    version=1, originator_id=f"r{i}", value=b"x"
                ) for i in range(5)
            })
            for _ in range(100):
                beats = [h for h in own_track.get_holds()
                         if h["name"] == "kvstore.digest"]
                if beats:
                    break
                await asyncio.sleep(0.02)
        finally:
            for queue in q.values():
                queue.close()
            await store.stop()
        assert beats
        attrs = beats[0]["attributes"]
        # the beacon's own key is telemetry, outside the digest
        assert attrs["keys"] == 5 and attrs["areas"] == 1
        # one to advertise, one to compare: the perf_opt PR that makes
        # it one shows here
        assert attrs["digests"] == 2

    @run_async
    async def test_a_damper_sweep_leaves_decision_damper_sweep(
        self, own_track
    ):
        async with DecisionHarness(config=DecisionConfig(
            debounce_min_ms=5, debounce_max_ms=20, overload_tick_s=0.05,
        )) as h:
            damper = h.decision._overload.damper
            for i in range(4):
                damper.record_change("0", f"adj:r{i}")
            h.decision._release_damped()
            sweeps = [s for s in own_track.get_holds()
                      if s["name"] == "decision.damper_sweep"]
            assert sweeps[-1]["attributes"] == {"records": 4, "released": 0}
            # and the tick loop does the same on its own clock
            for _ in range(100):
                if len([s for s in own_track.get_holds()
                        if s["name"] == "decision.damper_sweep"]) > 1:
                    break
                await asyncio.sleep(0.02)
            else:
                pytest.fail("the overload tick left no sweep")

    @run_async
    async def test_the_debounce_span_is_marked_a_wait(self, own_track):
        from openr_tpu.types import Publication
        from tests.test_decision import AREA, adj, adj_db_kv, two_node_mesh

        async with DecisionHarness() as h:
            two_node_mesh(h)
            h.synced()
            await h.next_route_update()
            ctx = own_track.start_trace("convergence")
            h.kv_q.push(Publication(key_vals=dict([
                adj_db_kv("1", [adj("1", "2", metric=7)], version=2),
            ]), area=AREA), trace=ctx)
            await h.next_route_update()
        (tr,) = own_track.get_traces(
            trace_id=ctx.trace_id, include_active=True
        )
        (debounce,) = [s for s in tr["spans"]
                       if s["name"] == "decision.debounce"]
        assert debounce["attributes"]["wait"] is True
