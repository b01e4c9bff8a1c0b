"""Boot-to-first-RIB lifecycle tracer tests (ISSUE 14 tentpole).

Unit tests pin the BootTracer contract (gapless phase tiling, node
gating, the phase() extra-dict, completion gauges, reset semantics);
the system test cold-starts a two-node stack and asserts the boot span
tree runs end-to-end — kvstore initial sync through the first
programmed RIB — with the ``boot.first_rib_ms`` headline stamped.
"""

import time

from openr_tpu.kvstore.wrapper import wait_until
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.lifecycle import BOOT_PHASES, BootTracer, boot_tracer
from openr_tpu.runtime.openr_wrapper import OpenrWrapper
from openr_tpu.runtime.tracing import tracer
from openr_tpu.spark import MockIoMesh
from tests.conftest import run_async

CONVERGENCE_S = 20.0


class TestBootTracerUnit:
    def test_report_disabled_before_begin(self):
        bt = BootTracer()
        assert bt.report() == {"enabled": False, "phases": []}
        assert bt.active() is False
        # stamps before begin are silently dropped, not errors
        bt.phase_mark("config_load")
        bt.complete()
        assert bt.report() == {"enabled": False, "phases": []}

    def test_phase_marks_tile_the_timeline(self):
        """Retroactive phase_mark spans previous-phase-end -> now: the
        phases tile the boot wall-clock with no gaps or overlaps."""
        bt = BootTracer()
        bt.begin("node-a")
        time.sleep(0.01)
        bt.phase_mark("config_load")
        time.sleep(0.01)
        bt.phase_mark("device_init")
        rep = bt.report()
        phases = rep["phases"]
        assert [p["name"] for p in phases] == ["config_load", "device_init"]
        assert phases[0]["start_ms"] == 0.0
        assert phases[0]["duration_ms"] > 0.0
        # contiguous: the second phase starts where the first ended
        end0 = phases[0]["start_ms"] + phases[0]["duration_ms"]
        assert abs(phases[1]["start_ms"] - end0) < 0.01
        bt.reset()

    def test_begin_backdates_over_prior_work(self):
        """`start=` backdates the root so config-load time (spent before
        the node name was even known) is still attributed."""
        bt = BootTracer()
        t0 = time.monotonic() - 0.05
        bt.begin("node-a", start=t0)
        bt.phase_mark("config_load")
        [phase] = bt.report()["phases"]
        assert phase["duration_ms"] >= 50.0
        bt.reset()

    def test_node_gating(self):
        """In a multi-node test process only the begun node records."""
        bt = BootTracer()
        bt.begin("node-a")
        bt.phase_mark("config_load", node="node-b")  # gated out
        bt.phase_mark("device_init", node="node-a")
        bt.phase_mark("jit_cache_attach")  # node-agnostic stamp passes
        assert [p["name"] for p in bt.report()["phases"]] == [
            "device_init",
            "jit_cache_attach",
        ]
        bt.complete(node="node-b")  # gated out too
        assert bt.report()["complete"] is False
        bt.reset()

    def test_phase_cm_merges_extra_dict(self):
        """The phase() context manager yields a dict for values only
        known inside the block; None attrs are filtered."""
        bt = BootTracer()
        bt.begin("node-a")
        with bt.phase("prewarm", namespace="mesh4", skipped=None) as extra:
            extra["baked_ms"] = 12.5
        [phase] = bt.report()["phases"]
        assert phase["name"] == "prewarm"
        assert phase["attrs"] == {"namespace": "mesh4", "baked_ms": 12.5}
        bt.reset()

    def test_complete_stamps_headline_and_closes_trace(self):
        bt = BootTracer()
        counters.set_counter("boot.complete", 0)
        bt.begin("node-a")
        bt.phase_mark("config_load")
        time.sleep(0.005)
        bt.complete(node="node-a")
        rep = bt.report()
        assert rep["complete"] is True
        assert rep["first_rib_ms"] > 0.0
        assert counters.get_counter("boot.first_rib_ms") == rep["first_rib_ms"]
        assert counters.get_counter("boot.complete") == 1
        assert counters.get_counter("boot.phase.config_load_ms") is not None
        # the trace closed with status="boot" (the whatif pattern: never
        # a convergence event) and carries the headline on its root
        tr = next(
            t
            for t in reversed(tracer.get_traces(limit=200))
            if t["name"] == "boot" and t["status"] == "boot"
        )
        assert tr["spans"][0]["attributes"]["first_rib_ms"] == (
            rep["first_rib_ms"]
        )

    def test_begin_is_idempotent_while_active(self):
        bt = BootTracer()
        bt.begin("node-a")
        bt.begin("node-b")  # ignored: one boot per process
        assert bt.report()["node"] == "node-a"
        bt.complete()
        bt.begin("node-b")  # a completed boot can be restarted (tests)
        assert bt.report()["node"] == "node-b"
        bt.reset()

    def test_reset_abandons_open_trace(self):
        bt = BootTracer()
        bt.begin("node-a")
        bt.reset()
        assert bt.report() == {"enabled": False, "phases": []}
        assert any(
            t["name"] == "boot" and t["status"] == "boot_abandoned"
            for t in tracer.get_traces(limit=200)
        )

    def test_phase_names_are_canonical(self):
        """BOOT_PHASES is the closed vocabulary the metric-name lint
        expands `boot.phase.X_ms` against; keep it in pipeline order."""
        assert BOOT_PHASES[0] == "config_load"
        assert BOOT_PHASES[-1] == "first_fib_program"
        assert len(BOOT_PHASES) == len(set(BOOT_PHASES))
        # the AOT executable preload (ISSUE 20) is its own attributed
        # phase, right after the jax compilation cache attaches and
        # before prewarm (which it turns into deserialize-and-install)
        assert (
            BOOT_PHASES.index("aot_load")
            == BOOT_PHASES.index("jit_cache_attach") + 1
        )
        assert BOOT_PHASES.index("aot_load") < BOOT_PHASES.index("prewarm")


class TestBootSystem:
    @run_async
    async def test_cold_start_records_complete_boot_span_tree(self):
        """ISSUE 14 acceptance: a cold restart of a full node stack
        yields a complete boot span tree ending at the first programmed
        RIB, with the `boot.first_rib_ms` headline stamped."""
        boot_tracer.reset()
        names = ["boot-a", "boot-b"]
        mesh = MockIoMesh()
        kv_ports: dict[str, int] = {}
        nodes = {n: OpenrWrapper(n, mesh.provider(n), kv_ports) for n in names}
        mesh.connect("boot-a", "if-ab", "boot-b", "if-ba")
        boot_tracer.begin("boot-a")
        boot_tracer.phase_mark("config_load", node="boot-a")
        try:
            await nodes["boot-a"].start("if-ab")
            await nodes["boot-b"].start("if-ba")
            nodes["boot-a"].advertise_prefix("10.42.0.1/32")
            nodes["boot-b"].advertise_prefix("10.42.0.2/32")
            await wait_until(
                lambda: boot_tracer.report()["complete"],
                timeout_s=CONVERGENCE_S,
            )
            rep = boot_tracer.report()
            phase_names = [p["name"] for p in rep["phases"]]
            # the whole pipeline is attributed, in pipeline order
            pipeline = (
                "kvstore_initial_sync",
                "first_solve",
                "first_rib_delta",
                "first_fib_program",
            )
            for name in pipeline:
                assert name in phase_names, phase_names
            indices = [phase_names.index(n) for n in pipeline]
            assert indices == sorted(indices), phase_names
            # headline stamped in the report AND as a scrapeable gauge
            assert rep["first_rib_ms"] > 0.0
            assert counters.get_counter("boot.first_rib_ms") == (
                rep["first_rib_ms"]
            )
            # the phases tile the boot: starts are monotonic and the
            # last one ends at (or before) the headline
            starts = [p["start_ms"] for p in rep["phases"]]
            assert starts == sorted(starts)
            last = rep["phases"][-1]
            assert (
                last["start_ms"] + last["duration_ms"]
                <= rep["first_rib_ms"] + 1.0
            )
            # the first solve carries its timing split for triage
            solve = next(
                p for p in rep["phases"] if p["name"] == "first_solve"
            )
            assert "build_ms" in solve["attrs"], solve
            # the span tree closed as one `boot` trace (status="boot")
            tr = next(
                t
                for t in reversed(tracer.get_traces(limit=200))
                if t["name"] == "boot" and t["status"] == "boot"
            )
            assert tr["num_spans"] >= 1 + len(pipeline)
            span_names = {s["name"] for s in tr["spans"]}
            for name in pipeline:
                assert f"boot.{name}" in span_names, span_names
        finally:
            boot_tracer.reset()
            for w in nodes.values():
                await w.stop()


class TestBootHeapFreeze:
    """The first programmed table ends the boot: what it built leaves the
    cyclic collector's sight, once a process (ISSUE 47: at 200,000 prefix
    entries a collection of the oldest generation walked them all, 0.4 s
    in every second event of wan50k_region.exit)."""

    def test_the_heap_is_frozen_once(self, monkeypatch):
        import gc

        from openr_tpu.runtime import lifecycle

        monkeypatch.setattr(lifecycle, "_heap_frozen", False)
        gc.unfreeze()
        thresholds = gc.get_threshold()
        try:
            keep = [[i] for i in range(1000)]  # tracked, alive
            lifecycle.freeze_boot_heap()
            frozen = gc.get_freeze_count()
            assert frozen >= len(keep)
            # the gauge is the count at the freeze; frozen objects that die
            # by their reference count leave it afterwards
            assert counters.get_counter("runtime.gc.frozen_objects") >= frozen
            # a frozen object still dies by its reference count
            del keep
            # and a second stack of the process leaves the heap alone
            more = [[i] for i in range(1000)]
            lifecycle.freeze_boot_heap()
            assert gc.get_freeze_count() <= frozen and more
            # the oldest generation runs a tenth as often, the others as
            # they did; once, not once a stack
            assert gc.get_threshold() == (
                thresholds[0], thresholds[1], 10 * thresholds[2])
        finally:
            gc.unfreeze()
            gc.set_threshold(*thresholds)

    @run_async
    async def test_fib_freezes_at_its_first_programmed_table(
        self, monkeypatch
    ):
        from openr_tpu.fib import fib as fib_module
        from tests.test_fib import FibHarness, full_sync, incremental, route

        calls = []
        monkeypatch.setattr(
            fib_module, "freeze_boot_heap", lambda: calls.append(1)
        )
        async with FibHarness() as h:
            assert not calls
            h.routes_q.push(full_sync(route("10.0.0.1/32")))
            await wait_until(lambda: h.fib.synced)
            assert calls == [1]
            h.routes_q.push(incremental([route("10.0.0.2/32")]))
            await wait_until(lambda: "10.0.0.2/32" in h.service.unicast)
            assert calls == [1]
