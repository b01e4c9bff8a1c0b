"""The per-layer metrics of the `event loop` layer: the seven readers that
read the program's holds (`benchmark/loop_holds.py`). On a traced
rehearsal of every small cell each gives a number; the window's bounds the
helper finds are the harness's; a block of the loop injected from here
shows; and against a program without the track every reader gives None.

Traced rehearsals share `benchmark/.trace`: they stay in this one file so
that `--dist loadfile` runs them one after another.
"""

from __future__ import annotations

import asyncio
import json
import time

import pytest

import files
import harness
import loop_holds
import metrics
import run
import test_harness
from test_harness import REHEARSAL, SMALL_CELLS

HOLD_METRICS = {
    "hold_gc_ms": "ms", "hold_digest_ms": "ms", "hold_damper_ms": "ms",
    "hold_unnamed_ms": "ms", "loop_held_share": "%",
    "loop_hold_max_ms": "ms", "damper_sweep_s": "s",
}


@pytest.fixture
def windows(monkeypatch):
    """Every window the harness runs, as it returned it."""
    seen = []
    window = harness.Session.window

    async def keep(self, *args, **kw):
        seen.append(await window(self, *args, **kw))
        return seen[-1]

    monkeypatch.setattr(harness.Session, "window", keep)
    return seen


def rehearse(capsys, name: str, seconds: int = 3) -> dict:
    result, _ = test_harness.rehearse(capsys, run.main, [
        "--workload", name, "--seed", "5", "--seconds", str(seconds),
        "--trace", "1", "--root", REHEARSAL,
    ])
    assert result["correct"] is True
    return result["metrics"]


def test_benchmark_json_lists_the_seven_for_every_cell():
    per_layer = {m["name"]: m for m in files.load_benchmark()["per_layer"]}
    for name, unit in HOLD_METRICS.items():
        m = per_layer[name]
        assert m["unit"] == unit and m["layer"] == "event loop"
        assert m["source"] == "program_span" and "workloads" not in m
        assert m["moves"] == (
            "setup_s" if name == "damper_sweep_s" else "churn_to_ack_p50_ms"
        )


@pytest.mark.parametrize("name", SMALL_CELLS)
def test_every_hold_reader_gives_a_number(name, capsys, monkeypatch, windows):
    bounds = []
    window_bounds = loop_holds.window_bounds

    def keep(series):
        bounds.append(window_bounds(series))
        return bounds[-1]

    monkeypatch.setattr(loop_holds, "window_bounds", keep)
    got = rehearse(capsys, name)
    for metric, unit in HOLD_METRICS.items():
        assert got[metric]["value"] >= 0.0, (metric, got)
        assert got[metric]["unit"] == unit
    assert got["loop_held_share"]["value"] <= 100.0
    # the bounds the helper finds are the harness's own (the last window
    # it ran is the measured one)
    assert bounds and all(b == bounds[0] for b in bounds)
    start, end = bounds[0]
    assert abs(start - windows[-1]["start"]) <= 0.25
    assert abs(end - windows[-1]["end"]) <= 0.25


def test_a_block_of_the_loop_shows(capsys, monkeypatch, windows):
    """A task on the served stack's own loop sleeps 0.3 s without yielding,
    inside the measured window: no harness edit, the program's heartbeat is
    the witness."""
    async def block_once():
        await asyncio.sleep(0.5)
        time.sleep(0.3)

    blockers = []
    window = harness.Session.window

    async def window_with_a_block(self, *args, **kw):
        if "sample_seed" in kw:  # the measured window, not the warm-up's
            blockers.append(asyncio.ensure_future(block_once()))
        return await window(self, *args, **kw)

    monkeypatch.setattr(harness.Session, "window", window_with_a_block)
    got = rehearse(capsys, "grid12.flap", seconds=3)
    assert len(blockers) == 1 and blockers[0].done()
    assert got["loop_hold_max_ms"]["value"] >= 150.0
    assert got["loop_held_share"]["value"] > 0.0
    # 0.15 s of 3 s at the least
    assert got["loop_held_share"]["value"] >= 5.0


def test_the_hold_report_writes_what_the_run_kept(capsys, monkeypatch, tmp_path):
    """tools/hold_report.py: the same run, and beside its lines a file of
    every hold since the process began with the window's bounds."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(files.ROOT), "tools", "hold_report.py")
    spec = importlib.util.spec_from_file_location("hold_report", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setattr(tool, "OUT", str(tmp_path))
    assert tool.main([
        "--workload", "grid12.flap", "--seed", "5", "--seconds", "2",
        "--trace", "1", "--root", REHEARSAL, "--rehearse",
    ]) == 0
    capsys.readouterr()
    with open(tmp_path / "holds.grid12.flap.5.json") as f:
        report = json.load(f)
    assert report["rc"] == 0 and report["holds_dropped"] == 0
    assert report["window"]["end"] > report["window"]["start"]
    assert len(report["events"]) >= 10
    assert all(ev["acked"] >= ev["sent"] >= ev["due"] - 1e-9
               for ev in report["events"])
    # the damper's tick alone leaves a hold a second
    assert "decision.damper_sweep" in {h["name"] for h in report["holds"]}
    assert report["counters"]["runtime.gc.collections"] > 0


def test_without_the_track_every_reader_gives_none(monkeypatch):
    """The parent of the PR that added the track: a tracer with no
    get_holds. The line leaves the seven out and nothing raises."""
    from openr_tpu.runtime import tracing

    class OldTracer:
        enabled = True
        holds_dropped = 0

    monkeypatch.setattr(tracing, "tracer", OldTracer())
    series = {
        "window.epochs": [3], "window.seconds": [3.0],
        "setup.setup_s": [1.0], "event.ack_ms": [1.0, 2.0, 3.0],
        "span.runtime.gc": [5.0],
    }
    for metric in HOLD_METRICS:
        assert metrics.read_metric(metric, "layer_metrics", series) is None


def test_with_the_track_and_no_hold_the_readers_give_zero(monkeypatch):
    from openr_tpu.runtime import tracing

    monkeypatch.setattr(tracing, "tracer", tracing.Tracer())
    series = {
        "window.epochs": [3], "window.seconds": [3.0],
        "setup.setup_s": [time.monotonic() - run.T_PROCESS],
        "event.ack_ms": [1.0, 2.0, 3.0],
    }
    for metric in HOLD_METRICS:
        assert metrics.read_metric(metric, "layer_metrics", series) == 0.0
    # and what a hold in an event reads: its sum over the timed events
    series["span.kvstore.digest"] = [600.0, 300.0]
    assert metrics.read_metric(
        "hold_digest_ms", "layer_metrics", series
    ) == pytest.approx(300.0)


def test_the_window_readers_by_hand(monkeypatch):
    from openr_tpu.runtime import tracing

    t = tracing.Tracer()
    monkeypatch.setattr(tracing, "tracer", t)
    setup_s = time.monotonic() - run.T_PROCESS
    start = run.T_PROCESS + setup_s
    series = {
        "window.epochs": [3], "window.seconds": [10.0],
        "setup.setup_s": [setup_s], "event.ack_ms": [1.0],
    }
    t.record_hold("decision.damper_sweep", start - 9.0, start - 8.5)
    t.record_hold("decision.damper_sweep", start - 7.0, start - 6.75)
    t.record_hold("kvstore.digest", start - 5.0, start - 4.0)
    t.record_hold("kvstore.digest", start - 0.5, start + 0.5)   # clipped
    t.record_hold("runtime.gc", start + 0.25, start + 0.75)     # overlaps it
    t.record_hold("runtime.unnamed_hold", start + 4.0, start + 4.25)
    t.record_hold("decision.damper_sweep", start + 9.5, start + 12.0)
    t.record_hold("runtime.gc", start + 20.0, start + 21.0)     # after

    def read(name):
        return metrics.read_metric(name, "layer_metrics", series)

    assert read("damper_sweep_s") == pytest.approx(0.75)
    # union inside the window: [0, .75] + [4, 4.25] + [9.5, 10]
    assert read("loop_held_share") == pytest.approx(15.0)
    assert read("loop_hold_max_ms") == pytest.approx(500.0)
    # a traced window's own length (first send to last ack) sets its end:
    # what holds the loop right after it (the profiler's stop) stays out
    series["device.window_ms"] = [8000.0]
    assert read("loop_held_share") == pytest.approx(12.5)


def test_a_ring_that_lost_holds_of_the_window_gives_none(monkeypatch):
    from openr_tpu.runtime import tracing

    monkeypatch.setattr(tracing, "MAX_HOLDS", 2)
    t = tracing.Tracer()
    monkeypatch.setattr(tracing, "tracer", t)
    setup_s = time.monotonic() - run.T_PROCESS
    start = run.T_PROCESS + setup_s
    series = {
        "window.epochs": [3], "window.seconds": [10.0],
        "setup.setup_s": [setup_s], "event.ack_ms": [1.0],
    }

    def read(name):
        return metrics.read_metric(name, "layer_metrics", series)

    for i in range(3):  # one dropped, all before the window
        t.record_hold("decision.damper_sweep", start - 9 + i, start - 8.5 + i)
    assert read("damper_sweep_s") is None  # wants every hold since the start
    assert read("loop_held_share") == 0.0  # what was lost ended before it
    for i in range(3):  # now the window's own are dropped
        t.record_hold("runtime.gc", start + 1 + i, start + 1.5 + i)
    assert read("loop_held_share") is None
    assert read("loop_hold_max_ms") is None
