"""The per-layer metrics that read the program's child spans (the splits
of lsdb_apply_ms, solver_sync_ms, solver_exec_ms and fib_program_ms, the
debounce and Fib's publication): on a traced rehearsal of every small
cell each reader gives a number, and the children add up to what the
layer's own metric reads from outside."""

from __future__ import annotations

import json

import pytest

import run
from test_harness import REHEARSAL, SMALL_CELLS

SPAN_METRICS = (
    "lsdb_decode_ms", "lsdb_update_ms", "debounce_ms",
    "solver_sync_plan_ms", "solver_sync_upload_ms", "solver_dispatch_ms",
    "solver_pull_ms", "solver_device_wait_ms", "fib_build_ms",
    "fib_write_ms", "fib_publish_ms",
)


@pytest.mark.parametrize("name", SMALL_CELLS)
def test_every_span_reader_gives_a_number(name, capsys):
    assert run.main([
        "--workload", name, "--seed", "5", "--seconds", "3", "--trace", "1",
        "--root", REHEARSAL, "--rehearse",
    ]) == 0
    lines = [
        json.loads(line) for line in capsys.readouterr().out.splitlines()
        if line.startswith("{")
    ]
    result = lines[-1]["would_print"]
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for metric in SPAN_METRICS:
        assert m.get(metric, 0.0) > 0.0, (metric, m)
        assert result["metrics"][metric]["unit"] == "ms"
    # the solver's stages are contiguous: their sum is exec_ms
    stages = sum(
        m[k] for k in
        ("solver_dispatch_ms", "solver_device_wait_ms", "solver_pull_ms")
    )
    assert stages == pytest.approx(m["solver_exec_ms"], rel=0.05)
    assert m["solver_sync_plan_ms"] + m["solver_sync_upload_ms"] <= (
        m["solver_sync_ms"]
    )
    assert m["lsdb_decode_ms"] + m["lsdb_update_ms"] <= m["lsdb_apply_ms"]
    layer = next(l["layer_means_ms"] for l in lines if "layer_means_ms" in l)
    assert m["fib_build_ms"] + m["fib_write_ms"] <= layer["platform_program"]
    # the debounce floor (10 ms by default) is inside the wait it names
    assert 5.0 < m["debounce_ms"] <= m["pre_solve_wait_ms"]
