#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (not measured, reported as `setup_s`): build the deployment's LSDB
from the configuration file, load it into the served stack, release
Decision and wait for the first programmed table (`first_rib_s`), then run
the cell's own traffic until a whole rotation of it installs no program.
The window: the traffic plan's next events at the plan's fixed period for
`--seconds`. After it: every route Fib holds against the plain reference
on the final LSDB, and the no-hiding conditions.

Every line of standard output is one JSON object; the last is the result
line. A run that finds no TPU, or fewer chips than the cell asks for,
exits non-zero with no result line. `--rehearse` relaxes only that, and
never prints a result line: it ends with {"rehearsal": true, ...}.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_HERE, os.path.dirname(_HERE)]  # the benchmark, the program

import files  # noqa: E402

TRACE_DIR = os.path.join(files.ROOT, ".trace")


def emit(**obj) -> None:
    print(json.dumps(obj, sort_keys=True, default=str), flush=True)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument(
        "--rehearse", action="store_true",
        help="allow a platform that is not a TPU; prints no result line",
    )
    p.add_argument(
        "--root", default=files.ROOT,
        help="a directory searched before the benchmark's own for "
        "configs/, traffic/, readers and BENCHMARK.json (rehearsals, tests)",
    )
    return p.parse_args(argv)


def find_cell(benchmark: dict, workload: str) -> dict:
    for cell in benchmark["workloads"]:
        if cell["name"] == workload:
            return cell
    raise SystemExit(f"run.py: BENCHMARK.json has no workload {workload!r}")


def open_cell(args) -> dict:
    """What run.py and the tools do alike before they serve: the look for
    the program and for the cell's chips, the peaks table, the compile
    cache, the cell's files, its LSDB."""
    benchmark = files.load_benchmark(args.root)
    cell = find_cell(benchmark, args.workload)
    try:
        import openr_tpu  # noqa: F401
    except ImportError as e:
        print(f"run.py: the program is not here: {e}", file=sys.stderr)
        raise SystemExit(3)
    import jax

    devices, device = device_of(jax, cell["chips"], args.rehearse)
    rehearsal = device["platform"] != "tpu"
    if rehearsal:
        emit(rehearsal=True, device=device)

    import harness
    import lsdb as lsdb_mod
    from openr_tpu.ops.xla_cache import enable_compilation_cache

    peaks = files.load_json(files.find(args.root, "peaks.json"))
    if not rehearsal and device["kind"] not in peaks["devices"]:
        print(f"run.py: {device['kind']!r} is not in peaks.json",
              file=sys.stderr)
        raise SystemExit(2)
    cache_dir = enable_compilation_cache()
    t_import = time.monotonic()
    config = lsdb_mod.load_config(cell["config"], args.root)
    traffic = harness.load_traffic(cell["traffic"], cell["config"], args.root)
    lsdb = lsdb_mod.build(config)
    emit(
        workload=cell["name"], seed=args.seed, device=device,
        compile_cache_dir=cache_dir, nodes=len(lsdb.adj_dbs),
        vantage=config["vantage"], period_ms=traffic["period_ms"],
    )
    return {
        "benchmark": benchmark, "cell": cell, "devices": devices,
        "device": device, "rehearsal": rehearsal, "config": config,
        "traffic": traffic, "lsdb": lsdb, "t_import": t_import,
        "t_lsdb": time.monotonic(),
    }


def device_of(jax, chips: int, rehearse: bool):
    devices = jax.devices()  # raises where jax finds no backend at all
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" or device["count"] < chips:
        if not rehearse:
            print(
                f"run.py: the cell needs {chips} TPU chip(s), jax found "
                f"{device}", file=sys.stderr,
            )
            raise SystemExit(2)
    return devices, device


def start_trace(jax) -> float:
    """Start the profiler and anchor its clock. -> monotonic ns at the
    anchor annotation."""
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    mono_ns = time.monotonic_ns()
    import reduce_trace

    with jax.profiler.TraceAnnotation(reduce_trace.ANCHOR):
        pass
    return mono_ns


def reduce_window_trace(anchor_mono_ns: float, window: dict,
                        traces: dict) -> dict:
    """The profiler's trace of the window, reduced; the program's spans are
    carried onto its clock through the anchor."""
    import harness
    import reduce_trace

    found = sorted(glob.glob(
        os.path.join(TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise harness.HarnessFailure("the profiler wrote no trace")
    trace = reduce_trace.read_xplane(found[-1])
    if trace["anchor_ns"] is None:
        raise harness.HarnessFailure("the trace has no anchor annotation")
    shift = trace["anchor_ns"] - anchor_mono_ns  # monotonic ns -> trace ns

    def at(mono_s: float) -> float:
        return mono_s * 1e9 + shift

    spans = []
    for tr in traces.values():
        root = tr["spans"][0]
        if root["end"] is not None:
            spans.append(["convergence", at(root["start"]), at(root["end"])])
        for span in tr["spans"][1:]:
            if span["name"] in harness.STAGES and span["end"] is not None:
                spans.append(
                    [span["name"], at(span["start"]), at(span["end"])]
                )
    window_ns = (at(window["start"]), at(window["end"]))
    reduced = reduce_trace.reduce(trace["device_ops"], window_ns, spans)
    reduced["lines"] = trace["lines"]
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return reduced


async def serve(args, config: dict, traffic: dict, lsdb, jax) -> dict:
    import harness

    session = harness.Session(config, traffic, args.seed, lsdb, args.root)
    period_s = traffic["period_ms"] / 1e3
    try:
        await session.boot()
        await session.settle()
        await session.warm_up(period_s)
        anchor = None
        if args.trace:
            anchor = start_trace(jax)
        setup_s = time.monotonic() - T_PROCESS
        cpu = (time.process_time(), time.thread_time())
        window = await session.window(
            args.seconds, period_s, sample_seed=args.seed
        )
        cpu = (time.process_time() - cpu[0], time.thread_time() - cpu[1])
        if args.trace:
            jax.profiler.stop_trace()
        return {
            "session": session, "window": window, "setup_s": setup_s,
            "anchor": anchor, "cpu_s": cpu,
        }
    finally:
        await session.close()


def main(argv=None) -> int:
    args = parse_args(argv)
    opened = open_cell(args)
    benchmark, cell = opened["benchmark"], opened["cell"]
    devices, device = opened["devices"], opened["device"]
    rehearsal, lsdb = opened["rehearsal"], opened["lsdb"]
    config, traffic = opened["config"], opened["traffic"]
    t_import, t_lsdb = opened["t_import"], opened["t_lsdb"]
    import jax

    import harness
    import metrics

    try:
        served = asyncio.run(serve(args, config, traffic, lsdb, jax))
    except harness.HarnessFailure as e:
        emit(failed=str(e))
        print(f"run.py: FAILED: {e}", file=sys.stderr)
        return 1
    session, window = served["session"], served["window"]

    stats = devices[0].memory_stats() or {}
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )
    if not rehearsal and "peak_bytes_in_use" not in stats:
        print("run.py: memory_stats() has no peak_bytes_in_use",
              file=sys.stderr)
        return 1

    # -- correctness, outside the window --
    verdict = session.verify(window, device["platform"])
    overload = session.overload_counters()
    correct = (
        verdict["tables_identical"]
        and all(verdict["no_hiding"].values())
        and window["failed"] == 0
    )
    for check in verdict["checks"]:
        emit(
            compared="every programmed route against the plain reference",
            limit_differences=0, **check,
        )
    emit(no_hiding=verdict["no_hiding"], limit="all true",
         verify_s=round(verdict["seconds"], 3))
    emit(
        events_without_ack=window["failed"], limit=0,
        attempted=len(window["events"]),
    )

    # -- what the run observed, as series; then the metrics read them --
    phases = session.phases
    setup = {
        "setup_s": served["setup_s"],
        "import_s": t_import - T_PROCESS,
        "lsdb_build_s": t_lsdb - t_import,
        "keys": session.keys,
        "compile_s": session.compiles.seconds_before(window["start"]),
        **phases,
    }
    device_series = {"peak_hbm_bytes": peak}
    reduced = None
    if args.trace:
        reduced = reduce_window_trace(
            served["anchor"], window, session.traces
        )
        if not rehearsal and reduced["busy_s"] <= 0:
            emit(failed="the trace shows no device operation in the window",
                 trace_lines=reduced["lines"])
            return 1
        device_series["busy_ms"] = reduced["busy_s"] * 1e3
        device_series["window_ms"] = reduced["window_s"] * 1e3
    series = metrics.series_of(window, setup, session.traces, device_series)
    emit(phases_s={k: round(v, 3) for k, v in setup.items()})
    emit(
        compiles_in_window=len(window["compiles"]),
        compiled_in_window=window["compiles"], overload=overload,
        events=len(window["events"]), epochs=series["window.epochs"][0],
        late_p95_ms=metrics.percentile(series["event.late_ms"], 95),
        ack_ms_median_by_class=metrics.medians_by(series),
        ack_ms_median_by_stratum=metrics.medians_by(
            series, "stratum.ack_ms."
        ),
        ack_ms_p50=metrics.percentile(series["event.ack_ms"], 50),
        ack_ms_p95=metrics.percentile(series["event.ack_ms"], 95),
        ack_ms_max=max(series["event.ack_ms"], default=None),
        layer_means_ms={
            name: metrics.REDUCTIONS["mean"](series[key])
            for name, key in (
                ("pre_solve_wait", "event.wait_ms"),
                ("solver_sync", "epoch.sync_ms"),
                ("solver_exec", "epoch.exec_ms"),
                ("solver_mat", "epoch.mat_ms"),
                ("rib_diff", "span.decision.rib_diff"),
                ("fib_diff", "span.fib.diff"),
                ("platform_program", "span.platform.program"),
            ) if series.get(key)
        },
        # the window's processor seconds, all threads and the loop's own,
        # beside its length: a process that is slow with the same
        # processor seconds was kept waiting by its machine
        host={
            "window_s": window["end"] - window["start"],
            "process_cpu_s": served["cpu_s"][0],
            "loop_thread_cpu_s": served["cpu_s"][1],
            "loadavg": os.getloadavg(),
            "cpus": len(os.sched_getaffinity(0)),
        },
        gc_pauses_ms={
            "count": len(series["host.gc_pause_ms"]),
            "sum": sum(series["host.gc_pause_ms"]),
            "oldest_generation": series["host.gc2_pause_ms"],
        },
    )

    result = {
        "correct": bool(correct),
        "attempted": len(window["events"]),
        "failed": window["failed"],
        "metrics": metrics.metrics_of(
            benchmark, cell["name"], bool(args.trace), series, args.root
        ),
        "device": {**device, "memory_peak_bytes": peak},
    }
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    if rehearsal:
        emit(rehearsal=True, would_print=result)
        return 0
    emit(**result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
