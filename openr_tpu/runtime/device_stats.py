"""Device-plane telemetry: HBM gauges, live-buffer census, and
on-demand JAX profiler capture.

The host-side fabric (counters.py, tracing.py) stops at the device
boundary; this module crosses it. Three concerns live here:

- **gauges** — `export_device_gauges()` reads
  `jax.local_devices()[i].memory_stats()` and publishes
  `device.<i>.hbm_in_use_mb` / `.peak_mb` / `.num_allocs` into the
  counter fabric, plus a `jax.live_arrays()` census attributed to
  registered solver pools. CPU backends expose no memory_stats — the
  snapshot then carries only the backend label, never an error.
- **pools** — long-lived device-buffer owners (the TPU solver's
  per-area mirrors) register a provider so the census can split live
  bytes into "pool X" vs "unattributed" — the shape of an HBM leak.
- **profiler** — single-flight `jax.profiler.start_trace`/`stop_trace`
  with an optional auto-stop timer, served by the ctrl API so an
  operator captures a Perfetto-compatible XLA trace from a live daemon.
  The capture is anchored to the host's monotonic clock, so the stop
  also reduces it to device time per named scope of the solver's
  programs and writes the convergence tracer's spans beside the
  profiler's files, on the profiler's clock.

Passive polling (the Monitor's metrics loop) must not *cause* a jax
import in processes that never touched the device — `_jax()` only
returns the module if something else already imported it. Explicit
requests (profiler start, bench) import it on purpose.
"""

from __future__ import annotations

import glob
import gzip
import json
import logging
import os
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Iterable, Optional

from openr_tpu.runtime.counters import counters

log = logging.getLogger(__name__)

_BYTES_PER_MB = 1024.0 * 1024.0

# -- solver-pool registry ---------------------------------------------------

_pools: dict[str, Callable[[], Iterable[Any]]] = {}
_pools_lock = threading.Lock()


def register_pool(name: str, arrays_fn: Callable[[], Iterable[Any]]) -> None:
    """Register a named owner of long-lived device buffers. `arrays_fn`
    returns the arrays the pool currently holds; the census charges
    their bytes to the pool. Re-registering a name replaces it."""
    with _pools_lock:
        _pools[name] = arrays_fn


def unregister_pool(name: str) -> None:
    with _pools_lock:
        _pools.pop(name, None)
        counters.erase_prefix(f"device.pool.{name}.")


def _jax(allow_import: bool):
    if allow_import:
        try:
            import jax

            return jax
        # lint: allow(broad-except) stats degrade to "unavailable"
        except Exception:  # pragma: no cover - jax is baked into the image
            return None
    return sys.modules.get("jax")


# -- device snapshot --------------------------------------------------------


def collect_device_stats(allow_import: bool = False) -> dict:
    """One snapshot of every local device's memory stats. Backends
    without memory_stats (CPU) yield devices with only id/platform —
    the caller distinguishes "no HBM accounting" from "no devices"."""
    jax = _jax(allow_import)
    if jax is None:
        return {"backend": "unavailable", "devices": []}
    try:
        backend = jax.default_backend()
        devices = jax.local_devices()
    # lint: allow(broad-except) failure surfaced in the returned payload
    except Exception as e:  # pragma: no cover - backend init failure
        return {"backend": "error", "error": str(e), "devices": []}
    out: dict = {"backend": backend, "devices": []}
    for i, dev in enumerate(devices):
        entry: dict = {"id": i, "platform": getattr(dev, "platform", backend)}
        try:
            ms = dev.memory_stats()
        # lint: allow(broad-except) CPU backends have no HBM accounting
        except Exception:
            ms = None
        if ms:
            entry["hbm_in_use_mb"] = round(
                ms.get("bytes_in_use", 0) / _BYTES_PER_MB, 3
            )
            entry["peak_mb"] = round(
                ms.get("peak_bytes_in_use", 0) / _BYTES_PER_MB, 3
            )
            entry["num_allocs"] = int(ms.get("num_allocs", 0))
            limit = ms.get("bytes_limit", 0)
            if limit:
                entry["hbm_limit_mb"] = round(limit / _BYTES_PER_MB, 3)
                entry["hbm_frac"] = round(
                    ms.get("bytes_in_use", 0) / limit, 4
                )
        out["devices"].append(entry)
    return out


def live_buffer_census(allow_import: bool = False) -> dict:
    """Count/bytes of every live jax array, split by registered pool.
    `other_bytes` is what no pool claims — a growing `other` with flat
    pools is the classic leak signature."""
    jax = _jax(allow_import)
    if jax is None:
        return {"count": 0, "bytes": 0, "pools": {}, "other_bytes": 0}
    try:
        arrays = jax.live_arrays()
    # lint: allow(broad-except) census degrades to empty, never crashes
    except Exception:
        arrays = []
    total_n, total_b = 0, 0
    for a in arrays:
        total_n += 1
        total_b += int(getattr(a, "nbytes", 0) or 0)
    pools_out: dict[str, dict] = {}
    attributed = 0
    with _pools_lock:
        providers = list(_pools.items())
    for name, fn in providers:
        n, b = 0, 0
        by_dev: dict[int, int] = {}
        try:
            for a in fn():
                n += 1
                b += int(getattr(a, "nbytes", 0) or 0)
                # per-device attribution: a sharded array (the
                # multichip solver tier) charges each shard's bytes to
                # the device that holds it, so the census shows how a
                # pool's footprint spreads across the mesh instead of
                # lumping it on device 0
                try:
                    for shard in a.addressable_shards:
                        d = getattr(shard.device, "id", 0)
                        sb = int(
                            getattr(shard.data, "nbytes", 0) or 0
                        )
                        by_dev[d] = by_dev.get(d, 0) + sb
                # lint: allow(broad-except) non-jax arrays have no shards
                except Exception:
                    pass
        # lint: allow(broad-except) torn-down pool reads as empty
        except Exception:
            pass  # a torn-down pool reads as empty, not as a crash
        pools_out[name] = {"count": n, "bytes": b}
        if by_dev:
            pools_out[name]["by_device"] = {
                str(d): by_dev[d] for d in sorted(by_dev)
            }
        attributed += b
    return {
        "count": total_n,
        "bytes": total_b,
        "pools": pools_out,
        "other_bytes": max(0, total_b - attributed),
    }


def export_device_gauges(allow_import: bool = False) -> dict:
    """Publish the snapshot into the counter fabric (the Monitor calls
    this every interval). Returns the snapshot for callers that want
    the structured form too."""
    snap = collect_device_stats(allow_import)
    counters.set_counter("device.count", len(snap["devices"]))
    for entry in snap["devices"]:
        if "hbm_in_use_mb" not in entry:
            continue
        base = f"device.{entry['id']}"
        counters.set_counter(f"{base}.hbm_in_use_mb", entry["hbm_in_use_mb"])
        counters.set_counter(f"{base}.peak_mb", entry["peak_mb"])
        counters.set_counter(f"{base}.num_allocs", entry["num_allocs"])
        if "hbm_frac" in entry:
            counters.set_counter(f"{base}.hbm_frac", entry["hbm_frac"])
    census = live_buffer_census(allow_import)
    snap["live"] = census
    counters.set_counter("device.live_arrays.count", census["count"])
    counters.set_counter(
        "device.live_arrays.bytes_mb", round(census["bytes"] / _BYTES_PER_MB, 3)
    )
    counters.set_counter(
        "device.live_arrays.other_mb",
        round(census["other_bytes"] / _BYTES_PER_MB, 3),
    )
    for name, p in census["pools"].items():
        counters.set_counter(f"device.pool.{name}.count", p["count"])
        counters.set_counter(
            f"device.pool.{name}.bytes_mb", round(p["bytes"] / _BYTES_PER_MB, 3)
        )
        for d, db in (p.get("by_device") or {}).items():
            counters.set_counter(
                f"device.pool.{name}.dev{d}.bytes_mb",
                round(db / _BYTES_PER_MB, 3),
            )
    return snap


def hbm_pressure(allow_import: bool = False) -> Optional[float]:
    """Worst-device HBM pressure: max over local devices of
    bytes_in_use / bytes_limit. The overload controller's brownout
    watermark input (runtime/overload.py). None where no backend keeps
    both numbers (CPU) — the ladder then runs on queue/RSS signals
    alone, it never guesses."""
    snap = collect_device_stats(allow_import)
    fracs = [
        e["hbm_frac"] for e in snap["devices"] if "hbm_frac" in e
    ]
    return max(fracs) if fracs else None


def peak_hbm_mb(allow_import: bool = True) -> tuple[Optional[float], str]:
    """(max over devices of peak_bytes_in_use, backend label) — bench
    records this next to wall-time. None where the backend keeps no
    HBM accounting (CPU)."""
    snap = collect_device_stats(allow_import)
    peaks = [e["peak_mb"] for e in snap["devices"] if "peak_mb" in e]
    return (max(peaks) if peaks else None), snap["backend"]


# -- profiler capture -------------------------------------------------------

_prof_lock = threading.Lock()
_prof_state: Optional[dict] = None


# the annotation inside which profiler_start stamps the host's monotonic
# clock: where it lies in the capture carries the tracer's spans
# (time.monotonic()) onto the profiler's clock
ANCHOR = "openr.anchor"
# the jax.named_scope names inside the solver's device programs
# (tpu_solver._make_pipeline, ops/relax.py, ops/incremental.py), the
# same in every variant of the pipeline
DEVICE_SCOPES = (
    "unpack", "seed", "seed.parent", "seed.cone", "relax", "relax.ladder",
    "relax.shift", "relax.residual", "candidates", "select", "nexthop",
    "lfa", "pack", "diff", "compact",
)
DEVICE_PROCESS = "/device:"
OPS_THREAD = "XLA Ops"
SPANS_FILE = "openr_spans.trace.json"
# what a stop answers where the capture holds nothing to reduce
_UNREDUCED = {"by_scope": None, "anchor": None, "spans_file": None}


def profiler_start(
    out_dir: Optional[str] = None, seconds: Optional[float] = None
) -> dict:
    """Start a jax profiler trace. Single-flight: a second start while
    one is capturing raises (the XLA profiler is process-global). With
    `seconds`, a daemon timer stops the capture even if the requesting
    client vanishes — a forgotten trace must not run forever."""
    global _prof_state
    import jax  # explicit request: importing jax here is the point

    with _prof_lock:
        if _prof_state is not None:
            raise RuntimeError(
                f"profiler already capturing to {_prof_state['out_dir']}"
            )
        out = out_dir or tempfile.mkdtemp(prefix="openr-tpu-trace-")
        os.makedirs(out, exist_ok=True)
        # the host side of the time line is the tracer's spans, so the
        # interpreter's own (per-call, costly) tracer stays off
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=options)
        # stamped inside the annotation: its start is taken on entry,
        # so the two clocks are read microseconds apart whatever the
        # first annotation of a process costs to set up
        with jax.profiler.TraceAnnotation(ANCHOR):
            anchor_mono_ns = time.monotonic_ns()
        timer = None
        if seconds is not None and seconds > 0:
            timer = threading.Timer(seconds, _profiler_auto_stop)
            timer.daemon = True
            timer.start()
        _prof_state = {
            "out_dir": out,
            "started_ts": time.time(),
            "anchor_mono_ns": anchor_mono_ns,
            "seconds": seconds,
            "timer": timer,
        }
    counters.increment("device.profiler.starts")
    log.info("profiler capture started -> %s", out)
    return {"ok": True, "out_dir": out, "auto_stop_s": seconds}


def profiler_stop() -> dict:
    """Stop the active capture; returns the trace directory and how
    many files the profiler wrote there (>0 is the smoke signal that
    the capture actually produced a trace), and what `reduce_capture`
    read from it."""
    global _prof_state
    with _prof_lock:
        if _prof_state is None:
            raise RuntimeError("profiler is not capturing")
        state, _prof_state = _prof_state, None
    timer = state.get("timer")
    if timer is not None:
        timer.cancel()
    import jax

    jax.profiler.stop_trace()
    try:
        reduced = reduce_capture(state["out_dir"], state["anchor_mono_ns"])
    # lint: allow(broad-except) the capture itself is on disk either way
    except Exception:
        log.exception("profiler capture could not be reduced")
        reduced = _UNREDUCED
    files = 0
    for _, _, names in os.walk(state["out_dir"]):
        files += len(names)
    counters.increment("device.profiler.stops")
    duration = round(time.time() - state["started_ts"], 3)
    log.info(
        "profiler capture stopped after %.1fs -> %s (%d files)",
        duration,
        state["out_dir"],
        files,
    )
    return {
        "ok": True,
        "out_dir": state["out_dir"],
        "duration_s": duration,
        "files": files,
        **reduced,
    }


def scope_of(parts: list) -> str:
    """The innermost of DEVICE_SCOPES on an operation's name-stack path
    (`jit(pipeline)/seed/relax/while/body/relax.shift/.../add:`, split
    at "/"); for an operation outside them the path's first component
    (the jitted function), or "unscoped" where there is no path."""
    for part in reversed(parts):
        if part in DEVICE_SCOPES:
            return part
    return (parts[0] if parts else "") or "unscoped"


def scope_ms(ops: Iterable) -> dict:
    """ops: [path, start, duration] of one device's operations, in one
    unit of time; -> that unit per scope (ms where the caller divides).
    The profiler nests a loop's body inside the loop's own event, so
    each instant goes to the innermost operation running in it: the
    values add up to the device's busy time, and `relax` beside
    `relax.shift` is the loop's own share (condition, closing minimum,
    trip overhead), not the whole loop. The compiler leaves a loop
    itself without a path: it takes what the paths of the operations
    inside it have in common."""
    own: dict[str, float] = {}
    # [end, own parts or None, time not given to a child, common parts
    # of the children]
    open_ops: list[list] = []

    def close(op) -> None:
        parts = op[1] if op[1] is not None else (op[3] or [])
        scope = scope_of(parts)
        own[scope] = own.get(scope, 0.0) + max(0.0, op[2])
        if open_ops and open_ops[-1][1] is None and parts:
            parent = open_ops[-1]
            if parent[3] is None:
                parent[3] = list(parts)
            else:
                n = 0
                for a, b in zip(parent[3], parts):
                    if a != b:
                        break
                    n += 1
                del parent[3][n:]

    for path, start, dur in sorted(ops, key=lambda o: (o[1], -o[2])):
        while open_ops and open_ops[-1][0] <= start:
            close(open_ops.pop())
        if open_ops:
            open_ops[-1][2] -= min(dur, open_ops[-1][0] - start)
        open_ops.append(
            [start + dur, path.split("/") if path else None, dur, None]
        )
    while open_ops:
        close(open_ops.pop())
    return own


def read_capture(trace_json_gz: str) -> dict:
    """The trace-event JSON the profiler writes beside its xplane: each
    device's operations as [scope path, start us, duration us], and
    where the anchor annotation lies (us). The path is the operation's
    `tf_op`: the xplane keeps it on the event's metadata, which
    jax.profiler.ProfileData does not show, and this file needs gzip
    and json alone. The profiler caps the file at about a million
    events: a capture of a few seconds, not of minutes."""
    with gzip.open(trace_json_gz, "rt") as f:
        events = json.load(f).get("traceEvents", [])
    processes: dict = {}
    threads: dict = {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            processes[ev["pid"]] = ev["args"]["name"]
        elif ev.get("name") == "thread_name":
            threads[(ev["pid"], ev.get("tid"))] = ev["args"]["name"]
    ops: dict[str, list] = {}
    anchor_us = None
    for ev in events:
        if ev.get("ph") != "X":
            continue
        process = processes.get(ev.get("pid"), "")
        if process.startswith(DEVICE_PROCESS):
            if threads.get((ev["pid"], ev.get("tid"))) == OPS_THREAD:
                ops.setdefault(process, []).append([
                    (ev.get("args") or {}).get("tf_op", ""),
                    ev["ts"], ev.get("dur", 0.0),
                ])
        elif anchor_us is None and ev.get("name") == ANCHOR:
            anchor_us = ev["ts"]
    return {"ops": ops, "anchor_us": anchor_us}


def reduce_capture(out_dir: str, anchor_mono_ns: int) -> dict:
    """What profiler_stop adds to its answer: `by_scope` (device ms per
    named scope, averaged over the devices that ran any), `anchor` (the
    monotonic clock and the profiler's at the anchor annotation, ns) and
    `spans_file`: the tracer's traces that closed during the capture as
    Chrome trace events on the profiler's clock, beside the xplane."""
    found = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.trace.json.gz"
    )))
    if not found:
        return _UNREDUCED
    capture = read_capture(found[-1])
    by_scope: dict[str, float] = {}
    for ops in capture["ops"].values():
        for scope, us in scope_ms(ops).items():
            by_scope[scope] = by_scope.get(scope, 0.0) + us / 1e3
    devices = max(1, len(capture["ops"]))
    by_scope = {
        scope: round(ms / devices, 6)
        for scope, ms in sorted(by_scope.items(), key=lambda kv: -kv[1])
    }
    if capture["anchor_us"] is None:
        return {**_UNREDUCED, "by_scope": by_scope}
    anchor_trace_ns = capture["anchor_us"] * 1e3
    spans_file = os.path.join(os.path.dirname(found[-1]), SPANS_FILE)
    _write_spans(spans_file, anchor_mono_ns, anchor_trace_ns)
    return {
        "by_scope": by_scope,
        "anchor": {"mono_ns": anchor_mono_ns, "trace_ns": anchor_trace_ns},
        "spans_file": spans_file,
    }


def _write_spans(path: str, anchor_mono_ns: int, anchor_trace_ns: float):
    from openr_tpu.runtime.tracing import MAX_CLOSED_TRACES, tracer

    def us(mono_s: float) -> float:
        return (mono_s * 1e9 - anchor_mono_ns + anchor_trace_ns) / 1e3

    events = [{
        "ph": "M", "pid": 1, "name": "process_name",
        "args": {"name": "openr_tpu convergence spans"},
    }]
    for tr in tracer.get_traces(limit=MAX_CLOSED_TRACES):
        root_end = tr["spans"][0]["end"]
        if root_end is None or root_end * 1e9 < anchor_mono_ns:
            continue  # closed before the capture began
        for span in tr["spans"]:
            if span["end"] is None:
                continue
            events.append({
                "ph": "X", "pid": 1, "tid": tr["trace_id"],
                "name": span["name"], "cat": tr["name"],
                "ts": us(span["start"]),
                "dur": (span["end"] - span["start"]) * 1e6,
                "args": {
                    k: v for k, v in span["attributes"].items()
                    if isinstance(v, (str, int, float, bool)) or v is None
                },
            })
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)


def _profiler_auto_stop() -> None:
    try:
        profiler_stop()
    except RuntimeError:
        pass  # operator beat the timer to it


def profiler_status() -> dict:
    with _prof_lock:
        if _prof_state is None:
            return {"capturing": False}
        return {
            "capturing": True,
            "out_dir": _prof_state["out_dir"],
            "elapsed_s": round(time.time() - _prof_state["started_ts"], 3),
            "auto_stop_s": _prof_state["seconds"],
        }
