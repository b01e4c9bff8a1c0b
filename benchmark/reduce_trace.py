"""From the profiler's trace of a window to device busy time, the device
operations that took most of it, and the idle gaps by what the host was
doing in them.

Two steps, so that the arithmetic can be checked on a small recorded
trace (testdata/) without a profiler:

  read_xplane(path)  -> the device operations of the trace, as
                        [name, start_ns, duration_ns, chip], and where the
                        benchmark's anchor annotation lies on the trace's
                        clock;
  reduce(...)        -> busy and window seconds, the top operations, the
                        idle gaps.

The profiler's clock starts at the trace; the program's spans are on the
host's monotonic clock. The harness opens a `bench.anchor` annotation
when it starts the trace and stamps the monotonic clock beside it; the
difference carries the spans onto the trace's clock.
"""

from __future__ import annotations

ANCHOR = "bench.anchor"
DEVICE_PLANE = "/device:TPU:"
# a device plane's other lines repeat the operations as modules and steps
OPS_LINE = "XLA Ops"
TOP = 10


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, anchor_ns, seen = [], None, []
    for plane in data.planes:
        chip = (
            int(plane.name[len(DEVICE_PLANE):].split()[0])
            if plane.name.startswith(DEVICE_PLANE) else None
        )
        for line in plane.lines:
            seen.append([plane.name, line.name])
            if chip is not None and line.name == OPS_LINE:
                # an event's name is the whole HLO instruction; what
                # stands before its " = " names it
                ops.extend(
                    [ev.name.split(" = ", 1)[0].lstrip("%"),
                     ev.start_ns, ev.duration_ns, chip]
                    for ev in line.events
                )
            elif chip is None and anchor_ns is None:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor_ns = ev.start_ns
                        break
    return {"device_ops": ops, "anchor_ns": anchor_ns, "lines": seen}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _overlap(a: list, b: list) -> float:
    """Total overlap of two lists of sorted, merged intervals."""
    total, j = 0.0, 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            total += min(end, b[k][1]) - max(start, b[k][0])
            k += 1
    return total


def reduce(device_ops: list, window_ns: tuple, host_spans: list) -> dict:
    """device_ops: [name, start_ns, duration_ns, chip]; window_ns: (start,
    end) on the same clock; host_spans: [name, start_ns, end_ns], the
    program's stage spans (they do not overlap one another) and, under the
    name "convergence", the whole of each epoch's trace.

    Idle time is the window less the union of the operations' intervals,
    on each chip, averaged over the chips that ran any. Each idle gap goes
    to the stage span open in it; what is left inside an epoch's trace is
    "between_stages" (the debounce and the queue hops), and what is left
    outside every trace is "waiting_for_event"."""
    w0, w1 = window_ns
    by_chip: dict[int, list] = {}
    by_name: dict[str, float] = {}
    for name, start, dur, chip in device_ops:
        start, end = max(start, w0), min(start + dur, w1)
        if end <= start:
            continue
        by_chip.setdefault(chip, []).append((start, end))
        by_name[name] = by_name.get(name, 0.0) + (end - start)
    chips = sorted(by_chip)
    busy = {chip: _union(by_chip[chip]) for chip in chips}
    busy_ns = [sum(e - s for s, e in busy[chip]) for chip in chips]

    stages: dict[str, list] = {}
    for name, start, end in host_spans:
        stages.setdefault(name, []).append((start, end))
    stages = {name: _union(spans) for name, spans in stages.items()}
    gaps: dict[str, float] = {}
    for chip in chips:
        idle, at = [], w0
        for start, end in busy[chip]:
            if start > at:
                idle.append([at, start])
            at = end
        if w1 > at:
            idle.append([at, w1])
        staged = 0.0
        for name, spans in stages.items():
            if name != "convergence":
                share = _overlap(idle, spans)
                gaps[name] = gaps.get(name, 0.0) + share
                staged += share
        inside = _overlap(idle, stages.get("convergence", []))
        between = max(0.0, inside - staged)
        waiting = sum(e - s for s, e in idle) - staged - between
        gaps["between_stages"] = gaps.get("between_stages", 0.0) + between
        gaps["waiting_for_event"] = (
            gaps.get("waiting_for_event", 0.0) + max(0.0, waiting)
        )
    n = max(1, len(chips))

    def top(d: dict) -> list:
        ranked = sorted(d.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / 1e9 / n] for name, ns in ranked if ns > 0]

    return {
        "busy_s": sum(busy_ns) / 1e9 / n,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": top(by_name),
        "idle_gaps": top(gaps),
    }
