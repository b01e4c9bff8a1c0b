"""From what a run observed to the numbers it reports.

A run's observations are flattened into named series of numbers
(`series_of`). A metric is a file of its own that reads them:
`end_to_end/<name>.json` and `layer_metrics/<name>.json` say which series,
how each is reduced and how the reductions combine;
`layer_metrics/<name>.py` gives a `read(series)` for what that cannot
say. A reader that finds nothing to read gives None, and the metric is
left out of the line.

Series (times in ms unless the name says otherwise):

  event.ack_ms            ack - due, per timed event of the window
  stratum.ack_ms.<name>   the same, for the timed events of one stratum
  event.ack_ms.<class>    ack - due for the events of one class, the
                          untimed ones (harness.py, `_take_slots`) too
  event.late_ms           sent - due, every event
  event.wait_ms           (traced) decision.spf's start - sent
  epoch.routes            routes in each programmed-routes publication
  epoch.sync_ms, epoch.exec_ms, epoch.mat_ms, epoch.rounds
                          the solver's last_timing at each ack
  span.<name>             (traced) the program's spans of that name
                          (event.wait_ms, epoch.* and span.*: of the timed
                          events and the epochs that carried them, so that
                          the layers add up to the metric)
  host.gc_pause_ms        each run of the interpreter's collector in the
                          window; host.gc2_pause_ms those of the oldest
                          generation
  window.events, window.epochs, window.compiles, window.seconds
  setup.*                 one number each: setup_s, first_rib_s, load_s,
                          keys, compile_s, ...
  device.*                (traced) busy_ms, window_ms; and peak_hbm_bytes
"""

from __future__ import annotations

import math
import os

from files import ROOT, find, load_json, load_module


def percentile(values, q: float):
    """The q-th percentile, interpolated between the two closest ranks."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


REDUCTIONS = {
    "mean": lambda xs: sum(xs) / len(xs),
    "median": lambda xs: percentile(xs, 50),
    "p95": lambda xs: percentile(xs, 95),
    "last": lambda xs: xs[-1],
}


def read_json_metric(spec: dict, series: dict):
    """{"series": [names], "reduce": how each is reduced, "combine": "sum"
    (the default) or "ratio" of the first to the second, "scale": number}"""
    parts = []
    for name in spec["series"]:
        xs = series.get(name)
        if not xs:
            return None
        parts.append(REDUCTIONS[spec.get("reduce", "mean")](xs))
    combine = spec.get("combine", "sum")
    if combine == "sum":
        value = sum(parts)
    elif combine == "ratio":
        if len(parts) != 2 or not parts[1]:
            return None
        value = parts[0] / parts[1]
    else:
        raise ValueError(f"unknown combine {combine!r}")
    return value * spec.get("scale", 1.0)


def medians_by(series: dict, prefix: str = "event.ack_ms.") -> dict:
    """Each event class's (or with "stratum.ack_ms." each stratum's) median
    churn-to-ack, for a run's earlier lines."""
    return {
        name[len(prefix):]: percentile(xs, 50)
        for name, xs in series.items() if name.startswith(prefix)
    }


def read_metric(name: str, directory: str, series: dict, root: str = ROOT):
    """The metric's own reader, found by the metric's name."""
    for ending in (".json", ".py"):
        path = find(root, directory, name + ending)
        if os.path.exists(path):
            if ending == ".json":
                return read_json_metric(load_json(path), series)
            return load_module(path).read(series)
    raise FileNotFoundError(f"no reader {directory}/{name}.json or .py")


def metrics_of(benchmark: dict, workload: str, traced: bool, series: dict,
               root: str = ROOT) -> dict:
    """The result line's `metrics`: the cell's end-to-end metrics, or with
    --trace 1 its per-layer metrics, each {"value", "unit"}."""
    group, directory = (
        ("per_layer", "layer_metrics") if traced
        else ("end_to_end", "end_to_end")
    )
    out = {}
    for metric in benchmark[group]:
        if workload not in metric.get("workloads", [workload]):
            continue
        value = read_metric(metric["name"], directory, series, root)
        if value is not None:
            out[metric["name"]] = {
                "value": float(value), "unit": metric["unit"]
            }
    return out


def series_of(window: dict, setup: dict, traces: dict, device: dict) -> dict:
    """Flatten one window's observations into named series."""
    ms = 1e3
    s: dict[str, list] = {}
    acked = [ev for ev in window["events"] if ev["acked"] is not None]
    events = [ev for ev in acked if ev.get("timed", True)]
    s["event.ack_ms"] = [(ev["acked"] - ev["due"]) * ms for ev in events]
    s["event.late_ms"] = [
        (ev["sent"] - ev["due"]) * ms for ev in window["events"]
    ]
    for ev in acked:
        ack_ms = (ev["acked"] - ev["due"]) * ms
        s.setdefault(f"event.ack_ms.{ev['class']}", []).append(ack_ms)
        if ev.get("timed", True):
            s.setdefault(
                f"stratum.ack_ms.{ev.get('stratum', '')}", []
            ).append(ack_ms)
    carrying = {ev["ack_epoch"] for ev in events}
    acks = [a for a in window["acks"] if a["epoch"] in carrying]
    every = {ev["ack_epoch"] for ev in acked}
    s["epoch.routes"] = [a["routes"] for a in acks]
    for key in ("sync_ms", "exec_ms", "mat_ms", "rounds"):
        s[f"epoch.{key}"] = [a["evidence"][key] for a in acks]
    s["host.gc_pause_ms"] = [sec * ms for _, sec in window["collections"]]
    s["host.gc2_pause_ms"] = [
        sec * ms for gen, sec in window["collections"] if gen == 2
    ]
    s["window.events"] = [len(window["events"])]
    s["window.epochs"] = [len(every)]
    s["window.compiles"] = [len(window["compiles"])]
    s["window.seconds"] = [window["seconds"]]
    for key, value in setup.items():
        s[f"setup.{key}"] = [value]
    for key, value in device.items():
        s[f"device.{key}"] = [value]

    # the program's spans, from the traces that closed on a window's epoch
    spf_start = {}
    for tr in traces.values():
        root = tr["spans"][0]
        epoch = root["attributes"].get("solve_epoch")
        if tr["status"] != "ok" or epoch not in carrying:
            continue
        for span in tr["spans"][1:]:
            if span["duration_ms"] is not None:
                s.setdefault(f"span.{span['name']}", []).append(
                    span["duration_ms"]
                )
            if span["name"] == "decision.spf":
                spf_start[epoch] = span["start"]
    waits = [
        (spf_start[ev["ack_epoch"]] - ev["sent"]) * ms
        for ev in events if ev["ack_epoch"] in spf_start
    ]
    if waits:
        s["event.wait_ms"] = waits
    return s
