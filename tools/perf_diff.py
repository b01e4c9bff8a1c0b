"""Per-headline perf verdicts between two bench JSONs, or a bench JSON
and the perf ledger.

    python -m tools.perf_diff BENCH_FLAPSTORM_r01.json bench-flapstorm.json
    python -m tools.perf_diff --ledger <ledger-dir> bench-new.json

Both bench output shapes (quick and full) flatten to dotted numeric
paths; each path present in BOTH inputs gets a verdict:

    improved    better by more than --threshold (fractional)
    regressed   worse by more than --threshold
    neutral     within the threshold band

Direction is inferred from the key: `*_ms` / `*_mb` / `*_s` / `value`
are lower-better; speedup-style keys are higher-better; anything else is
compared but only reported (never a verdict) — a count changing is a
fact, not a regression. Values below --min-value on both sides are
skipped: a 0.4 ms metric doubling on a shared CI runner is noise, not a
regression. Exit status is the CI contract: 0 when nothing regressed,
1 otherwise.

Ledger mode compares the flattened bench metrics against the stored
quantile baselines for matching kernel keys (see runtime/perf_ledger.py
for the key scheme).
"""

from __future__ import annotations

import argparse
import json
import sys

# keys where MORE is better; everything else numeric-lower-better is
# inferred from its unit suffix
HIGHER_BETTER = {
    "speedup",
    "device_speedup",
    "vs_baseline",
    "scenarios_per_s",
    "overlap_efficiency",
    "solves",
    # AOT executable cache (ISSUE 20): fraction of warm-boot lookups
    # served from the serialized-executable disk cache
    "aot_hit_rate",
}
LOWER_BETTER_SUFFIXES = ("_ms", "_mb", "_s", "_bytes")


def direction(key: str) -> str:
    """'lower' / 'higher' / 'info' for one dotted path's leaf key."""
    leaf = key.rsplit(".", 1)[-1]
    if leaf in HIGHER_BETTER:
        return "higher"
    if leaf == "value" or leaf.endswith(LOWER_BETTER_SUFFIXES):
        return "lower"
    return "info"


def flatten(doc, prefix: str = "") -> dict[str, float]:
    """Numeric leaves of a nested JSON document as dotted paths."""
    out: dict[str, float] = {}
    if isinstance(doc, dict):
        for k, v in doc.items():
            out.update(flatten(v, f"{prefix}{k}."))
    elif isinstance(doc, list):
        for i, v in enumerate(doc):
            out.update(flatten(v, f"{prefix}{i}."))
    elif isinstance(doc, (int, float)) and not isinstance(doc, bool):
        out[prefix[:-1]] = float(doc)
    return out


def lanes_of(flat: dict[str, float]) -> set[str]:
    """Bench lane (config) names present in a flattened bench doc —
    every `configs.<name>.*` path contributes <name>."""
    lanes = set()
    for key in flat:
        if key.startswith("configs."):
            rest = key[len("configs."):]
            if "." in rest:
                lanes.add(rest.split(".", 1)[0])
    return lanes


def vanished_lane_rows(
    baseline: dict[str, float],
    candidate: dict[str, float],
    expect_lanes: set[str] | None = None,
) -> list[dict]:
    """A lane present in the baseline but absent from the candidate is
    an explicit regression, not a neutral skip — a silently-skipped
    bench config must not pass the CI gate. `expect_lanes` narrows the
    check (a smoke gate that only runs mesh4 passes --expect-lanes
    mesh4); None means every baseline lane is expected."""
    base_lanes = lanes_of(baseline)
    cand_lanes = lanes_of(candidate)
    expected = base_lanes if expect_lanes is None else (
        base_lanes & set(expect_lanes)
    )
    rows = []
    for lane in sorted(expected - cand_lanes):
        rows.append(
            {
                "metric": f"configs.{lane}",
                "baseline": "present",
                "candidate": "MISSING",
                "delta_pct": None,
                "verdict": "regressed",
            }
        )
    return rows


def compare(
    baseline: dict[str, float],
    candidate: dict[str, float],
    threshold: float,
    min_value: float,
) -> list[dict]:
    rows = []
    for key in sorted(set(baseline) & set(candidate)):
        base, cand = baseline[key], candidate[key]
        if abs(base) < min_value and abs(cand) < min_value:
            continue
        d = direction(key)
        if base == 0:
            delta = 0.0 if cand == 0 else float("inf")
        else:
            delta = (cand - base) / abs(base)
        if d == "info":
            verdict = "info"
        else:
            worse = delta if d == "lower" else -delta
            if worse > threshold:
                verdict = "regressed"
            elif worse < -threshold:
                verdict = "improved"
            else:
                verdict = "neutral"
        rows.append(
            {
                "metric": key,
                "baseline": round(base, 3),
                "candidate": round(cand, 3),
                "delta_pct": (
                    round(delta * 100.0, 1) if delta != float("inf") else None
                ),
                "verdict": verdict,
            }
        )
    return rows


def _load_bench(path: str) -> dict[str, float]:
    with open(path) as f:
        doc = json.load(f)
    # the committed BENCH_rNN baselines wrap the bench line in a driver
    # envelope ({"cmd", "rc", "parsed": {...}}); unwrap so envelope and
    # raw bench outputs flatten to the same paths
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    flat = flatten(doc)
    # skipped configs flatten to nothing numeric; rig_rtt_ms is the
    # machine's property, not the code's — never a verdict subject
    return {k: v for k, v in flat.items() if not k.endswith("rig_rtt_ms")}


def _load_ledger(dir_path: str) -> dict[str, float]:
    """Ledger baselines flattened to comparable dotted paths:
    `configs.<name>.<metric>` from `solve[<name>]` default-variant p95s,
    so they line up with a flattened bench JSON."""
    sys.path.insert(0, ".")
    from openr_tpu.runtime import perf_ledger

    lg = perf_ledger.PerfLedger(dir_path)
    out: dict[str, float] = {}
    for key, entry in lg.snapshot()["keys"].items():
        kernel, _sig, variant, _fp = (key.split("|") + [""] * 4)[:4]
        if not (kernel.startswith("solve[") and kernel.endswith("]")):
            continue
        if variant != "default":
            continue
        name = kernel[len("solve["):-1]
        for metric, quantiles in entry["metrics"].items():
            out[f"configs.{name}.{metric}"] = quantiles["p95"]
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="perf-diff", description=__doc__.split("\n")[0]
    )
    p.add_argument("baseline", help="baseline bench JSON (or, with "
                   "--ledger, ignored in favor of the ledger dir)")
    p.add_argument("candidate", nargs="?", default=None,
                   help="candidate bench JSON (defaults to `baseline` "
                   "when --ledger supplies the baseline side)")
    p.add_argument("--ledger", default=None, metavar="DIR",
                   help="compare the candidate bench JSON against the "
                   "perf ledger in DIR instead of a baseline JSON")
    p.add_argument("--threshold", type=float, default=0.25,
                   help="fractional change beyond which a headline is "
                   "improved/regressed (default 0.25 = 25%%)")
    p.add_argument("--min-value", type=float, default=1.0,
                   help="skip metrics below this on both sides — "
                   "sub-threshold timings are runner noise (default 1)")
    p.add_argument("--expect-lanes", default=None, metavar="NAMES",
                   help="comma-separated bench lanes the candidate must "
                   "contain; a listed (or, without this flag, ANY "
                   "baseline) lane missing from the candidate is a "
                   "regression — a skipped config can't pass the gate")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable verdict rows")
    args = p.parse_args(argv)

    if args.ledger:
        base = _load_ledger(args.ledger)
        cand = _load_bench(args.candidate or args.baseline)
    else:
        if args.candidate is None:
            p.error("candidate JSON required without --ledger")
        base = _load_bench(args.baseline)
        cand = _load_bench(args.candidate)

    expect = (
        {s for s in args.expect_lanes.split(",") if s}
        if args.expect_lanes is not None
        else None
    )
    rows = vanished_lane_rows(base, cand, expect)
    rows += compare(base, cand, args.threshold, args.min_value)
    regressed = [r for r in rows if r["verdict"] == "regressed"]
    if args.as_json:
        print(json.dumps({"rows": rows, "regressed": len(regressed)}))
    else:
        width = max((len(r["metric"]) for r in rows), default=10)
        for r in rows:
            if r["verdict"] == "info":
                continue
            mark = {"regressed": "✗", "improved": "✓"}.get(r["verdict"], " ")
            print(
                f"{mark} {r['metric']:<{width}}  "
                f"{r['baseline']:>12} -> {r['candidate']:>12}  "
                f"{'' if r['delta_pct'] is None else r['delta_pct']:>7}%  "
                f"{r['verdict']}"
            )
        print(
            f"{len(rows)} compared, {len(regressed)} regressed "
            f"(threshold {args.threshold:.0%}, floor {args.min_value})"
        )
    return 1 if regressed else 0


if __name__ == "__main__":
    sys.exit(main())
