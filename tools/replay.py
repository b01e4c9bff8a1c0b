"""Offline incident replay: re-execute a flight-recorder bundle's
`inputs` annex through the real Decision ingest path and bit-compare
per-epoch RIB digests against the recording.

    python -m tools.replay <bundle-dir | bundle.json> [--solver cpu|tpu] [-v]
    python -m tools.replay --selftest --out <dir>

A RIB is a deterministic function of the ordered LSDB event stream
plus config, so replay is exact, not approximate: the harness loads
the bundle's LSDB snapshot anchor, ingests it through the same
deserialize/apply path live publications take, then replays the
recorded event ring epoch by epoch — coalescing driven by the
RECORDED epoch boundaries (each epoch's event-ring cursor, captured at
the live solve's LSDB read), never by timers — and recomputes the
per-epoch RIB digest after each solve. The run is headless and
synchronous on CPU jax by default; no actors, no queues with readers,
no debounce.

The verdict is a bisection: the first epoch whose replayed digest
differs from the recording is printed with its recorded solver
kind/kernel and the event window that fed it — from there the
subsystem runbook takes over (docs/Operations.md § Incident replay).
`--solver cpu|tpu` turns the same bundle into an A/B parity test: a
recording made by the device pipeline must replay bit-identically on
the CPU oracle, so a digest mismatch localizes WHICH side (and which
epoch) diverged over real incident data.

Exit status: 0 bit-identical, 1 diverged (first divergent epoch
printed), 2 not replayable (no annex, or the event ring had a gap).

`--selftest` records a short two-node churn session in-process through
a real Decision, writes the bundle to --out, replays it bit-identically
AND verifies that an injected divergence bisects to the right epoch —
the CI replay smoke lane.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import sys
from typing import Optional

# headless on CPU jax by default: replay must run on machines with no
# accelerator (and must not grab one on machines that have it)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPLAYABLE_SCHEMAS = ("openr-tpu-replay/1",)


def load_bundle(path: str) -> dict:
    """Accept a bundle directory, a bundle.json, or a bare annex."""
    if os.path.isdir(path):
        path = os.path.join(path, "bundle.json")
    with open(path) as f:
        bundle = json.load(f)
    if bundle.get("schema") in REPLAYABLE_SCHEMAS:
        # bare inputs annex (recorder export written directly)
        return {"node": bundle.get("node", ""), "inputs": bundle}
    return bundle


def _headless_decision(node: str, solver: str, spf_kernel: str):
    """A real Decision, driven synchronously: no event loop, no
    debounce, readerless route-updates queue, recorder off (replay
    must not re-record itself)."""
    from openr_tpu.config import DecisionConfig
    from openr_tpu.decision.decision import Decision
    from openr_tpu.messaging import ReplicateQueue

    cfg = DecisionConfig(
        solver_backend=solver,
        spf_kernel=spf_kernel,
        async_dispatch=False,
        replay_recorder=False,
    )
    return Decision(
        node_name=node,
        config=cfg,
        kvstore_updates_queue=None,
        static_routes_queue=None,
        route_updates_queue=ReplicateQueue("replay.routes"),
    )


def _ingest_snapshot(d, snapshot: dict) -> None:
    for area, kvs in snapshot.get("areas", {}).items():
        for key, (_version, _orig, value_b64) in kvs.items():
            d._update_key_in_lsdb(area, key, base64.b64decode(value_b64))


def _apply_event(d, ev: dict) -> None:
    from openr_tpu.types import Publication, Value

    if ev["kind"] == "kv":
        pub = Publication(
            key_vals={
                ev["key"]: Value(
                    version=int(ev.get("version") or 1),
                    originator_id=ev.get("originator") or "",
                    value=base64.b64decode(ev["value_b64"]),
                )
            },
            area=ev["area"],
        )
    else:
        pub = Publication(expired_keys=[ev["key"]], area=ev["area"])
    d.process_publication(pub)


def _solve(d, full: bool) -> str:
    """One manual rebuild over whatever is pending; returns the epoch's
    RIB digest (computed by the same _finish_rebuild path as live)."""
    from openr_tpu.decision.decision import PendingUpdates

    pending = d.pending
    d.pending = PendingUpdates()
    if full:
        pending.needs_full_rebuild = True
    d._rebuild(pending)
    return d.last_rib_digest


def replay_bundle(
    bundle: dict,
    solver: str = "cpu",
    verbose: bool = False,
    out=sys.stdout,
) -> dict:
    """Replay one bundle; returns the report dict (see `status` key:
    "identical" | "diverged" | "unreplayable")."""

    def say(msg: str) -> None:
        if verbose:
            print(msg, file=out)

    inputs = bundle.get("inputs")
    if not isinstance(inputs, dict) or inputs.get("schema") not in (
        REPLAYABLE_SCHEMAS
    ):
        return {
            "status": "unreplayable",
            "error": "bundle carries no replayable `inputs` annex "
            "(recorded before the replay recorder, or recorder "
            "disabled)",
        }
    if inputs.get("gap"):
        return {
            "status": "unreplayable",
            "error": "event ring overflowed past the snapshot anchor: "
            "the recording has a hole (see replay.ring_gaps; raise "
            "decision_config.replay_ring or lower "
            "replay_snapshot_every_epochs)",
        }
    snapshot = inputs["snapshot"]
    events = sorted(inputs["events"], key=lambda e: e["seq"])
    epochs = [
        e for e in inputs["epochs"] if e["cursor"] > snapshot["cursor"]
    ]
    meta = inputs.get("meta", {})
    node = inputs.get("node", bundle.get("node", ""))
    spf_kernel = meta.get("spf_kernel", "bucketed")

    d = _headless_decision(node, solver, spf_kernel)
    say(
        f"replaying node={node!r} solver={solver}: "
        f"snapshot@cursor={snapshot['cursor']} "
        f"base_epoch={snapshot['base_epoch']}, "
        f"{len(events)} events, {len(epochs)} epochs"
    )
    _ingest_snapshot(d, snapshot)
    # baseline build: materializes the anchor epoch's full table so the
    # first replayed epoch diffs against the same previous RIB the live
    # solve did. Its digest is a full-table fingerprint — the recording
    # has a DELTA digest for that epoch, so the baseline is not compared.
    _solve(d, full=True)
    base_epoch = snapshot.get("base_epoch")
    if base_epoch is not None:
        d._solve_epoch = int(base_epoch)

    compared = []
    first_divergent: Optional[dict] = None
    prev_cursor = snapshot["cursor"]
    ei = 0
    for ep in epochs:
        window = []
        while ei < len(events) and events[ei]["seq"] <= ep["cursor"]:
            if events[ei]["seq"] > prev_cursor:
                window.append(events[ei])
            ei += 1
        prev_cursor = ep["cursor"]
        for ev in window:
            # flap-damping withheld this event from the live LSDB
            # (runtime/overload.py) — it is recorded for incident
            # fidelity, but applying it here would perturb state the
            # live solve never saw and break the digest bit-compare
            if ev.get("suppressed"):
                continue
            _apply_event(d, ev)
        replayed = _solve(d, full=ep.get("full", True))
        match = replayed == ep["digest"]
        compared.append({
            "epoch": ep["epoch"],
            "recorded": ep["digest"],
            "replayed": replayed,
            "match": match,
            "events": len(window),
        })
        say(
            f"  epoch {ep['epoch']}: recorded={ep['digest']} "
            f"replayed={replayed} "
            f"{'ok' if match else '** DIVERGED **'} "
            f"({len(window)} events, {ep.get('solver_kind')}/"
            f"{ep.get('spf_kernel')})"
        )
        if not match and first_divergent is None:
            first_divergent = {
                "epoch": ep["epoch"],
                "recorded": ep["digest"],
                "replayed": replayed,
                "solver_kind": ep.get("solver_kind"),
                "spf_kernel": ep.get("spf_kernel"),
                "event_keys": [ev["key"] for ev in window],
            }

    report = {
        "status": "diverged" if first_divergent else "identical",
        "node": node,
        "solver": solver,
        "recorded_meta": meta,
        "epochs_compared": len(compared),
        "epochs": compared,
        "first_divergent": first_divergent,
    }
    return report


def _print_verdict(report: dict, out=sys.stdout) -> None:
    if report["status"] == "unreplayable":
        print(f"UNREPLAYABLE: {report['error']}", file=out)
        return
    n = report["epochs_compared"]
    if report["status"] == "identical":
        print(
            f"IDENTICAL: {n} epoch digests replayed bit-identically "
            f"(solver={report['solver']})",
            file=out,
        )
        return
    fd = report["first_divergent"]
    print(
        f"DIVERGED at epoch {fd['epoch']} "
        f"(first of {n} compared): recorded {fd['recorded']} != "
        f"replayed {fd['replayed']}\n"
        f"  recorded solver_kind={fd['solver_kind']} "
        f"spf_kernel={fd['spf_kernel']}\n"
        f"  epoch's event window ({len(fd['event_keys'])} keys): "
        f"{', '.join(fd['event_keys'][:8])}"
        f"{' ...' if len(fd['event_keys']) > 8 else ''}\n"
        f"  next: docs/Operations.md § Incident replay",
        file=out,
    )


# -- selftest: the CI replay smoke lane --------------------------------


def _selftest_record(tmp_dir: str) -> str:
    """Record a short two-node churn session through a real Decision
    (recorder on) and write a flight-recorder-shaped bundle; returns
    the bundle directory."""
    import random

    from openr_tpu.config import DecisionConfig
    from openr_tpu.decision.decision import Decision
    from openr_tpu.messaging import ReplicateQueue
    from openr_tpu.runtime.replay_log import get_recorder
    from openr_tpu.serde import serialize
    from openr_tpu.types import (
        Adjacency,
        AdjacencyDatabase,
        PrefixDatabase,
        PrefixEntry,
        Publication,
        Value,
        adj_key,
        prefix_key,
    )

    cfg = DecisionConfig(solver_backend="cpu", replay_recorder=True)
    d = Decision(
        node_name="replay-smoke",
        config=cfg,
        kvstore_updates_queue=None,
        static_routes_queue=None,
        route_updates_queue=ReplicateQueue("selftest.routes"),
    )

    def adj_db(node: str, other: str, metric: int) -> bytes:
        return serialize(AdjacencyDatabase(
            this_node_name=node,
            adjacencies=(Adjacency(
                other_node_name=other,
                if_name=f"if-{node}-{other}",
                other_if_name=f"if-{other}-{node}",
                metric=metric,
            ),),
        ))

    def pfx_db(node: str, prefix: str) -> bytes:
        return serialize(PrefixDatabase(
            this_node_name=node,
            prefix_entries=(PrefixEntry(prefix=prefix),),
        ))

    def publish(key: str, raw: bytes, originator: str, version: int):
        d.process_publication(Publication(
            key_vals={key: Value(
                version=version, originator_id=originator, value=raw
            )},
        ))

    # two-node mesh; replay-smoke computes routes to node "peer"
    names = {"replay-smoke": "peer", "peer": "replay-smoke"}
    for node, other in names.items():
        publish(adj_key(node), adj_db(node, other, 10), node, 1)
    for i in range(4):
        publish(
            prefix_key("peer", "0", f"10.0.{i}.0/24"),
            pfx_db("peer", f"10.0.{i}.0/24"),
            "peer", 1,
        )
    from openr_tpu.decision.decision import PendingUpdates

    pending = d.pending
    d.pending = PendingUpdates()
    pending.needs_full_rebuild = True
    d._rebuild(pending)  # anchor epoch: first solve takes the snapshot

    # randomized churn: metric flaps + a withdrawal/re-advertise
    rng = random.Random(18)
    version = {n: 1 for n in names}
    for _ in range(12):
        node = rng.choice(list(names))
        version[node] += 1
        publish(
            adj_key(node),
            adj_db(node, names[node], rng.randint(1, 100)),
            node, version[node],
        )
        if rng.random() < 0.3:
            d.process_publication(Publication(
                expired_keys=[prefix_key("peer", "0", "10.0.3.0/24")],
            ))
        elif rng.random() < 0.5:
            publish(
                prefix_key("peer", "0", "10.0.3.0/24"),
                pfx_db("peer", "10.0.3.0/24"),
                "peer", 1,
            )
        pending = d.pending
        d.pending = PendingUpdates()
        d._rebuild(pending)

    rec = get_recorder("replay-smoke")
    inputs = rec.export()
    assert inputs is not None and not inputs["gap"], "recorder gap"
    bundle_dir = os.path.join(tmp_dir, "replay-smoke-selftest")
    os.makedirs(bundle_dir, exist_ok=True)
    with open(os.path.join(bundle_dir, "bundle.json"), "w") as f:
        json.dump({
            "schema": "openr-tpu-flight-recorder/1",
            "node": "replay-smoke",
            "trigger": {"reason": "selftest", "ts_ms": 0, "detail": {}},
            "inputs": inputs,
        }, f, indent=1, sort_keys=True, default=str)
    return bundle_dir


def selftest(out_dir: str, verbose: bool = False) -> int:
    bundle_dir = _selftest_record(out_dir)
    print(f"recorded selftest bundle: {bundle_dir}")
    bundle = load_bundle(bundle_dir)
    report = replay_bundle(bundle, solver="cpu", verbose=verbose)
    _print_verdict(report)
    if report["status"] != "identical" or report["epochs_compared"] < 3:
        print("selftest FAILED: recording did not replay bit-identically")
        return 1
    # injected divergence must bisect to exactly the tampered epoch
    tampered = json.loads(json.dumps(bundle))
    victim = tampered["inputs"]["epochs"][1]
    victim["digest"] = ("0" * 16 if victim["digest"] != "0" * 16
                        else "f" * 16)
    report2 = replay_bundle(tampered, solver="cpu", verbose=verbose)
    fd = report2.get("first_divergent")
    if report2["status"] != "diverged" or fd["epoch"] != victim["epoch"]:
        print(
            f"selftest FAILED: injected divergence at epoch "
            f"{victim['epoch']} not bisected (got {fd})"
        )
        return 1
    print(
        f"selftest OK: bit-identical replay + injected divergence "
        f"bisected to epoch {fd['epoch']}"
    )
    return 0


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tools.replay",
        description="replay a flight-recorder bundle's inputs annex and "
        "bit-compare per-epoch RIB digests",
    )
    ap.add_argument("bundle", nargs="?", help="bundle dir or bundle.json")
    ap.add_argument(
        "--solver", choices=("cpu", "tpu"), default="cpu",
        help="solver backend to replay on (default cpu)",
    )
    ap.add_argument("-v", "--verbose", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="print the full report as JSON")
    ap.add_argument("--selftest", action="store_true",
                    help="record + replay a two-node churn session "
                    "(CI smoke lane)")
    ap.add_argument("--out", default=".",
                    help="selftest bundle output directory")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest(args.out, verbose=args.verbose)
    if not args.bundle:
        ap.error("bundle path required (or --selftest)")
    bundle = load_bundle(args.bundle)
    report = replay_bundle(
        bundle,
        solver=args.solver,
        verbose=args.verbose,
    )
    if args.json:
        json.dump(report, sys.stdout, indent=2, sort_keys=True)
        print()
    else:
        _print_verdict(report)
    return {"identical": 0, "diverged": 1}.get(report["status"], 2)


if __name__ == "__main__":
    sys.exit(main())
