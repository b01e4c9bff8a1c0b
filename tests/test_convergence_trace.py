"""The convergence trace on the served path (KvStore -> Decision -> TPU
solver -> Fib, chip_smoke.py's stack, on the CPU at a small grid and a
small fabric): every layer's child spans are there, each where its work
ran; the two-phase LSDB apply decodes no suppressed key and keeps the
replay log's order; the profiler capture's reduction to named scopes.
"""

import asyncio
import functools
import gzip
import json
import os

import pytest

from chip_smoke import AREA, ServedStack, adj_kv, lsdb_key_vals, set_metric
from openr_tpu.decision import decision as decision_mod
from openr_tpu.decision import tpu_solver
from openr_tpu.models import topologies
from openr_tpu.runtime import device_stats
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.tracing import tracer
from openr_tpu.types import Publication
from tests.conftest import run_async
from tests.test_decision import (
    DecisionHarness,
    adj,
    adj_db_kv,
    prefix_db_kv,
    two_node_mesh,
)
from tests.test_overload import _flap_cfg

# span -> parent, as docs/Observability.md's taxonomy has them
CHILDREN = {
    "decision.lsdb_apply.decode": "decision.lsdb_apply",
    "decision.lsdb_apply.update": "decision.lsdb_apply",
    "decision.debounce": "convergence",
    "tpu.sync": "decision.spf",
    "tpu.sync.plan": "tpu.sync",
    "tpu.sync.upload": "tpu.sync",
    "tpu.dispatch": "decision.spf",
    "tpu.device_wait": "decision.spf",
    "tpu.pull": "decision.spf",
    "tpu.mat": "decision.spf",
    "platform.program.build": "platform.program",
    "platform.program.write": "platform.program",
    "fib.publish": "convergence",
}
TOPOLOGIES = {
    "grid": (lambda: topologies.grid(12, node_labels=False), "node-6-6"),
    "fabric": (
        lambda: topologies.fabric(
            pods=6, planes=6, ssws_per_plane=2, rsws_per_pod=8
        ),
        "pod000-rsw00",
    ),
}
FULL_PULL_BUDGET = 2  # changed rows a delta pull may carry, in the test


def _raise_links(adj_dbs, index, node, metric, skip=()):
    """Every link of `node` (but those to `skip`) to `metric`, both
    directions; -> the changed databases, each once."""
    changed = {}
    for a in adj_dbs[index[node]].adjacencies:
        if a.other_node_name not in skip:
            for db in set_metric(
                adj_dbs, index, node, a.other_node_name, metric
            ):
                changed[db.this_node_name] = db
    return list(changed.values())


async def _serve(topology: str) -> dict:
    """Boot, one far node's links raised (an incremental epoch, a delta
    pull), then a neighbour's onward links raised with the delta budget
    cut to FULL_PULL_BUDGET (a full pull after the delta's head).
    -> kind -> (the epoch's closed trace, last_timing at its ack)."""
    build, me = TOPOLOGIES[topology]
    adj_dbs, prefix_dbs = build()
    adj_dbs = list(adj_dbs)
    index = {db.this_node_name: i for i, db in enumerate(adj_dbs)}
    near = adj_dbs[index[me]].adjacencies[0].other_node_name
    far = adj_dbs[-1].this_node_name
    assert far != me and far != near
    tracer.clear()
    stack = ServedStack(me)
    await stack.start()
    out = {}
    budget = tpu_solver._DELTA_BUDGET

    async def acked(kind: str) -> None:
        ack = await stack.next_ack()
        timing = dict(stack.decision.solver.last_timing)
        traces = [
            tr for tr in tracer.get_traces(limit=8)
            if tr["status"] == "ok"
            and tr["spans"][0]["attributes"].get("solve_epoch")
            == ack.solve_epoch
        ]
        assert len(traces) == 1, (kind, ack.solve_epoch)
        out[kind] = (traces[0], timing)

    try:
        await stack.load(lsdb_key_vals(adj_dbs, prefix_dbs))
        stack.release()
        await acked("boot")
        for version, (kind, node, skip) in enumerate((
            ("incremental", far, ()), ("full_pull", near, (me,)),
        ), start=2):
            if kind == "full_pull":
                tpu_solver._DELTA_BUDGET = FULL_PULL_BUDGET
            changed = _raise_links(adj_dbs, index, node, 3, skip)
            await stack.kvstore.set_key_vals(
                AREA, dict(adj_kv(db, version) for db in changed)
            )
            await acked(kind)
    finally:
        tpu_solver._DELTA_BUDGET = budget
        await stack.stop()
        # a boot that compiles takes seconds: left in the process-wide
        # stats it would burn a later test's Monitor SLOs
        for stat in ("convergence_ms", "fleet_convergence_ms"):
            counters.erase(stat)
    return out


@functools.lru_cache(maxsize=None)
def _served(topology: str) -> dict:
    return asyncio.run(asyncio.wait_for(_serve(topology), timeout=240))


@pytest.mark.parametrize("kind", ["boot", "incremental", "full_pull"])
@pytest.mark.parametrize("topology", sorted(TOPOLOGIES))
def test_closed_trace_holds_every_layer_split(topology, kind):
    trace, timing = _served(topology)[kind]
    spans = trace["spans"]
    by_id = {s["span_id"]: s for s in spans}
    by_name = {}
    for s in spans:
        assert "[" not in s["name"], s["name"]
        assert s["end"] is not None and s["end"] >= s["start"], s
        by_name.setdefault(s["name"], []).append(s)

    eps = 1e-9
    for name, parent in CHILDREN.items():
        assert len(by_name.get(name, [])) == 1, (name, sorted(by_name))
        span = by_name[name][0]
        up = by_id[span["parent_id"]]
        assert up["name"] == parent, (name, up["name"])
        assert up["start"] - eps <= span["start"], (name, span, up)
        assert span["end"] <= up["end"] + eps, (name, span, up)
    siblings = {}
    for s in spans[1:]:
        if s["attributes"].get("hold"):
            # a hold of the loop (the collector's pause, say) is copied
            # under the root beside the stage it landed in: it overlaps
            # that stage by design, and lies inside the trace
            assert spans[0]["start"] - eps <= s["start"] <= s["end"]
            continue
        siblings.setdefault(s["parent_id"], []).append(s)
    for group in siblings.values():
        group.sort(key=lambda s: (s["start"], s["end"]))
        for a, b in zip(group, group[1:]):
            assert a["end"] <= b["start"] + eps, (a["name"], b["name"])

    one = {name: ss[0] for name, ss in by_name.items() if len(ss) == 1}
    exec_ms = sum(
        one[n]["duration_ms"]
        for n in ("tpu.dispatch", "tpu.device_wait", "tpu.pull")
    )
    assert abs(exec_ms - timing["exec_ms"]) < 1.0, (exec_ms, timing)
    assert one["tpu.sync"]["duration_ms"] == pytest.approx(
        timing["sync_ms"], abs=1e-6
    )
    assert one["tpu.mat"]["duration_ms"] == pytest.approx(
        timing["mat_ms"], abs=1e-6
    )
    assert one["tpu.sync"]["attributes"]["area"] == AREA
    wait = one["tpu.device_wait"]["attributes"]
    assert wait["rounds"] == timing["rounds"] and wait["relax_bytes"] > 0
    update = one["decision.lsdb_apply.update"]["attributes"]
    assert update["keys"] >= 1
    decode = one["decision.lsdb_apply.decode"]["attributes"]
    assert 1 <= decode["keys"] <= update["keys"]
    # an adjacency on the wire is a few hundred bytes, a database more
    assert decode["bytes"] > 100 * decode["adjacencies"] >= 100
    assert 0 <= update["link_state_ms"] <= (
        one["decision.lsdb_apply.update"]["duration_ms"]
    )
    assert one["platform.program.build"]["attributes"]["routes"] >= 1

    pull = one["tpu.pull"]["attributes"]
    dispatch = one["tpu.dispatch"]["attributes"]
    if kind == "boot":
        assert one["platform.program"]["attributes"]["mode"] == "full_sync"
        assert pull["full_pull"] and pull["changed_rows"] is None
        assert not dispatch["incremental"]
    else:
        assert one["platform.program"]["attributes"]["mode"] == "incremental"
        assert dispatch["incremental"]
        assert one["tpu.sync.upload"]["attributes"]["dirty_slots"] >= 2
    if kind == "incremental":
        assert not pull["full_pull"] and pull["changed_rows"] >= 1
    if kind == "full_pull":
        assert pull["full_pull"]
        assert pull["changed_rows"] > FULL_PULL_BUDGET


@run_async
async def test_suppressed_key_is_not_decoded_and_replay_order_holds(
    monkeypatch,
):
    """The two-phase apply (damper verdicts, then decode, then update):
    a key the damper suppresses is held undecoded, and the recorder
    still sees the publication's keys once each, in arrival order."""
    async with DecisionHarness(config=_flap_cfg()) as h:
        two_node_mesh(h)
        h.synced()
        await h.next_route_update()
        key2, _ = adj_db_kv("2", [adj("2", "1")])
        for i in range(4):
            _, val = adj_db_kv(
                "2", [adj("2", "1", metric=10 + i)], version=10 + i
            )
            h.decision.process_publication(
                Publication(key_vals={key2: val}, area=AREA)
            )
        assert h.decision._overload.damper.is_suppressed(AREA, key2)

        decoded = []
        real = decision_mod.deserialize

        def spy(raw, kind):
            decoded.append(raw)
            return real(raw, kind)

        monkeypatch.setattr(decision_mod, "deserialize", spy)
        key_a, val_a = prefix_db_kv("2", "10.0.0.31/32")
        _, val_2 = adj_db_kv("2", [adj("2", "1", metric=77)], version=99)
        key_b, val_b = prefix_db_kv("2", "10.0.0.32/32")
        before = len(h.decision._replay.export()["events"])
        h.decision.process_publication(Publication(
            key_vals={key_a: val_a, key2: val_2, key_b: val_b}, area=AREA
        ))
        assert decoded == [val_a.value, val_b.value]
        events = h.decision._replay.export()["events"][before:]
        assert [(e["key"], e["suppressed"]) for e in events] == [
            (key_a, False), (key2, True), (key_b, False),
        ]
        dbs = h.decision.area_link_states[AREA].get_adjacency_databases()
        assert dbs["2"].adjacencies[0].metric != 77  # held, not applied


@run_async
async def test_decode_span_says_what_it_decoded():
    """keys, bytes and adjacencies on decision.lsdb_apply.decode: the
    values handed to the decoder and what came out, so a trace gives the
    cost per adjacency."""
    async with DecisionHarness() as h:
        key_1, val_1 = adj_db_kv("1", [adj("1", "2"), adj("1", "3")])
        key_2, val_2 = adj_db_kv("2", [adj("2", "1")])
        key_p, val_p = prefix_db_kv("2", "10.0.0.2/32")
        pub = Publication(
            key_vals={key_1: val_1, key_p: val_p, key_2: val_2}, area=AREA
        )
        ctx = tracer.start_trace("convergence")
        tracer.attach(pub, ctx)
        h.decision.process_publication(pub)
        (trace,) = tracer.get_traces(
            trace_id=ctx.trace_id, include_active=True
        )
        (decode,) = [
            s for s in trace["spans"]
            if s["name"] == "decision.lsdb_apply.decode"
        ]
        assert decode["attributes"] == {
            "keys": 3,
            "bytes": sum(len(v.value) for v in (val_1, val_p, val_2)),
            "adjacencies": 3,
        }
        tracer.end_trace(ctx, status="ignored")


def test_scope_reducer_gives_each_instant_to_the_innermost_scope():
    loop = "jit(pipeline)/seed/relax/while/body"
    ops = [
        ["jit(pipeline)/seed/seed.cone/reduce_sum:", 0, 10],
        ["", 10, 100],  # the loop itself: the compiler leaves it no path
        [f"{loop}/relax.shift/while/body/closed_call/add:", 20, 30],
        ["", 50, 20],  # the shift classes' inner loop
        [f"{loop}/relax.shift/while/body/closed_call/min:", 55, 10],
        [f"{loop}/relax.residual/gather:", 70, 20],
        [f"{loop}/min:", 90, 5],
        ["jit(pipeline)/select/reduce_max:", 110, 40],
        ["jit(scatter)/scatter:", 200, 7],
        ["", 300, 3],
    ]
    got = device_stats.scope_ms(ops)
    assert got == {
        "seed.cone": 10, "relax": 30, "relax.shift": 50,
        "relax.residual": 20, "select": 40, "jit(scatter)": 7,
        "unscoped": 3,
    }
    assert sum(got.values()) == 160  # the union of the intervals


def test_profiler_stop_reduces_a_recorded_capture(tmp_path):
    """reduce_capture on a toy trace-event file shaped like the one the
    profiler writes on a TPU: by_scope in ms, the anchor carried from
    the monotonic clock, the tracer's spans on the profiler's clock."""
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 1000.0, "dur": 1.0,
         "name": device_stats.ANCHOR},
        {"ph": "X", "pid": 3, "tid": 2, "ts": 2000.0, "dur": 5000.0,
         "name": "jit_pipeline(1)"},  # a module, not an operation
        {"ph": "X", "pid": 3, "tid": 3, "ts": 2000.0, "dur": 3000.0,
         "name": "while.1", "args": {}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 2100.0, "dur": 2000.0,
         "name": "fusion.1",
         "args": {"tf_op": "jit(pipeline)/seed/relax/while/body/"
                           "relax.shift/add:"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 5000.0, "dur": 1500.0,
         "name": "fusion.2", "args": {"tf_op": "jit(pipeline)/lfa/min:"}},
    ]
    with gzip.open(run / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)

    tracer.clear()
    anchor_mono_ns = 50_000_000_000
    # closed as "ignored": no convergence_ms sample for these toy times
    ctx = tracer.start_trace("convergence", start=50.002)
    tracer.record_span(ctx, "tpu.device_wait", 50.0021, 50.0051, rounds=8)
    tracer._active[ctx.trace_id].spans[0].end = 50.006
    tracer.end_trace(ctx, status="ignored")
    early = tracer.start_trace("convergence", start=49.0)
    tracer._active[early.trace_id].spans[0].end = 49.5  # before the capture
    tracer.end_trace(early, status="ignored")

    got = device_stats.reduce_capture(str(tmp_path), anchor_mono_ns)
    assert got["by_scope"] == {
        "relax.shift": 3.0, "lfa": 1.5,
    }
    assert got["anchor"] == {"mono_ns": anchor_mono_ns, "trace_ns": 1e6}
    assert os.path.dirname(got["spans_file"]) == str(run)
    with open(got["spans_file"]) as f:
        written = [
            e for e in json.load(f)["traceEvents"] if e["ph"] == "X"
        ]
    assert {e["name"] for e in written} == {"convergence", "tpu.device_wait"}
    wait = next(e for e in written if e["name"] == "tpu.device_wait")
    # 2.1 ms after the anchor, which lies at 1000 us of the capture
    assert wait["ts"] == pytest.approx(1000.0 + 2100.0, abs=1e-3)
    assert wait["dur"] == pytest.approx(3000.0, abs=1e-3)
    assert wait["args"]["rounds"] == 8


def test_every_pipeline_variant_carries_the_same_scopes():
    """The named scopes are in the programs: the full and the
    incremental pipeline lower with every name of DEVICE_SCOPES that
    their stages have, and with no other name of a stage."""
    import jax

    key = (256, 4, 8, 4, True, 4, 256, 2)  # a small capacity class
    avals = tpu_solver._pipeline_avals(key)

    def scopes_of(text: str) -> set:
        return {s for s in device_stats.DEVICE_SCOPES if f"/{s}/" in text}

    full = jax.jit(tpu_solver._make_pipeline(
        *key, 64, lfa=True, emit_dist=True, kernel="bucketed", delta_exp=1
    )).lower(*avals).as_text(debug_info=True)
    # the incremental solve's own, and of those `candidates` the narrow
    # variant's: the mask of the rows its moved node columns can reach
    incr_only = {"seed.parent", "seed.cone", "candidates"}
    assert scopes_of(full) == set(device_stats.DEVICE_SCOPES) - incr_only

    n_cap, d_cap = key[0], key[5]
    dirty = jax.ShapeDtypeStruct((64,), "int32")
    incr_avals = avals + (
        jax.ShapeDtypeStruct((d_cap, n_cap), "int32"),
        dirty, dirty, dirty, dirty, jax.ShapeDtypeStruct((), "int32"),
    )
    incr = jax.jit(tpu_solver._make_pipeline(
        *key, 64, lfa=True, emit_dist=True, incr=True,
        kernel="bucketed", delta_exp=1,
    )).lower(*incr_avals).as_text(debug_info=True)
    assert scopes_of(incr) == set(device_stats.DEVICE_SCOPES) - {"candidates"}
    narrow = jax.jit(tpu_solver._make_pipeline(
        *key, 64, lfa=True, emit_dist=True, incr=True,
        kernel="bucketed", delta_exp=1, narrow=True,
    )).lower(
        *incr_avals, dirty, jax.ShapeDtypeStruct((), "int32"),
    ).as_text(debug_info=True)
    assert scopes_of(narrow) == set(device_stats.DEVICE_SCOPES)

    # the sync rounds have no ladder, and nothing else differs
    sync = jax.jit(tpu_solver._make_pipeline(
        *key, 64, lfa=True, emit_dist=True
    )).lower(*avals).as_text(debug_info=True)
    assert scopes_of(full) - scopes_of(sync) == {"relax.ladder"}
