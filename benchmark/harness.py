"""The served path under paced load: one process, one event loop.

`ServedStack` is chip_smoke.py's: the real KvStore, Decision and Fib
actors on the queues OpenrWrapper wires them with, an in-memory
FibService behind Fib. `Session` loads a deployment into it the way a
peer's full sync arrives, releases Decision, and then drives a traffic
plan through it at the plan's fixed period: event i of a window is due at
the window's start + i * period, is sent as one `KvStore.set_key_vals`,
and is acked by Fib's programmed-routes publication for the solve epoch
that carried it. Every time is the host's monotonic clock, the clock the
program's tracer stamps its spans with.
"""

from __future__ import annotations

import asyncio
import gc
import os
import time

from files import ROOT, find, load_json, load_module
from lsdb import AREA

LOAD_CHUNK_KEYS = 16384  # a peer's full sync arrives in chunks like this
BOOT_ACK_TIMEOUT_S = 1100.0  # covers a cold compile of each program
ACK_TIMEOUT_S = 30.0  # in a window, where nothing compiles
MAX_WARMUP_ROTATIONS = 12
SETTLE_BEAT_S, SETTLE_LATE_S = 0.010, 0.020
SETTLE_QUIET_S, SETTLE_TIMEOUT_S = 3.0, 150.0
VERIFIED_EVENTS = 3  # the window's last and two the seed draws
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the program's spans a traced run reads, in the order one epoch runs them
STAGES = (
    "kvstore.publication", "decision.lsdb_apply", "decision.spf",
    "decision.rib_diff", "fib.diff", "platform.program",
)


class HarnessFailure(Exception):
    pass


def load_traffic(name: str, config: str, root: str = ROOT) -> dict:
    """traffic/<name>.json, overlaid by traffic/<name>.<config>.json: what
    a mix is in general, then what it is on this deployment."""
    params = load_json(find(root, "traffic", f"{name}.json"))
    overlay = find(root, "traffic", f"{name}.{config}.json")
    if os.path.exists(overlay):
        params.update(load_json(overlay))
    return params


def load_kind(kind: str, root: str = ROOT):
    return load_module(find(root, "traffic_kinds", f"{kind}.py"))


class ServedStack:
    """KvStore -> Decision -> Fib with OpenrWrapper's queues. Spark and
    LinkMonitor are not on the publication -> FIB-ack path, and with them
    running the vantage's own adj: key would be self-originated and
    replace the injected one — so the stack is composed as the Decision
    tests compose it."""

    def __init__(self, me: str, decision_config: dict, solver_backend: str):
        from openr_tpu.config import DecisionConfig, FibConfig, KvstoreConfig
        from openr_tpu.decision.decision import Decision
        from openr_tpu.fib import Fib, MockFibService
        from openr_tpu.kvstore.kvstore import KvStore
        from openr_tpu.messaging import ReplicateQueue

        q = {
            n: ReplicateQueue(f"{me}.{n}") for n in (
                "peerUpdates", "kvRequests", "kvStoreUpdates",
                "kvStoreEvents", "staticRoutes", "routeUpdates",
                "fibRouteUpdates", "logSamples",
            )
        }
        self.queues = q
        self.kvstore = KvStore(
            me, KvstoreConfig(), [AREA],
            q["peerUpdates"].get_reader(), q["kvRequests"].get_reader(),
            q["kvStoreUpdates"], q["kvStoreEvents"],
        )
        self.decision = Decision(
            me, DecisionConfig(**decision_config),
            q["kvStoreUpdates"].get_reader(), q["staticRoutes"].get_reader(),
            q["routeUpdates"], solver_backend=solver_backend,
            log_sample_queue=q["logSamples"],
        )
        self.fib_service = MockFibService()
        self.fib = Fib(
            me, FibConfig(route_delete_delay_ms=0), self.fib_service,
            q["routeUpdates"].get_reader(), q["fibRouteUpdates"],
            log_sample_queue=q["logSamples"],
        )
        self.fib.attach_kvstore(self.kvstore)
        self.acks = q["fibRouteUpdates"].get_reader("benchmark")

    async def start(self) -> None:
        for actor in (self.kvstore, self.decision, self.fib):
            await actor.start()

    async def stop(self) -> None:
        for queue in self.queues.values():
            queue.close()
        for actor in (self.fib, self.decision, self.kvstore):
            await actor.stop()

    async def load(self, key_vals: dict) -> None:
        items = list(key_vals.items())
        for i in range(0, len(items), LOAD_CHUNK_KEYS):
            await self.kvstore.set_key_vals(
                AREA, dict(items[i:i + LOAD_CHUNK_KEYS])
            )
            await asyncio.sleep(0)  # let Decision drain between chunks

    def release(self) -> None:
        """The initial (empty) peer event a standalone node's
        LinkMonitor sends: KvStore answers with KVSTORE_SYNCED."""
        from openr_tpu.types import AreaPeerEvent

        self.queues["peerUpdates"].push({AREA: AreaPeerEvent()})


def epoch_evidence(decision) -> dict:
    """What the epoch that just acked ran on, read where the program
    itself records it (chip_smoke.py's)."""
    from openr_tpu.decision.columnar_rib import LazyUnicastRoutes

    tm = getattr(decision.solver, "last_timing", None) or {}
    areas = tm.get("areas") or {}
    routes = decision.route_db.unicast_routes
    return {
        "solver_kind": decision._solver_kind(True),
        "device_exec": bool(areas) and all(
            a.get("kernel") and a.get("exec_ms", 0) > 0
            for a in areas.values()
        ),
        "host_routes": (
            len(routes.base) if isinstance(routes, LazyUnicastRoutes)
            else len(routes)
        ),
        "sync_ms": tm.get("sync_ms", 0.0),
        "exec_ms": tm.get("exec_ms", 0.0),
        "mat_ms": tm.get("mat_ms", 0.0),
        "rounds": tm.get("rounds", 0),
    }


def no_hiding(decision, acks: list, platform: str) -> dict:
    """chip_smoke.py's conditions: nothing stood in for the device. Each
    is a boolean; the run is sound where all are true."""
    from openr_tpu.ops.xla_cache import ledger
    from openr_tpu.runtime.counters import counters

    def counter(key: str) -> float:
        return counters.get_counter(key) or 0

    kernels = ledger.snapshot()
    device_arrays = getattr(decision.solver, "_device_arrays", None)
    resident = list(device_arrays()) if device_arrays else []
    return {
        "tpu_solver": type(decision.solver).__name__ == "TpuSpfSolver",
        "no_failover": counter("decision.solver.failovers") == 0
        and counter("decision.solver.degraded") == 0
        and all(a["evidence"]["solver_kind"] != "failover-cpu"
                for a in acks),
        "every_epoch_on_device": bool(acks) and all(
            a["evidence"]["device_exec"] for a in acks
        ),
        "no_host_computed_route": all(
            a["evidence"]["host_routes"] == 0 for a in acks
        ),
        "kernels_compiled": bool(kernels) and all(
            e["compile_ms"] is not None or e["aot_loaded"]
            for e in kernels.values()
        ),
        "resident_on_platform": bool(resident) and all(
            d.platform == platform for arr in resident for d in arr.devices()
        ),
    }


class CompileLog:
    """Every executable jax builds or fetches from its cache, stamped: what
    `compiles_in_window` and `compile_s` count. The benchmark's own
    listener, so it sees a compile whoever asked for it."""

    def __init__(self):
        import jax.monitoring

        self.stamps: list[tuple] = []  # (end, seconds, what)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.stamps.append(
                (time.monotonic(), seconds, kw.get("fun_name", "?"))
            )

    def since(self, t: float) -> list[str]:
        return [what for end, _, what in self.stamps if end >= t]

    def seconds_before(self, t: float) -> float:
        return sum(s for end, s, _ in self.stamps if end < t)


def sample_events(n_events: int, seed: int) -> list[int]:
    """The events of a window after which the table is compared: drawn
    from the seed, besides the last, which always is."""
    import random

    others = range(max(0, n_events - 1))
    return sorted(random.Random(seed).sample(
        others, min(len(others), VERIFIED_EVENTS - 1)
    ))


class Session:
    """One deployment, booted once and driven through windows of one
    traffic plan. run.py drives one window; tools/sweep.py several."""

    def __init__(self, config: dict, traffic: dict, seed: int, lsdb,
                 root: str = ROOT):
        self.config = config
        self.traffic = traffic
        self.lsdb = lsdb
        self.kind = load_kind(traffic["kind"], root)
        self.plan = self.kind.plan(lsdb, traffic, seed)
        self.compiles = CompileLog()
        self.stack = ServedStack(
            config["vantage"], config.get("decision_config", {}),
            config["solver_backend"],
        )
        self._pending = None  # the plan's next event, looked at already
        self.acks: list[dict] = []
        # solve epochs after whose ack the held table is kept for verify
        self._snapshot_epochs: set[int] = set()
        self.snapshots: dict[int, dict] = {}
        self.traces: dict[int, dict] = {}
        self._ack_seen = asyncio.Event()
        self._reader = None
        self.phases: dict[str, float] = {}
        # the interpreter's collector holds the one event loop while it
        # runs: stamp each collection, (generation, start, seconds)
        self.collections: list[tuple] = []
        self._gc_start = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.monotonic()
        else:
            self.collections.append((
                info["generation"], self._gc_start,
                time.monotonic() - self._gc_start,
            ))

    # -- set-up ----------------------------------------------------------

    async def boot(self) -> None:
        """Load the LSDB, release Decision, wait for the first full
        table: `load_s` and `first_rib_s`."""
        t0 = time.monotonic()
        key_vals = self.lsdb.key_vals()
        self.keys = len(key_vals)
        t1 = time.monotonic()
        await self.stack.start()
        self._reader = asyncio.ensure_future(self._read_acks())
        await self.stack.load(key_vals)
        t2 = time.monotonic()
        self.stack.release()
        await self._wait_for(lambda: self.acks, BOOT_ACK_TIMEOUT_S,
                             "the first programmed table")
        t3 = time.monotonic()
        self.phases.update(
            serialize_s=t1 - t0, load_s=t2 - t1, first_rib_s=t3 - t2
        )

    async def settle(self) -> None:
        """Idle until the loop runs freely. A full sync leaves work behind
        that comes due on timers (at lsdb100k the flap damper sweeps its
        record of every loaded key once a second until it has forgotten
        them, PERF.md section 6); the window measures the steady state, so
        it waits: a 10 ms beat must not come more than 20 ms late for three
        seconds on end."""
        t0 = time.monotonic()
        quiet_since = t0
        while time.monotonic() - quiet_since < SETTLE_QUIET_S:
            if time.monotonic() - t0 > SETTLE_TIMEOUT_S:
                raise HarnessFailure(
                    f"the idle loop is still held for over "
                    f"{SETTLE_LATE_S * 1e3:.0f} ms at a time after "
                    f"{SETTLE_TIMEOUT_S:.0f} s"
                )
            due = time.monotonic() + SETTLE_BEAT_S
            await asyncio.sleep(SETTLE_BEAT_S)
            if time.monotonic() - due > SETTLE_LATE_S:
                quiet_since = time.monotonic()
        self.phases["settle_s"] = time.monotonic() - t0

    async def warm_up(self, period_s: float) -> int:
        """The traffic itself, at the window's period, in whole rotations
        of the plan, each followed by bursts of events sent at once: at
        least `warmup_rotations`, and on until one whole rotation has
        installed no program. -> events sent."""
        from openr_tpu.ops.xla_cache import ledger

        per_rotation = self.kind.rotation_events(self.traffic)
        least = self.traffic.get("warmup_rotations", 2)
        t0 = time.monotonic()
        sent = 0
        for rotation in range(1, MAX_WARMUP_ROTATIONS + 1):
            kernels = set(ledger.snapshot())
            t_rot = time.monotonic()
            result = await self.window(
                per_rotation * period_s, period_s,
                # a cold program compiles at its first event
                ack_timeout_s=(
                    BOOT_ACK_TIMEOUT_S if rotation == 1 else ACK_TIMEOUT_S
                ),
            )
            failed = result["failed"]
            sent += len(result["events"])
            # where the host is held for longer than a period, the events
            # that fell due meanwhile share one solve epoch, and the
            # solver's delta scatter is compiled for each count of changed
            # slots: the mix says which such bursts to send too
            for burst in self.traffic.get("warmup_bursts", ()):
                result = await self.window(
                    burst * period_s, 0.0, count=burst
                )
                failed += result["failed"]
                sent += burst
            if failed:
                raise HarnessFailure(f"warm-up: {failed} events never acked")
            quiet = (
                not self.compiles.since(t_rot)
                and set(ledger.snapshot()) == kernels
            )
            if rotation >= least and quiet:
                self.phases["warmup_s"] = time.monotonic() - t0
                self.phases["warmup_events"] = sent
                return sent
        raise HarnessFailure(
            f"warm-up: programs still being installed after "
            f"{MAX_WARMUP_ROTATIONS} rotations"
        )

    # -- the window ------------------------------------------------------

    def _take_slots(self, n: int) -> list[list[dict]]:
        """The plan's next n slots. A slot is one timed event and the
        untimed events the plan puts behind it ({"timed": false, "after":
        fraction of a period}): what gives back what the timed event took,
        so that every timed event of a window is of one class."""
        slots = []
        while len(slots) < n:
            event, self._pending = self._pending or next(self.plan), None
            if not event.get("timed", True):
                raise HarnessFailure("the plan begins with an untimed event")
            slot = [event]
            while True:
                event = next(self.plan)
                if event.get("timed", True):
                    self._pending = event
                    break
                if not 0.0 <= event.get("after", 0.0) < 1.0:
                    raise HarnessFailure("an untimed event's `after` is "
                                         "a fraction of one period")
                slot.append(event)
            slots.append(slot)
        return slots

    def _publication(self, event: dict, offset_s: float) -> dict:
        """Apply the event to the benchmark's LSDB and serialize what it
        changed. Events are applied in the order they will be sent in."""
        nodes = self.lsdb.apply(event["ops"])
        return {
            "offset": offset_s, "pub": self.lsdb.publication(nodes),
            "batch": len(self.lsdb.log), "class": event["class"],
            "stratum": event.get("stratum", ""),
            "timed": event.get("timed", True),
        }

    async def window(self, seconds: float, period_s: float,
                     ack_timeout_s: float = ACK_TIMEOUT_S,
                     sample_seed=None, count: int = 0) -> dict:
        """Send the plan's next slots, slot i due at start + i * period for
        every i * period < seconds (an untimed event of the slot `after`
        periods later, or once the event before it is acked if that comes
        later still), and wait for the last ack. With `sample_seed`, the
        table Fib's service holds after the ack of each of `sample_events`
        is kept (a shallow copy) for `verify`. `count` fixes the number of
        slots instead (the warm-up's bursts, with period 0): their timed
        events at once, then, once those are acked, the rest one by one."""
        from openr_tpu.runtime.counters import counters

        n = count or max(1, int(seconds / period_s + 1e-9))
        slots = self._take_slots(n)
        if period_s > 0:
            phases = [sorted(
                (
                    self._publication(
                        ev, (i + ev.get("after", 0.0)) * period_s
                    )
                    for i, slot in enumerate(slots) for ev in slot
                ), key=lambda e: e["offset"],
            )]
        else:
            phases = [
                [self._publication(slot[0], 0.0) for slot in slots],
                [self._publication(ev, 0.0)
                 for slot in slots for ev in slot[1:]],
            ]
        first_ack = len(self.acks)
        sampled = set(
            () if sample_seed is None
            else sample_events(len(phases[0]), sample_seed)
        )
        events = []
        t_start = time.monotonic()
        for phase in phases:
            t_phase = time.monotonic() if events else t_start
            for i, ev in enumerate(phase):
                due = t_phase + ev.pop("offset")
                delay = due - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                if not ev["timed"] and events:
                    # what gives back waits for the ack of what took: sent
                    # into the same solve epoch the two would cancel out,
                    # and an epoch that changes no route is never acked
                    took = events[-1]["epoch"]
                    await self._wait_for(
                        lambda: self.acks and self.acks[-1]["epoch"] >= took,
                        ack_timeout_s, None,
                    )
                # no solve runs while this coroutine does: the epoch that
                # carries the event is the next one Decision starts
                epoch = (
                    counters.get_counter("decision.solve_epoch") or 0
                ) + 1
                if i in sampled:
                    self._snapshot_epochs.add(epoch)
                sent = time.monotonic()
                await self.stack.kvstore.set_key_vals(AREA, ev.pop("pub"))
                events.append({
                    **ev, "due": due, "sent": sent, "epoch": epoch,
                    "acked": None, "ack_epoch": None,
                })
            if phase:
                last = events[-1]["epoch"]
                await self._wait_for(
                    lambda: self.acks and self.acks[-1]["epoch"] >= last,
                    ack_timeout_s, None,
                )
        t_end = time.monotonic()
        acks = self.acks[first_ack:]
        k = 0
        for ev in events:
            while k < len(acks) and acks[k]["epoch"] < ev["epoch"]:
                k += 1
            if k < len(acks):
                ev["acked"] = acks[k]["t"]
                ev["ack_epoch"] = acks[k]["epoch"]
        return {
            "start": t_start, "end": t_end, "seconds": seconds,
            "period_s": period_s, "events": events, "acks": acks,
            "failed": sum(1 for ev in events if ev["acked"] is None),
            "compiles": self.compiles.since(t_start),
            "collections": [
                (gen, pause) for gen, start, pause in self.collections
                if start >= t_start
            ],
        }

    async def _read_acks(self) -> None:
        """Stamp every programmed-routes publication as it leaves Fib."""
        from openr_tpu.runtime.tracing import tracer
        from openr_tpu.types import InitializationEvent

        while True:
            item = await self.stack.acks.get()
            if isinstance(item, InitializationEvent):
                continue
            t = time.monotonic()
            self.acks.append({
                "t": t, "epoch": item.solve_epoch or 0,
                "routes": len(item.unicast_routes_to_update)
                + len(item.unicast_routes_to_delete),
                "evidence": epoch_evidence(self.stack.decision),
            })
            epoch = self.acks[-1]["epoch"]
            due = {e for e in self._snapshot_epochs if e <= epoch}
            if due:
                self._snapshot_epochs -= due
                self.snapshots[epoch] = dict(self.stack.fib_service.unicast)
            # the tracer keeps its last 256 closed traces: take them as
            # they close (in every run, so that a traced run differs from
            # the others by the profiler alone)
            for tr in tracer.get_traces(limit=8):
                self.traces.setdefault(tr["trace_id"], tr)
            self._ack_seen.set()

    async def _wait_for(self, cond, timeout_s: float, what) -> None:
        deadline = time.monotonic() + timeout_s
        while not cond():
            if self._reader.done():
                self._reader.result()  # raises what stopped the reader
                raise HarnessFailure("the ack reader stopped")
            left = deadline - time.monotonic()
            if left <= 0:
                if what is None:
                    return
                raise HarnessFailure(f"timed out waiting for {what}")
            self._ack_seen.clear()
            try:
                await asyncio.wait_for(self._ack_seen.wait(), min(left, 1.0))
            except asyncio.TimeoutError:
                pass

    async def close(self) -> None:
        gc.callbacks.remove(self._on_gc)
        if self._reader is not None:
            self._reader.cancel()
            try:
                await self._reader
            except asyncio.CancelledError:
                pass
        await self.stack.stop()

    # -- after the window ------------------------------------------------

    def verify(self, window: dict, platform: str) -> dict:
        """Once the window has closed: the table Fib's service held after
        the ack of each sampled event (`sample_events`), and the one it
        holds now, each against the plain reference on the LSDB as it
        stood once that ack's solve epoch had its last event; and the
        no-hiding conditions."""
        import reference

        t0 = time.monotonic()
        me = self.config["vantage"]
        lfa = bool(self.config.get("decision_config", {}).get("enable_lfa"))
        acked = [
            ev for ev in window["events"] if ev["ack_epoch"] is not None
        ]
        tables = {
            epoch: table for epoch, table in self.snapshots.items()
            if any(ev["ack_epoch"] == epoch for ev in acked)
        }
        if acked:  # the window's end: what the service holds now
            tables[max(ev["ack_epoch"] for ev in acked)] = (
                self.stack.fib_service.unicast
            )
        first = min(ev["batch"] for ev in window["events"])
        checks = []
        for epoch in sorted(tables):
            last = max(
                ev["batch"] for ev in acked if ev["ack_epoch"] <= epoch
            )
            then = self.lsdb.replay(last)
            want = reference.routes(then.adj_dbs, then.prefix_dbs, me, lfa)
            check = reference.compare(
                reference.programmed(tables[epoch]), want
            )
            check["after_event"] = last - first
            checks.append(check)
        hiding = no_hiding(self.stack.decision, self.acks, platform)
        return {
            "checks": checks,
            "seconds": time.monotonic() - t0,
            "tables_identical": bool(checks) and all(
                c["routes_compared"] > 0
                and not (c["missing"] or c["extra"] or c["differing"])
                for c in checks
            ),
            "no_hiding": hiding,
        }

    def overload_counters(self) -> dict:
        """The damper's and the overload ladder's counters: all zero
        where the plan is sound."""
        from openr_tpu.runtime.counters import counters

        got = counters.get_counters("overload.")
        out = {
            k: got.get(f"overload.{k}", 0) for k in (
                "damped_keys", "suppressed_events", "shed_epochs",
                "damper.suppressions", "state",
            )
        }
        ctl = getattr(self.stack.decision, "_overload", None)
        if ctl is not None:
            # which keys: one of the plan's (adj:, prefix:) is a fault of
            # the plan; the program's own telemetry keys are its business
            names = sorted(ctl.damper.report()["suppressed"])
            out["plan_keys_damped"] = [
                n for n in names
                if n.split("/", 1)[-1].startswith(("adj:", "prefix:"))
            ]
            out["other_keys_damped"] = [
                n for n in names if n not in out["plan_keys_damped"]
            ]
        return out
