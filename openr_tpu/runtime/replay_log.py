"""Input black-box recorder: the always-on ring of everything Decision
consumed, exportable as a flight-recorder `inputs` annex.

A RIB is a deterministic function of the ordered LSDB event stream
plus config, so recording THAT stream — not symptoms — makes every
incident re-executable offline (tools/replay.py). The recorder keeps:

- a bounded event ring of every publication delta Decision applied
  (area, key, version, originator, raw value payload, monotonic recv
  timestamp) and every key expiry, each stamped with a monotonically
  increasing sequence number (the replay cursor space);
- one full LSDB snapshot anchor (raw kv form, re-serialized from
  Decision's parsed state at a solve boundary) so replay never needs
  events older than the ring holds — re-anchored every
  `replay_snapshot_every_epochs` solves and on demand;
- a per-epoch ledger: RIB digest + rolling digest, solver kind,
  spf_kernel, and the event-ring cursor captured at the
  solve's LSDB read, which is what lets replay coalesce by recorded
  epoch boundaries instead of timers.

Snapshot anchoring is two-phase because a solve may leave the loop
(async_dispatch) while events keep arriving: Decision captures the
snapshot at `_begin_rebuild`
(the one point where LSDB state and cursor are exactly the solve's
input) and the anchor only commits in `_finish_rebuild` once the epoch
number it bases is known. A solve that dies before finishing re-arms
the request instead of committing a baseless anchor.

Hot-path cost is one deque.append of a tuple per applied key — the
counter-fabric export happens once per epoch, never per event. One
recorder per node, looked up by node name (`get_recorder`): in-process
multi-node emulations keep their input streams separate, production
daemons have exactly one.
"""

from __future__ import annotations

import base64
import time
from collections import deque
from typing import Optional

from openr_tpu.runtime.counters import counters

ANNEX_SCHEMA = "openr-tpu-replay/1"

# closed vocabulary of the replay.* counter family — exported per epoch
# via set_counter(f"replay.{field}", ...); tools/lint/metric_names.py
# expands this list for collision checking (keep the two in sync by
# importing, never copying)
REPLAY_COUNTER_FIELDS = (
    "events", "snapshots", "ring_gaps", "epochs", "suppressed",
)


class ReplayRecorder:
    """Per-node input recorder; see module docstring."""

    def __init__(
        self,
        node_name: str,
        ring: int = 8192,
        snapshot_every: int = 1024,
        meta: Optional[dict] = None,
    ):
        self.node_name = node_name
        self.ring = max(1, int(ring))
        self.snapshot_every = max(1, int(snapshot_every))
        # config fingerprint, capacity signature, solver meta — stamped
        # once by Decision at construction, exported with every annex
        self.meta = dict(meta or {})
        self._seq = 0  # cursor space: seq of the last recorded event
        # (seq, t_mono, kind, area, key, version, originator, raw|None,
        #  suppressed) — suppressed events (overload flap damping
        # withheld them from the LSDB) are recorded for incident
        # fidelity but NEVER applied on replay: they did not perturb
        # the live RIB, so replaying them would break the digest ledger
        self._events: deque = deque(maxlen=self.ring)
        self._suppressed = 0
        self._evicted_seq = 0  # newest seq the ring has dropped
        self._snapshot: Optional[dict] = None  # committed anchor
        self._snapshot_requested = True  # first solve anchors
        self._snapshot_inflight = False
        self._epochs_since_snapshot = 0
        self._ledger: deque = deque(maxlen=self.ring)
        self._snapshots = 0
        self._gaps = 0
        self._gap_open = False
        self._epochs_recorded = 0

    # -- event ring (Decision ingest hot path) -------------------------

    def _append(self, item: tuple) -> None:
        if len(self._events) == self._events.maxlen:
            self._evicted_seq = self._events[0][0]
        self._events.append(item)

    def record_kv(
        self,
        area: str,
        key: str,
        version: int,
        originator: str,
        raw: bytes,
        recv_t: Optional[float] = None,
        suppressed: bool = False,
    ) -> None:
        self._seq += 1
        if suppressed:
            self._suppressed += 1
        self._append((
            self._seq,
            recv_t if recv_t is not None else time.monotonic(),
            "kv", area, key, version, originator, raw,
            bool(suppressed),
        ))

    def record_expired(
        self,
        area: str,
        key: str,
        recv_t: Optional[float] = None,
        suppressed: bool = False,
    ) -> None:
        self._seq += 1
        if suppressed:
            self._suppressed += 1
        self._append((
            self._seq,
            recv_t if recv_t is not None else time.monotonic(),
            "expire", area, key, 0, "", None,
            bool(suppressed),
        ))

    def cursor(self) -> int:
        return self._seq

    # -- snapshot anchor (two-phase, see module docstring) -------------

    def request_snapshot(self) -> None:
        self._snapshot_requested = True

    def snapshot_due(self) -> bool:
        if self._snapshot_inflight:
            return False
        return (
            self._snapshot_requested
            or self._snapshot is None
            or self._epochs_since_snapshot >= self.snapshot_every
        )

    def take_snapshot(self, areas: dict) -> dict:
        """Phase 1, at the solve's LSDB read: capture raw kv state +
        cursor. `areas` maps area -> {key: (version, originator, raw)}.
        Returns the pending anchor to ride the solve's pending batch."""
        t0 = time.perf_counter()
        snap = {
            "cursor": self._seq,
            "base_epoch": None,
            "areas": areas,
        }
        self._snapshot_requested = False
        self._snapshot_inflight = True
        counters.add_stat_value(
            "replay.snapshot_ms", (time.perf_counter() - t0) * 1e3
        )
        return snap

    def abort_snapshot(self, snap: Optional[dict]) -> None:
        """The solve that captured `snap` never finished — re-arm."""
        if snap is not None:
            self._snapshot_inflight = False
            self._snapshot_requested = True

    # -- epoch ledger --------------------------------------------------

    def record_epoch(
        self,
        epoch: int,
        cursor: int,
        digest: str,
        rolling: str,
        solver_kind: str,
        spf_kernel: str,
        full: bool,
        snapshot: Optional[dict] = None,
    ) -> None:
        """Phase 2, at the epoch's finish: ledger entry (+ anchor
        commit when this solve carried one) and the once-per-epoch
        counter export."""
        if snapshot is not None:
            snapshot["base_epoch"] = epoch
            self._snapshot = snapshot
            self._snapshot_inflight = False
            self._epochs_since_snapshot = 0
            self._snapshots += 1
            self._gap_open = False
        else:
            self._epochs_since_snapshot += 1
        self._ledger.append({
            "epoch": epoch,
            "cursor": cursor,
            "digest": digest,
            "rolling": rolling,
            "solver_kind": solver_kind,
            "spf_kernel": spf_kernel,
            "full": bool(full),
        })
        self._epochs_recorded += 1
        if (
            self._snapshot is not None
            and self._evicted_seq > self._snapshot["cursor"]
            and not self._gap_open
        ):
            # the ring dropped events newer than the anchor: the
            # recording has a hole until the next anchor commits
            self._gap_open = True
            self._gaps += 1
            self._snapshot_requested = True
        for field, value in (
            ("events", self._seq),
            ("snapshots", self._snapshots),
            ("ring_gaps", self._gaps),
            ("epochs", self._epochs_recorded),
            ("suppressed", self._suppressed),
        ):
            counters.set_counter(f"replay.{field}", value)

    # -- export --------------------------------------------------------

    def export(self) -> Optional[dict]:
        """The flight-recorder `inputs` annex (JSON-safe), or None when
        nothing replayable has been recorded yet."""
        snap = self._snapshot
        if snap is None:
            return None
        areas_b64 = {
            area: {
                key: [v, o, base64.b64encode(raw).decode("ascii")]
                for key, (v, o, raw) in kvs.items()
            }
            for area, kvs in snap["areas"].items()
        }
        cursor = snap["cursor"]
        events = [
            {
                "seq": seq,
                "t": t,
                "kind": kind,
                "area": area,
                "key": key,
                "version": version,
                "originator": originator,
                "value_b64": (
                    None if raw is None
                    else base64.b64encode(raw).decode("ascii")
                ),
                "suppressed": suppressed,
            }
            for seq, t, kind, area, key, version, originator, raw,
            suppressed in self._events
            if seq > cursor
        ]
        return {
            "schema": ANNEX_SCHEMA,
            "node": self.node_name,
            "meta": dict(self.meta),
            "snapshot": {
                "cursor": cursor,
                "base_epoch": snap["base_epoch"],
                "areas": areas_b64,
            },
            "events": events,
            "epochs": [
                e for e in self._ledger if e["cursor"] > cursor
            ],
            "gap": self._evicted_seq > cursor,
            "recorded_at_ms": int(time.time() * 1000),
        }

    def status(self) -> dict:
        """`breeze decision replay` payload: recorder health at a
        glance, no payload bytes."""
        snap = self._snapshot
        return {
            "enabled": True,
            "node": self.node_name,
            "ring": self.ring,
            "ring_fill": len(self._events),
            "cursor": self._seq,
            "snapshots": self._snapshots,
            "snapshot_cursor": None if snap is None else snap["cursor"],
            "snapshot_base_epoch": (
                None if snap is None else snap["base_epoch"]
            ),
            "epochs_recorded": self._epochs_recorded,
            "epochs_since_snapshot": self._epochs_since_snapshot,
            "suppressed_events": self._suppressed,
            "ring_gaps": self._gaps,
            "gap": (
                snap is not None
                and self._evicted_seq > snap["cursor"]
            ),
            "ledger_tail": list(self._ledger)[-5:],
        }


# -- per-node registry (Monitor/ctrl lookup path) ----------------------

_registry: dict[str, ReplayRecorder] = {}


def register(recorder: ReplayRecorder) -> ReplayRecorder:
    """Install `recorder` as its node's recorder (latest wins — test
    harnesses rebuild Decisions under one node name)."""
    _registry[recorder.node_name] = recorder
    return recorder


def get_recorder(node_name: str) -> Optional[ReplayRecorder]:
    return _registry.get(node_name)


def unregister(node_name: str) -> None:
    _registry.pop(node_name, None)
