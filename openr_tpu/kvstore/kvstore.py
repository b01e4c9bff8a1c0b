"""KvStore actor — the distributed store / inter-node comm backend.

Role of the reference's openr/kvstore/KvStore.{h,cpp} (KvStore<ClientType>
:732, per-area KvStoreDb :148):

  - eventually-consistent replicated map per area, CRDT-LWW merge
    (engine.merge_key_values; ref KvStoreUtil.cpp:42-210)
  - peer FSM IDLE -> SYNCING -> INITIALIZED with exponential backoff on
    transport errors (ref KvStore.cpp:981 getNextState, :2134-2141)
  - 3-way initial full sync: send local hashes, peer returns delta +
    to-be-updated list, initiator finalizes back
    (ref KvStore.cpp:1838 requestThriftPeerSync, :1974 processThriftSuccess,
    :3022 finalizeFullSync); parallel-sync limit doubles 2 -> max
  - incremental flooding with node_ids path-vector loop suppression and
    rate limiting (ref KvStore.cpp:3155-3290)
  - TTL countdown + expiry publications (ref KvStore.h:652-656)
  - self-originated keys: persist + ttl-refresh + version-bump-to-win
    (ref KvStore.h:48-61,184,304-309,678-698)

Transport is runtime/rpc.py (role of fbthrift KvStoreService). The actor
consumes peerUpdatesQueue (PeerEvent) and kvRequestQueue (KeyValueRequest),
publishes Publication | InitializationEvent to kvStoreUpdatesQueue, and
emits KvStoreSyncEvent to kvStoreEventsQueue (ref Main.cpp:223-266 wiring).
"""

from __future__ import annotations

import asyncio
import collections
import hashlib
import json
import logging
import time
from dataclasses import dataclass, field
from typing import Optional

from openr_tpu.config import KvstoreConfig
from openr_tpu.kvstore.engine import (
    KvStoreFilters,
    MergeStats,
    TtlCountdownQueue,
    dump_all_with_filters,
    dump_difference,
    dump_hash_with_filters,
    merge_key_values,
)
from openr_tpu.messaging import RQueue, ReplicateQueue
from openr_tpu.runtime.actor import Actor
from openr_tpu.runtime.counters import counters
from openr_tpu.runtime.faults import maybe_fail
from openr_tpu.runtime.lifecycle import boot_tracer
from openr_tpu.runtime.overload import get_controller
from openr_tpu.runtime.rpc import RpcClient, RpcServer
from openr_tpu.runtime.throttle import ExponentialBackoff
from openr_tpu.runtime.tracing import tracer
from openr_tpu.serde import from_plain, to_plain
from openr_tpu.types import (
    AreaPeerEvent,
    InitializationEvent,
    KeyValueRequest,
    KeyValueRequestType,
    KvStorePeerState,
    KvStoreSyncEvent,
    PeerSpec,
    Publication,
    TTL_INFINITY,
    Value,
)

log = logging.getLogger(__name__)

_PEER_SYNC_BACKOFF_MIN_S = 0.2  # scaled-down ref Constants (4s/256s) for
_PEER_SYNC_BACKOFF_MAX_S = 10.0  # single-process emulation timescales
_INITIAL_PARALLEL_SYNCS = 2  # doubles to max on progress (ref KvStore.cpp)
_TTL_ERASE_MS = 256  # short ttl for unset tombstones

# observatory key namespace: per-node TTL'd telemetry keys that ride the
# flooding fabric but are NOT protocol state — excluded from the LSDB
# digest (each node's beacons/health differ by design and would read as
# permanent divergence)
MONITOR_KEY_PREFIX = "monitor:"
LSDB_DIGEST_PREFIX = "monitor:lsdb-digest:"
FLOOD_PROBE_PREFIX = "monitor:flood-probe:"
CONV_ACK_PREFIX = "monitor:conv-ack:"
# per-node FIB-ack backchannel: ring size bounds the payload, the TTL
# ages a dead node's acks out of every store by itself
_CONV_ACK_RING = 64
_CONV_ACK_TTL_MS = 60_000
# beacons a node advertised more than this many intervals ago are
# ignored by the divergence check (also the beacon TTL multiple, so a
# dead node's beacon ages out of the comparison set by itself)
_DIGEST_STALE_INTERVALS = 3
# local digests remembered per area: a peer beacon matching ANY recent
# digest means the peer is merely behind on in-flight floods, not
# diverged — churn the fabric converges through must not flap the gauge
_DIGEST_HISTORY = 4


@dataclass
class Peer:
    """Per-peer session state (ref KvStore.h KvStorePeer :584-627)."""

    node_name: str
    spec: PeerSpec
    state: KvStorePeerState = KvStorePeerState.IDLE
    client: Optional[RpcClient] = None
    last_full_sync: float = 0.0  # monotonic; anti-entropy round-robin key
    backoff: ExponentialBackoff = field(
        default_factory=lambda: ExponentialBackoff(
            _PEER_SYNC_BACKOFF_MIN_S, _PEER_SYNC_BACKOFF_MAX_S
        )
    )


@dataclass
class SelfOriginatedValue:
    """ref KvStore.h:48-61."""

    value: Value
    persisted: bool = False  # re-advertise-to-win + periodic ttl refresh
    # monotonic stamp of the last (re-)advertisement; the imminent-TTL
    # alarm fires when an owned finite-ttl key goes unrefreshed past
    # 3/4 of its ttl (ref KvStore.h:553-564 checkKeyTtl fiber)
    last_refresh: float = 0.0


class KvStoreArea:
    """Per-area store + peers (ref KvStoreDb, KvStore.h:148)."""

    def __init__(self, area: str, node_name: str, cfg: KvstoreConfig):
        self.area = area
        self.node_name = node_name
        self.cfg = cfg
        self.kv: dict[str, Value] = {}
        self.peers: dict[str, Peer] = {}
        self.self_originated: dict[str, SelfOriginatedValue] = {}
        self.ttl_queue = TtlCountdownQueue()
        self.initial_sync_done = False  # all initial peers INITIALIZED
        # DUAL SPT flood topology (ref Dual.h; None = full-mesh flooding)
        self.dual: Optional["Dual"] = None
        # recent local LSDB digests, newest last (divergence beacons)
        self.digest_history: collections.deque[str] = collections.deque(
            maxlen=_DIGEST_HISTORY
        )
        # calls of digest(): each hashes every key (kvstore.digest hold)
        self.digests_taken = 0

    def hashes(self) -> dict[str, Value]:
        return dump_hash_with_filters(self.area, self.kv).key_vals

    def digest(self) -> tuple[str, int]:
        """Rolling LSDB digest: blake2b over the sorted
        (key, version, ttl_version, value-hash) tuples — the same
        per-key identity `breeze kv compare` and the 3-way sync deltas
        compare on (Value.hash covers version/originator/value). Two
        stores with equal digests hold the same protocol state; the
        `monitor:` telemetry namespace is excluded (per-node by
        design)."""
        self.digests_taken += 1
        h = hashlib.blake2b(digest_size=8)
        n = 0
        for key in sorted(self.kv):
            if key.startswith(MONITOR_KEY_PREFIX):
                continue
            v = self.kv[key]
            h.update(
                f"{key}\x00{v.version}\x00{v.ttl_version}\x00{v.hash}\x01"
                .encode()
            )
            n += 1
        return h.hexdigest(), n


class KvStore(Actor):
    """The distributed-store actor; one RPC server, N areas."""

    def __init__(
        self,
        node_name: str,
        config: KvstoreConfig,
        areas: list[str],
        peer_updates_queue: RQueue,
        kv_request_queue: RQueue,
        kvstore_updates_queue: ReplicateQueue,
        kvstore_events_queue: ReplicateQueue,
        listen_port: int = 0,
        listen_addr: str = "127.0.0.1",
        server_ssl=None,
        client_ssl=None,
    ):
        super().__init__(f"kvstore:{node_name}")
        self.node_name = node_name
        self.cfg = config
        self.areas: dict[str, KvStoreArea] = {
            a: KvStoreArea(a, node_name, config) for a in areas
        }
        if config.enable_flood_optimization:
            from openr_tpu.kvstore.dual import Dual

            for st in self.areas.values():
                st.dual = Dual(
                    node_name,
                    send=(
                        lambda peer, msg, _st=st: self._dual_send(
                            _st, peer, msg
                        )
                    ),
                    is_root=config.is_flood_root,
                    on_parent_change=(
                        lambda root, parent, _st=st: (
                            self._on_dual_parent_change(_st, root, parent)
                        )
                    ),
                )
        self._peer_updates = peer_updates_queue
        self._kv_requests = kv_request_queue
        self._updates_q = kvstore_updates_queue
        self._events_q = kvstore_events_queue
        self._listen_port = listen_port
        self._listen_addr = listen_addr
        # TLS on the PEER plane (flooding + full sync): the reference
        # runs inter-node thrift with SSL; plaintext protocol traffic
        # would let any on-path host inject LSDB state. server_ssl is
        # an ssl.SSLContext for our listener; client_ssl one for peer
        # sessions (pinning happens via expected_peer per connection).
        self._server_ssl = server_ssl
        self._client_ssl = client_ssl
        self.server = RpcServer(self.name)
        self.port: int = 0
        self._parallel_sync_limit = _INITIAL_PARALLEL_SYNCS
        self._sync_wakeup = asyncio.Event()
        self._ttl_wakeup = asyncio.Event()
        self._refresh_wakeup = asyncio.Event()
        self._flood_tokens = float(config.flood_rate_burst_size or 0)
        self._flood_tokens_ts = time.monotonic()
        self._initialized_signalled = False
        # KVSTORE_SYNCED gates on the initial peer event from LinkMonitor
        # (ref initialization protocol): an empty initial event means a
        # standalone node, which is synced trivially.
        self._initial_peers_received = False
        # observatory state: version counters seeded from the wall clock
        # so a restarted node's first beacon beats its previous
        # incarnation's TTL'd remnant (same idiom as monitor:health)
        self._digest_version = int(time.time())
        self._probe_version = int(time.time())
        self._probe_seq = 0
        # origin-event id counter, wall-seeded so a restarted node's
        # event ids never collide with its previous incarnation's
        self._origin_seq = int(time.time() * 1000)
        # fleet-convergence FIB-ack backchannel (monitor:conv-ack:<node>)
        self._conv_acks: collections.deque = collections.deque(
            maxlen=_CONV_ACK_RING
        )
        self._conv_ack_version = int(time.time())
        self._divergence: dict = {}  # last computed divergence report

    # -- lifecycle ---------------------------------------------------------

    async def on_start(self) -> None:
        self.server.register("kvstore.set_key_vals", self._rpc_set_key_vals)
        self.server.register("kvstore.dump_filtered", self._rpc_dump_filtered)
        self.server.register("kvstore.dump_hashes", self._rpc_dump_hashes)
        self.server.register("kvstore.dual", self._rpc_dual)
        # server-side identity check: a CA-valid client must also CLAIM
        # a node name we actually peer with — otherwise any domain
        # member could pull another segment's LSDB under a bogus name.
        # (A peer connecting moments before LinkMonitor registers it is
        # rejected once and heals on the sync loop's backoff retry.)
        peer_verifier = None
        if self._server_ssl is not None:
            from openr_tpu.config import cert_peer_names

            def peer_verifier(cert):
                names = cert_peer_names(cert)
                known = {
                    name for st in self.areas.values() for name in st.peers
                }
                return bool(names & known)

        self.port = await self.server.start(
            host=self._listen_addr, port=self._listen_port,
            ssl=self._server_ssl, peer_verifier=peer_verifier,
        )
        # long-lived fibers run supervised: a crash restarts the loop
        # (queue readers keep their backlog) instead of leaving a
        # half-dead store that still answers RPCs
        self.add_supervised_task(
            self._peer_updates_loop, name=f"{self.name}.peers"
        )
        self.add_supervised_task(
            self._kv_requests_loop, name=f"{self.name}.requests"
        )
        self.add_supervised_task(self._sync_loop, name=f"{self.name}.sync")
        self.add_supervised_task(self._ttl_loop, name=f"{self.name}.ttl")
        self.add_supervised_task(
            self._ttl_refresh_loop, name=f"{self.name}.ttl-refresh"
        )
        self.add_supervised_task(
            self._ttl_alarm_loop, name=f"{self.name}.ttl-alarm"
        )
        if self.cfg.sync_interval_s > 0:
            self.add_supervised_task(
                self._anti_entropy_loop, name=f"{self.name}.anti-entropy"
            )
        if self.cfg.enable_lsdb_digest:
            self.add_supervised_task(
                self._digest_loop, name=f"{self.name}.digest"
            )
        if self.cfg.enable_flood_probes:
            self.add_supervised_task(
                self._flood_probe_loop, name=f"{self.name}.flood-probe"
            )

    async def on_stop(self) -> None:
        await self.server.stop()
        for area in self.areas.values():
            for peer in area.peers.values():
                if peer.client is not None:
                    await peer.client.close()

    async def on_fiber_restart(self, task_name: str) -> None:
        """Supervisor recovery: re-kick every wakeup event — the crashed
        fiber may have consumed a wakeup without acting on it, and the
        sync FSM must re-examine peers left mid-transition."""
        self._sync_wakeup.set()
        self._ttl_wakeup.set()
        self._refresh_wakeup.set()

    # -- RPC server side ---------------------------------------------------

    def _authorize_peer(self, area: str) -> None:
        """Per-request authorization on the secured peer plane: the
        caller's VERIFIED cert identity (transport truth, not the
        request's sender_id field) must name a peer of THIS area —
        otherwise a node valid in one area could dump or inject another
        area's LSDB through the shared connection."""
        if self._server_ssl is None:
            return
        from openr_tpu.runtime.rpc import current_peer_cert_names

        names = current_peer_cert_names() or frozenset()
        st = self.areas.get(area)
        if st is None or not (names & set(st.peers)):
            raise PermissionError(
                f"peer {sorted(names)} is not a registered peer of "
                f"area {area!r}"
            )

    async def _rpc_set_key_vals(
        self, area: str, publication: dict, sender_id: str = ""
    ) -> dict:
        """Peer flood / finalize-sync ingress (ref KvStoreDb::setKeyVals)."""
        self._authorize_peer(area)
        pub = from_plain(publication, Publication)
        pub.area = area
        counters.increment(f"kvstore.{self.node_name}.thrift.num_flood_pub")
        self._merge_and_flood(pub, sender_id=sender_id)
        return {"ok": True}

    async def _rpc_dump_filtered(
        self,
        area: str,
        prefixes: Optional[list] = None,
        originator_ids: Optional[list] = None,
        key_val_hashes: Optional[dict] = None,
    ) -> dict:
        """Full-sync / filtered dump (ref getKvStoreKeyValsFilteredArea)."""
        self._authorize_peer(area)
        st = self.areas[area]
        filters = KvStoreFilters(
            key_prefixes=tuple(prefixes or ()),
            originator_ids=frozenset(originator_ids or ()),
        )
        if key_val_hashes is not None:
            req_hashes = {
                k: from_plain(v, Value) for k, v in key_val_hashes.items()
            }
            # filters restrict which of OUR keys enter the delta
            my_kv = (
                dump_all_with_filters(area, st.kv, filters).key_vals
                if (prefixes or originator_ids)
                else st.kv
            )
            pub = dump_difference(area, my_kv, req_hashes)
            counters.increment(f"kvstore.{self.node_name}.full_sync_served")
        else:
            pub = dump_all_with_filters(area, st.kv, filters)
        self._decrement_out_ttls(pub)
        return to_plain(pub)

    async def _rpc_dual(self, area: str, sender_id: str, msg: dict) -> dict:
        """DUAL message ingress (ref processDualMessages)."""
        self._authorize_peer(area)
        st = self.areas.get(area)
        if st is not None and st.dual is not None:
            st.dual.handle_message(sender_id, msg)
        return {}

    def _on_dual_parent_change(self, st: KvStoreArea, root, parent) -> None:
        """Full-sync with a newly adopted SPT parent: publications that
        flooded over the tree while this node was attaching would
        otherwise be missed until the periodic anti-entropy sync (ref
        dual parent-change sync behavior). Only the SELECTED flooding
        root's tree matters — parent churn on secondary roots must not
        trigger sync storms."""
        if parent is None or st.dual is None:
            return
        if st.dual.current_root() != root:
            return
        peer = st.peers.get(parent)
        if peer is not None and peer.state == KvStorePeerState.INITIALIZED:
            peer.state = KvStorePeerState.IDLE
            self._sync_wakeup.set()

    def _dual_send(self, st: KvStoreArea, peer_name: str, msg: dict) -> None:
        """Fire-and-forget DUAL egress over the peer's session; transport
        loss is healed by the next update/peer-FSM round trip."""
        peer = st.peers.get(peer_name)
        if peer is None or peer.client is None:
            return

        async def send(client=peer.client):
            try:
                await client.request(
                    "kvstore.dual",
                    {
                        "area": st.area,
                        "sender_id": self.node_name,
                        "msg": msg,
                    },
                )
            except asyncio.CancelledError:
                raise
            except Exception:
                # a lost DUAL message on a "healthy" session would leave
                # permanently divergent tree state (missing child claim,
                # querier stuck ACTIVE). Treat transport failure like a
                # flood failure: reset the session — the peer_down/up
                # cycle discards pending replies and re-introduces state
                # on both sides.
                counters.increment(
                    f"kvstore.{self.node_name}.dual_send_failure"
                )
                log.info(
                    "%s: dual send to %s failed; resetting peer",
                    self.name, peer_name,
                )
                self._reset_peer(st, peer)

        self.add_task(send(), name=f"{self.name}.dual:{peer_name}")

    async def _rpc_dump_hashes(self, area: str, prefix: str = "") -> dict:
        self._authorize_peer(area)
        st = self.areas[area]
        filters = KvStoreFilters(key_prefixes=(prefix,) if prefix else ())
        return to_plain(dump_hash_with_filters(area, st.kv, filters))

    def _decrement_out_ttls(self, pub: Publication) -> None:
        """Outgoing finite TTLs decay by ttl_decrement_ms so a key cannot
        circulate forever (ref kTtlDecrement flood semantics)."""
        dec = self.cfg.ttl_decrement_ms
        for key in list(pub.key_vals):
            v = pub.key_vals[key]
            if v.ttl_ms == TTL_INFINITY:
                continue
            remaining = v.ttl_ms - dec
            if remaining <= 0:
                del pub.key_vals[key]
                continue
            pub.key_vals[key] = Value(
                version=v.version,
                originator_id=v.originator_id,
                value=v.value,
                ttl_ms=remaining,
                ttl_version=v.ttl_version,
                hash=v.hash,
                origin_node=v.origin_node,
                origin_event_id=v.origin_event_id,
                origin_ts_ms=v.origin_ts_ms,
            )

    # -- merge + publish + flood (ref mergePublication KvStore.cpp:3394) ---

    def _merge_and_flood(self, pub: Publication, sender_id: str = "") -> None:
        t0 = time.monotonic()
        st = self.areas[pub.area]
        # fleet-convergence origin stamp: a locally-originated publication
        # (module write, ctrl write, beacon/probe origination) is THE
        # origin event — stamp it once here; flood merge carries the stamp
        # unchanged so every receiver can attribute its convergence work
        # (and its FIB ack) back to this event
        if not sender_id:
            self._origin_seq += 1
            event_id = f"{self.node_name}:{self._origin_seq}"
            ts_ms = time.time() * 1000.0
            for val in pub.key_vals.values():
                if val.origin_node is None and val.value is not None:
                    val.origin_node = self.node_name
                    val.origin_event_id = event_id
                    val.origin_ts_ms = ts_ms
        stats = MergeStats()
        updates = merge_key_values(st.kv, pub.key_vals, stats=stats)
        counters.increment(
            f"kvstore.{self.node_name}.updated_key_vals", len(updates)
        )
        # flood-latency probes: every RECEIVING store stamps propagation
        # delay at merge time, so one probing node maps the whole
        # fleet's flood latency (measurement is unconditional — it only
        # fires when probe keys actually flow)
        for key, val in updates.items():
            if (
                key.startswith(FLOOD_PROBE_PREFIX)
                and val.value is not None
                and val.originator_id != self.node_name
            ):
                self._record_probe_rtt(val)
        for key in updates:
            live = st.kv.get(key)
            if live is not None:
                st.ttl_queue.track(key, live)
        self._resched_ttl()

        # self-originated override protection: if a merged update beat one of
        # our persisted keys, re-advertise with a bumped version
        # (ref KvStore.cpp advertiseSelfOriginatedKeys / key-override check)
        for key in list(updates):
            own = st.self_originated.get(key)
            if own is None or not own.persisted:
                continue
            live = st.kv[key]
            if live.originator_id != self.node_name or live.value != own.value.value:
                self._persist_self_originated(
                    st, key, own.value.value, own.value.ttl_ms
                )
        if not updates and not pub.expired_keys:
            return
        out = Publication(
            key_vals=updates,
            expired_keys=list(pub.expired_keys),
            node_ids=list(pub.node_ids),
            area=pub.area,
        )
        # trace root: one topology event enters here and carries a single
        # trace_id through decision -> fib -> platform programming ack.
        # The origin stamp of the winning values links this node's span
        # tree to the remote (or local) origin event — the cross-node
        # stitch the fleet-convergence view joins on.
        origin_attrs: dict = {}
        for val in updates.values():
            if val.origin_event_id is not None:
                origin_attrs = {
                    "origin_node": val.origin_node,
                    "origin_event_id": val.origin_event_id,
                    "origin_ts_ms": val.origin_ts_ms,
                }
                break
        ctx = tracer.start_trace(
            "convergence",
            start=t0,
            node=self.node_name,
            area=pub.area,
            origin=sender_id or "local",
            num_keys=len(updates),
            num_expired=len(pub.expired_keys),
            **origin_attrs,
        )
        if ctx is not None:
            tracer.record_span(
                ctx, "kvstore.publication", t0, time.monotonic(),
                node=self.node_name, sender=sender_id or "local",
            )
        self._publish_local(out, trace=ctx)
        if updates:
            self._flood(st, out, sender_id=sender_id)

    def _publish_local(self, pub: Publication, trace=None) -> None:
        # receive stamp for the input black-box recorder: Decision logs
        # each event at the time THIS store handed it over, so replay
        # timelines show kvstore-merge time, not ingest-dequeue time
        pub.recv_t = time.monotonic()
        self._updates_q.push(pub, trace=trace)

    def _flood(self, st: KvStoreArea, pub: Publication, sender_id: str) -> None:
        """Fan out to INITIALIZED peers not already on the publication's
        path (ref floodPublication KvStore.cpp:3155-3290)."""
        flood = Publication(
            key_vals=dict(pub.key_vals),
            node_ids=list(pub.node_ids) + [self.node_name],
            area=st.area,
        )
        self._decrement_out_ttls(flood)
        if not flood.key_vals:
            return
        # DUAL flood optimization: restrict the fan-out to the spanning
        # tree (parent + children) when one is converged; None falls back
        # to full mesh (no reachable root / mid-diffusion), and KvStore's
        # periodic full sync heals any reconvergence-window gaps
        # (ref Dual.h:27-100 + floodPublication's SPT peer selection)
        spt = st.dual.flood_peers() if st.dual is not None else None
        if spt is not None:
            counters.increment(
                f"kvstore.{self.node_name}.flood_spt", len(spt)
            )
        for peer in st.peers.values():
            if spt is not None and peer.node_name not in spt:
                continue
            # Flood to INITIALIZED peers, and to SYNCING peers with a live
            # session: a merge landing between a peer's dump-request and our
            # sync completion would otherwise never reach it (the 3-way
            # exchange only covers keys present at dump time). IDLE peers
            # catch up via the eventual full sync.
            if peer.state == KvStorePeerState.IDLE or (
                peer.state == KvStorePeerState.SYNCING and peer.client is None
            ):
                continue
            if peer.node_name == sender_id or peer.node_name in pub.node_ids:
                continue
            self.add_task(
                self._flood_to_peer(st, peer, flood),
                name=f"{self.name}.flood:{peer.node_name}",
            )

    async def _flood_to_peer(
        self, st: KvStoreArea, peer: Peer, pub: Publication
    ) -> None:
        await self._flood_rate_limit()
        if peer.state == KvStorePeerState.IDLE:
            return  # peer torn down while we waited; sync loop owns retry
        if peer.client is None:
            # INITIALIZED/SYNCING without a session is inconsistent — demote
            # so the sync loop re-establishes it
            self._reset_peer(st, peer)
            return
        try:
            t0 = time.monotonic()
            # chaos seam: lands in the transport-failure path below, which
            # must reset the peer session for re-sync
            maybe_fail("kvstore.flood")
            await peer.client.request(
                "kvstore.set_key_vals",
                {
                    "area": st.area,
                    "publication": to_plain(pub),
                    "sender_id": self.node_name,
                },
            )
            counters.add_stat_value(
                "kvstore.flood_ms", (time.monotonic() - t0) * 1000.0
            )
            counters.increment(f"kvstore.{self.node_name}.thrift.num_flood_sent")
        except asyncio.CancelledError:
            raise
        except Exception as e:
            # transport failure resets the peer to IDLE for re-sync
            # (ref processThriftFailure KvStore.cpp:2134-2141)
            counters.increment(
                f"kvstore.{self.node_name}.thrift.num_flood_failure"
            )
            log.info(
                "%s: flood to %s failed: %s", self.name, peer.node_name, e
            )
            self._reset_peer(st, peer)

    async def _flood_rate_limit(self) -> None:
        """Token bucket (ref flood rate-limit + buffered batch)."""
        rate = self.cfg.flood_rate_msgs_per_sec
        if rate <= 0:
            return
        burst = max(1.0, float(self.cfg.flood_rate_burst_size or 1))
        while True:
            now = time.monotonic()
            self._flood_tokens = min(
                burst, self._flood_tokens + (now - self._flood_tokens_ts) * rate
            )
            self._flood_tokens_ts = now
            if self._flood_tokens >= 1.0:
                self._flood_tokens -= 1.0
                return
            await asyncio.sleep((1.0 - self._flood_tokens) / rate)

    # -- peer management + sync FSM ----------------------------------------

    async def _peer_updates_loop(self) -> None:
        while True:
            event = await self._peer_updates.get()
            for area, area_event in event.items():
                if not isinstance(area_event, AreaPeerEvent):
                    area_event = from_plain(area_event, AreaPeerEvent)
                self._handle_peer_event(area, area_event)

    def _handle_peer_event(self, area: str, ev: AreaPeerEvent) -> None:
        st = self.areas.get(area)
        if st is None:
            log.warning("%s: peer event for unknown area %r", self.name, area)
            return
        for name in ev.peers_to_del:
            peer = st.peers.pop(name, None)
            if peer is not None and peer.client is not None:
                self.add_task(
                    peer.client.close(), name=f"{self.name}.close:{name}"
                )
            if peer is not None and st.dual is not None:
                st.dual.peer_down(name)
        for name, spec in ev.peers_to_add.items():
            existing = st.peers.get(name)
            if existing is not None and existing.spec == spec:
                continue
            if existing is not None and existing.client is not None:
                self.add_task(
                    existing.client.close(), name=f"{self.name}.close:{name}"
                )
            if existing is not None and st.dual is not None:
                # spec change = new incarnation: the old one's distances/
                # child role must not survive into the new session
                st.dual.peer_down(name)
            st.peers[name] = Peer(node_name=name, spec=spec)
            counters.increment(f"kvstore.{self.node_name}.peers_added")
        self._initial_peers_received = True
        self._sync_wakeup.set()
        self._maybe_signal_initial_sync()  # empty initial event => synced

    def _reset_peer(self, st: KvStoreArea, peer: Peer) -> None:
        if st.peers.get(peer.node_name) is not peer:
            return
        peer.state = KvStorePeerState.IDLE
        peer.backoff.report_error()
        if st.dual is not None:
            st.dual.peer_down(peer.node_name)
        if peer.client is not None:
            client, peer.client = peer.client, None
            self.add_task(
                client.close(), name=f"{self.name}.close:{peer.node_name}"
            )
        self._sync_wakeup.set()

    async def _anti_entropy_loop(self) -> None:
        """Periodic full-sync round robin over INITIALIZED peers
        (cfg.sync_interval_s; role of the reference's periodic KvStore
        sync): bounds how long ANY flood gap can persist — an SPT
        reconvergence window, or a message lost without a transport
        error. One stalest peer per area per tick keeps the overhead
        O(1); every peer is re-synced within peers*interval."""
        while True:
            await asyncio.sleep(self.cfg.sync_interval_s)
            now = time.monotonic()
            for st in self.areas.values():
                cands = [
                    p
                    for p in st.peers.values()
                    if p.state == KvStorePeerState.INITIALIZED
                ]
                if not cands:
                    continue
                stalest = min(cands, key=lambda p: p.last_full_sync)
                if now - stalest.last_full_sync >= self.cfg.sync_interval_s:
                    stalest.state = KvStorePeerState.IDLE
                    counters.increment(
                        f"kvstore.{self.node_name}.anti_entropy_syncs"
                    )
                    self._sync_wakeup.set()

    async def _sync_loop(self) -> None:
        """Drive IDLE peers through full sync, bounded by the parallel-sync
        limit which doubles on progress (ref requestSync KvStore.cpp)."""
        in_flight: set[str] = set()

        while True:
            self._sync_wakeup.clear()
            idle = [
                (st, p)
                for st in self.areas.values()
                for p in st.peers.values()
                if p.state == KvStorePeerState.IDLE
                and p.node_name not in in_flight
            ]
            started = False
            for st, peer in idle:
                if len(in_flight) >= self._parallel_sync_limit:
                    break
                if not peer.backoff.can_try_now():
                    continue
                peer.state = KvStorePeerState.SYNCING
                in_flight.add(peer.node_name)
                started = True

                async def run_sync(st=st, peer=peer):
                    try:
                        await self._full_sync(st, peer)
                    finally:
                        in_flight.discard(peer.node_name)
                        self._sync_wakeup.set()

                self.add_task(
                    run_sync(), name=f"{self.name}.sync:{peer.node_name}"
                )
            if started:
                continue
            # Nothing startable: wait for wakeup, or the earliest backoff
            # retry. Peers blocked only by the concurrency cap have no
            # timeout of their own — a sync completion sets the wakeup.
            at_capacity = len(in_flight) >= self._parallel_sync_limit
            delays = [
                p.backoff.time_until_retry_s()
                for st in self.areas.values()
                for p in st.peers.values()
                if p.state == KvStorePeerState.IDLE
                and p.node_name not in in_flight
                and not p.backoff.can_try_now()
            ] if not at_capacity else []
            timeout = min(delays) if delays else None
            try:
                await asyncio.wait_for(
                    self._sync_wakeup.wait(),
                    None if timeout is None else max(0.01, timeout),
                )
            except asyncio.TimeoutError:
                pass

    def _make_peer_client(self, peer: Peer) -> RpcClient:
        """Peer session, TLS-wrapped when the peer plane is secured; the
        peer's certificate must claim its NODE NAME (CN/SAN identity
        pinning — CA membership alone would let any node impersonate
        any other)."""
        return RpcClient(
            peer.spec.peer_addr,
            peer.spec.ctrl_port,
            name=f"{self.node_name}->{peer.node_name}",
            ssl=self._client_ssl,
            expected_peer=(
                peer.node_name if self._client_ssl is not None else ""
            ),
        )

    async def _full_sync(self, st: KvStoreArea, peer: Peer) -> None:
        """3-way full sync, initiator side (ref requestThriftPeerSync
        KvStore.cpp:1838, processThriftSuccess :1974, finalizeFullSync
        :3022)."""
        t0 = time.monotonic()
        try:
            if peer.client is None:
                peer.client = self._make_peer_client(peer)
            hashes = {k: to_plain(v) for k, v in st.hashes().items()}
            resp = await peer.client.request(
                "kvstore.dump_filtered",
                {"area": st.area, "key_val_hashes": hashes},
            )
            pub = from_plain(resp, Publication)
            # merge peer's better values; flood onward (we are now part of
            # the flood topology for these updates)
            self._merge_and_flood(
                Publication(
                    key_vals=pub.key_vals,
                    node_ids=[peer.node_name],
                    area=st.area,
                ),
                sender_id=peer.node_name,
            )
            # finalize: send back full values for keys where ours is better
            finalize = {
                k: st.kv[k] for k in pub.to_be_updated_keys if k in st.kv
            }
            if finalize:
                fin_pub = Publication(
                    key_vals=dict(finalize),
                    node_ids=[self.node_name],
                    area=st.area,
                )
                self._decrement_out_ttls(fin_pub)
                await peer.client.request(
                    "kvstore.set_key_vals",
                    {
                        "area": st.area,
                        "publication": to_plain(fin_pub),
                        "sender_id": self.node_name,
                    },
                )
        except asyncio.CancelledError:
            raise
        except Exception as e:
            log.info(
                "%s: full sync with %s failed: %s", self.name, peer.node_name, e
            )
            counters.increment(f"kvstore.{self.node_name}.full_sync_failure")
            self._reset_peer(st, peer)
            return

        if st.peers.get(peer.node_name) is not peer:
            return  # peer replaced mid-sync
        if peer.state != KvStorePeerState.SYNCING or peer.client is None:
            # a concurrent _reset_peer (failed flood) demoted us while the
            # last RPC was resolving: stay IDLE and let the sync loop retry
            return
        peer.state = KvStorePeerState.INITIALIZED
        peer.backoff.report_success()
        peer.last_full_sync = time.monotonic()
        if st.dual is not None:
            st.dual.peer_up(peer.node_name)
        self._parallel_sync_limit = min(
            self.cfg.max_parallel_initial_syncs, self._parallel_sync_limit * 2
        )
        counters.increment(f"kvstore.{self.node_name}.full_sync_success")
        counters.add_stat_value(
            f"kvstore.{self.node_name}.full_sync_ms",
            (time.monotonic() - t0) * 1e3,
        )
        self._events_q.push(KvStoreSyncEvent(peer.node_name, st.area))
        self._maybe_signal_initial_sync()

    def _maybe_signal_initial_sync(self) -> None:
        """Emit KVSTORE_SYNCED once every configured peer reached
        INITIALIZED (ref initialization gating, KvStore.cpp
        processInitializationEvent)."""
        if self._initialized_signalled or not self._initial_peers_received:
            return
        for st in self.areas.values():
            for p in st.peers.values():
                if p.state != KvStorePeerState.INITIALIZED:
                    return
        self._initialized_signalled = True
        boot_tracer.phase_mark(
            "kvstore_initial_sync",
            node=self.node_name,
            areas=len(self.areas),
            peers=sum(len(st.peers) for st in self.areas.values()),
        )
        self._updates_q.push(InitializationEvent.KVSTORE_SYNCED)

    # -- self-originated keys (ref KvStore.h:304-309) ----------------------

    async def _kv_requests_loop(self) -> None:
        while True:
            req = await self._kv_requests.get()
            self.process_key_value_request(req)

    def process_key_value_request(self, req: KeyValueRequest) -> None:
        st = self.areas.get(req.area)
        if st is None:
            log.warning(
                "%s: key-value request for unknown area %r", self.name, req.area
            )
            return
        if req.request_type == KeyValueRequestType.PERSIST:
            self._persist_self_originated(
                st, req.key, req.value, req.set_ttl or self.cfg.key_ttl_ms
            )
        elif req.request_type == KeyValueRequestType.SET:
            self._set_self_originated(
                st,
                req.key,
                req.value,
                req.version,
                req.set_ttl or self.cfg.key_ttl_ms,
            )
        elif req.request_type == KeyValueRequestType.CLEAR:
            self._unset_self_originated(st, req.key, req.value)

    def _persist_self_originated(
        self,
        st: KvStoreArea,
        key: str,
        value: Optional[bytes],
        ttl_ms: int,
        min_version: int = 1,
    ) -> None:
        """Advertise + own the key: version-bump to beat any existing value
        (ref persistSelfOriginatedKey)."""
        existing = st.kv.get(key)
        version = min_version
        if existing is not None:
            if (
                existing.originator_id == self.node_name
                and existing.value == value
            ):
                version = max(existing.version, min_version)  # ours, unchanged
            else:
                version = max(existing.version + 1, min_version)
        new_val = Value(
            version=version,
            originator_id=self.node_name,
            value=value,
            ttl_ms=ttl_ms,
            ttl_version=0,
        )
        st.self_originated[key] = SelfOriginatedValue(
            new_val, persisted=True, last_refresh=time.monotonic()
        )
        if ttl_ms != TTL_INFINITY:
            self._refresh_wakeup.set()
        self._merge_and_flood(
            Publication(key_vals={key: new_val}, area=st.area)
        )

    def _set_self_originated(
        self,
        st: KvStoreArea,
        key: str,
        value: Optional[bytes],
        version: Optional[int],
        ttl_ms: int,
    ) -> None:
        """One-shot set: ttl-refreshed but not defended
        (ref setSelfOriginatedKey)."""
        if version is None:
            existing = st.kv.get(key)
            version = (existing.version + 1) if existing is not None else 1
        new_val = Value(
            version=version,
            originator_id=self.node_name,
            value=value,
            ttl_ms=ttl_ms,
            ttl_version=0,
        )
        st.self_originated[key] = SelfOriginatedValue(
            new_val, persisted=False, last_refresh=time.monotonic()
        )
        if ttl_ms != TTL_INFINITY:
            self._refresh_wakeup.set()
        self._merge_and_flood(
            Publication(key_vals={key: new_val}, area=st.area)
        )

    def _unset_self_originated(
        self, st: KvStoreArea, key: str, tombstone: Optional[bytes]
    ) -> None:
        """Stop defending + advertise a short-ttl tombstone so the key ages
        out network-wide (ref unsetSelfOriginatedKey)."""
        st.self_originated.pop(key, None)
        existing = st.kv.get(key)
        version = (existing.version + 1) if existing is not None else 1
        new_val = Value(
            version=version,
            originator_id=self.node_name,
            value=tombstone if tombstone is not None else b"",
            ttl_ms=_TTL_ERASE_MS,
            ttl_version=0,
        )
        self._merge_and_flood(
            Publication(key_vals={key: new_val}, area=st.area)
        )

    async def _ttl_refresh_loop(self) -> None:
        """Periodically bump ttl_version on finite-ttl self-originated keys
        (ref advertiseTtlUpdates KvStore.h:512; refresh at ttl/4)."""
        while True:
            # refresh at a quarter of the SHORTEST finite self-originated
            # ttl (per-request set_ttl may be far below cfg.key_ttl_ms)
            finite = [
                own.value.ttl_ms
                for st in self.areas.values()
                for own in st.self_originated.values()
                if own.value.ttl_ms != TTL_INFINITY
            ]
            base_ms = min(finite) if finite else self.cfg.key_ttl_ms
            interval = max(0.02, base_ms / 1e3 / 4)
            # interruptible sleep: persisting a shorter-ttl key mid-sleep
            # must shorten the current cycle, not just the next one
            try:
                await asyncio.wait_for(self._refresh_wakeup.wait(), interval)
                self._refresh_wakeup.clear()
                continue  # recompute the interval with the new key set
            except asyncio.TimeoutError:
                pass
            for st in self.areas.values():
                refresh: dict[str, Value] = {}
                for key, own in st.self_originated.items():
                    if own.value.ttl_ms == TTL_INFINITY:
                        continue
                    live = st.kv.get(key)
                    if live is None or live.originator_id != self.node_name:
                        continue  # lost ownership; persist path defends
                    own.value.ttl_version = live.ttl_version + 1
                    own.last_refresh = time.monotonic()
                    refresh[key] = Value(
                        version=live.version,
                        originator_id=self.node_name,
                        value=None,  # ttl-only advertisement
                        ttl_ms=own.value.ttl_ms,
                        ttl_version=live.ttl_version + 1,
                        hash=live.hash,
                    )
                if refresh:
                    self._merge_and_flood(
                        Publication(key_vals=refresh, area=st.area)
                    )

    async def _ttl_alarm_loop(self) -> None:
        """Imminent-TTL alarm (ref KvStore.h:553-564): an owned
        finite-ttl adjacency key that has gone unrefreshed past 3/4 of
        its ttl is about to age out network-wide — the refresh pipeline
        is wedged or ownership was silently lost. Warn + count; the
        counter (kvstore.<node>.imminent_ttl_expiry) surfaces through
        Monitor/ctrl."""
        while True:
            finite = [
                own.value.ttl_ms
                for st in self.areas.values()
                for own in st.self_originated.values()
                if own.value.ttl_ms != TTL_INFINITY
            ]
            interval = max(0.05, (min(finite) if finite else
                                  self.cfg.key_ttl_ms) / 1e3 / 4)
            await asyncio.sleep(interval)
            self._check_imminent_ttls()

    def _check_imminent_ttls(self, now: Optional[float] = None) -> int:
        from openr_tpu.types import ADJ_DB_MARKER

        now = time.monotonic() if now is None else now
        flagged = 0
        for st in self.areas.values():
            for key, own in st.self_originated.items():
                if (
                    own.value.ttl_ms == TTL_INFINITY
                    or not key.startswith(ADJ_DB_MARKER)
                    or not own.last_refresh
                ):
                    continue
                stale_s = now - own.last_refresh
                if stale_s > own.value.ttl_ms / 1e3 * 0.75:
                    flagged += 1
                    counters.increment(
                        f"kvstore.{self.node_name}.imminent_ttl_expiry"
                    )
                    log.warning(
                        "%s: adj key %s unrefreshed for %.1fs "
                        "(ttl %.1fs) — imminent expiry",
                        self.name, key, stale_s,
                        own.value.ttl_ms / 1e3,
                    )
        return flagged

    # -- observatory: LSDB digest beacons + flood-latency probes -----------

    async def _digest_loop(self) -> None:
        """Advertise a TTL'd per-area LSDB digest beacon and compare
        every peer's beacon against our recent digests — two stores
        that silently disagree flip the kvstore.divergence.* gauges
        within one interval, fleet-wide, over the flooding fabric
        itself (same self-observation idiom as monitor:health)."""
        while True:
            await asyncio.sleep(self.cfg.digest_interval_s)
            if not self._probe_admitted():
                continue
            # each digest() hashes the whole store in one stretch of the
            # loop: a hold no event asked for, so it goes on the tracer's
            # background track
            taken = self._digests_taken()
            with tracer.hold(
                "kvstore.digest", areas=len(self.areas)
            ) as beat:
                self._advertise_digests()
                report = self._check_divergence()
                if beat is not None:
                    beat.set(
                        keys=sum(
                            a["keys"] for a in report["areas"].values()
                        ),
                        digests=self._digests_taken() - taken,
                    )

    def _digests_taken(self) -> int:
        return sum(st.digests_taken for st in self.areas.values())

    def _advertise_digests(self) -> None:
        ttl_ms = max(
            int(self.cfg.digest_interval_s * 1000 * _DIGEST_STALE_INTERVALS),
            2500,
        )
        key = f"{LSDB_DIGEST_PREFIX}{self.node_name}"
        for st in self.areas.values():
            digest, nkeys = st.digest()
            if not st.digest_history or st.digest_history[-1] != digest:
                st.digest_history.append(digest)
            self._digest_version += 1
            payload = json.dumps(
                {
                    "node": self.node_name,
                    "area": st.area,
                    "ts_ms": int(time.time() * 1000),
                    "digest": digest,
                    "keys": nkeys,
                },
                sort_keys=True,
            ).encode()
            self._merge_and_flood(
                Publication(
                    key_vals={
                        key: Value(
                            version=self._digest_version,
                            originator_id=self.node_name,
                            value=payload,
                            ttl_ms=ttl_ms,
                        )
                    },
                    area=st.area,
                )
            )
        counters.increment(f"kvstore.{self.node_name}.digest_advertisements")

    def _check_divergence(self) -> dict:
        """Compare every fresh peer beacon in each area against our
        digest history. Matching ANY recent local digest means the peer
        is merely behind on in-flight floods (a state we ourselves
        passed through); matching none of them is divergence."""
        now_ms = int(time.time() * 1000)
        stale_ms = int(
            self.cfg.digest_interval_s * 1000 * _DIGEST_STALE_INTERVALS
        )
        areas: dict[str, dict] = {}
        suspects: set[str] = set()
        for st in self.areas.values():
            digest, nkeys = st.digest()
            known = set(st.digest_history) | {digest}
            mismatched = []
            compared = 0
            for key, val in st.kv.items():
                if not key.startswith(LSDB_DIGEST_PREFIX) or val.value is None:
                    continue
                peer = key[len(LSDB_DIGEST_PREFIX):]
                if peer == self.node_name:
                    continue
                try:
                    blob = json.loads(val.value.decode())
                except (ValueError, UnicodeDecodeError):
                    continue
                if now_ms - int(blob.get("ts_ms", 0)) > stale_ms:
                    continue  # beacon older than its own TTL horizon
                compared += 1
                if blob.get("digest") not in known:
                    mismatched.append(
                        {
                            "peer": peer,
                            "digest": blob.get("digest"),
                            "keys": blob.get("keys"),
                            "ts_ms": blob.get("ts_ms"),
                        }
                    )
                    suspects.add(peer)
            areas[st.area] = {
                "local_digest": digest,
                "keys": nkeys,
                "compared": compared,
                "mismatched": mismatched,
            }
        diverged = sorted(suspects)
        if diverged and not self._divergence.get("diverged"):
            # edge-triggered monotonic event count: the gauge above says
            # "diverged NOW"; this says "how many times we ENTERED the
            # diverged state" — the series SLO burn-rate math needs
            counters.increment("kvstore.divergence.events")
        counters.set_counter(
            "kvstore.divergence.detected", 1.0 if diverged else 0.0
        )
        counters.set_counter(
            "kvstore.divergence.suspect_peers", float(len(diverged))
        )
        counters.set_counter(
            "kvstore.divergence.areas_diverged",
            float(sum(1 for a in areas.values() if a["mismatched"])),
        )
        counters.increment("kvstore.divergence.checks")
        self._divergence = {
            "node": self.node_name,
            "ts_ms": now_ms,
            "diverged": bool(diverged),
            "suspect_peers": diverged,
            "areas": areas,
        }
        return self._divergence

    async def _first_divergent_key(self, st: KvStoreArea, peer: Peer) -> dict:
        """Attribute a digest mismatch: pull the suspect peer's
        hash-only dump (the 3-way-sync comparison view) and report the
        lexicographically first key whose (version, ttl_version, hash)
        identity differs — the starting point of the operator's
        `breeze kv compare` drill-down."""
        client, temp = peer.client, False
        if client is None:
            client, temp = self._make_peer_client(peer), True
        try:
            resp = await client.request(
                "kvstore.dump_hashes", {"area": st.area, "prefix": ""}
            )
            theirs = from_plain(resp, Publication).key_vals
        finally:
            if temp:
                await client.close()
        mine = st.kv
        for key in sorted(set(mine) | set(theirs)):
            if key.startswith(MONITOR_KEY_PREFIX):
                continue
            m, t = mine.get(key), theirs.get(key)
            if m is None or t is None:
                return {
                    "first_divergent_key": key,
                    "reason": "missing_local" if m is None else "missing_peer",
                }
            if (m.version, m.ttl_version, m.hash) != (
                t.version, t.ttl_version, t.hash
            ):
                return {
                    "first_divergent_key": key,
                    "reason": "mismatch",
                    "local": {
                        "version": m.version,
                        "ttl_version": m.ttl_version,
                        "hash": m.hash,
                    },
                    "peer": {
                        "version": t.version,
                        "ttl_version": t.ttl_version,
                        "hash": t.hash,
                    },
                }
        # digests disagreed but the hash dumps agree: the store converged
        # between the peer's beacon and this dump — divergence was
        # transient and the next beacon tick clears the gauge
        return {"first_divergent_key": None, "reason": "converged"}

    async def _flood_probe_loop(self) -> None:
        """Opt-in: originate a timestamped synthetic key every interval;
        every receiving store measures propagation delay into the
        kvstore.flood_rtt_ms percentile windows — the first direct
        measurement of the fabric's flood latency."""
        while True:
            await asyncio.sleep(self.cfg.flood_probe_interval_s)
            if not self._probe_admitted():
                continue
            self._originate_flood_probe()

    def _probe_admitted(self) -> bool:
        """Overload admission for background anti-entropy traffic
        (runtime/overload.py): digest beacons and flood probes are the
        'probe' priority class — deferred (skip this interval, counted
        as overload.deferred_probes) from backpressure up. Live
        flooding is never gated here."""
        ctl = get_controller(self.node_name)
        return ctl is None or ctl.admit("probe")

    def _originate_flood_probe(self) -> None:
        ttl_ms = max(int(self.cfg.flood_probe_interval_s * 3000), 1000)
        self._probe_seq += 1
        self._probe_version += 1
        key = f"{FLOOD_PROBE_PREFIX}{self.node_name}"
        payload = json.dumps(
            {"node": self.node_name, "seq": self._probe_seq, "ts": time.time()}
        ).encode()
        for st in self.areas.values():
            self._merge_and_flood(
                Publication(
                    key_vals={
                        key: Value(
                            version=self._probe_version,
                            originator_id=self.node_name,
                            value=payload,
                            ttl_ms=ttl_ms,
                        )
                    },
                    area=st.area,
                )
            )
        counters.increment(f"kvstore.{self.node_name}.flood_probes_sent")

    def _record_probe_rtt(self, val: Value) -> None:
        """Receiving-side probe stamp. Cross-machine deployments measure
        origin wall clock vs ours, so the stat carries clock skew; on
        the in-process emulation it is pure flood-path latency."""
        try:
            blob = json.loads(val.value.decode())
            delay_ms = max(0.0, (time.time() - float(blob["ts"])) * 1000.0)
        except (ValueError, KeyError, TypeError, UnicodeDecodeError):
            return
        counters.add_stat_value("kvstore.flood_rtt_ms", delay_ms)
        counters.add_stat_value(
            f"kvstore.flood_rtt_ms.{val.originator_id}", delay_ms
        )
        counters.increment(f"kvstore.{self.node_name}.flood_probes_received")

    # -- fleet-convergence FIB-ack backchannel -----------------------------

    def record_convergence_ack(
        self,
        area: str,
        origin_node: str,
        origin_event_id: str,
        fleet_convergence_ms: float,
        component: str = "",
        component_ms: float = 0.0,
    ) -> None:
        """Called by Fib when a programmed-routes publication closes a
        trace carrying a remote (or local) origin stamp: append the ack
        to this node's ring and flood it as a TTL'd
        `monitor:conv-ack:<node>` key, so ANY node can join origin
        events to the fleet-wide set of FIB acks and render per-event
        fleet convergence (origin -> last ack anywhere). `component` is
        the dominant latency-budget component of this node's epoch, so
        the fleet join can name the straggler STAGE, not just the node."""
        ack = {
            "event": origin_event_id,
            "origin": origin_node,
            "node": self.node_name,
            "ms": round(float(fleet_convergence_ms), 3),
            "ts_ms": int(time.time() * 1000),
        }
        if component:
            ack["comp"] = component
            ack["comp_ms"] = round(float(component_ms), 3)
        self._conv_acks.append(ack)
        counters.increment(f"kvstore.{self.node_name}.conv_acks")
        st = self.areas.get(area) or next(iter(self.areas.values()), None)
        if st is None:
            return
        self._conv_ack_version += 1
        payload = json.dumps(
            {"node": self.node_name, "acks": list(self._conv_acks)}
        ).encode()
        self._merge_and_flood(
            Publication(
                key_vals={
                    f"{CONV_ACK_PREFIX}{self.node_name}": Value(
                        version=self._conv_ack_version,
                        originator_id=self.node_name,
                        value=payload,
                        ttl_ms=_CONV_ACK_TTL_MS,
                    )
                },
                area=st.area,
            )
        )

    # -- TTL expiry --------------------------------------------------------

    def _resched_ttl(self) -> None:
        """New TTL entries may expire sooner than the current sleep."""
        self._ttl_wakeup.set()

    async def _ttl_loop(self) -> None:
        while True:
            delays = [
                st.ttl_queue.next_expiry_in_s() for st in self.areas.values()
            ]
            delays = [d for d in delays if d is not None]
            timeout = min(delays) if delays else None
            try:
                await asyncio.wait_for(
                    self._ttl_wakeup.wait(),
                    None if timeout is None else max(0.01, timeout),
                )
                self._ttl_wakeup.clear()
                continue  # new entries tracked; recompute earliest expiry
            except asyncio.TimeoutError:
                pass
            for st in self.areas.values():
                expired = st.ttl_queue.expire(st.kv)
                if not expired:
                    continue
                # A persisted self-originated key that expired locally (e.g.
                # the refresh tick was starved past ttl) must be defended,
                # not dropped: re-advertise it immediately.
                reported: list[str] = []
                for key in expired:
                    own = st.self_originated.get(key)
                    if own is not None and own.persisted:
                        # min_version beats copies of the expired incarnation
                        # other stores may still hold
                        self._persist_self_originated(
                            st,
                            key,
                            own.value.value,
                            own.value.ttl_ms,
                            min_version=own.value.version + 1,
                        )
                    else:
                        st.self_originated.pop(key, None)
                        reported.append(key)
                counters.increment(
                    f"kvstore.{self.node_name}.expired_keys", len(reported)
                )
                if reported:
                    # expiry publications are local-only: every store ages
                    # keys independently (ref KvStore.cpp cleanup)
                    self._publish_local(
                        Publication(expired_keys=reported, area=st.area)
                    )

    # -- module API (role of semifuture_* KvStore.h:774-840) ---------------

    async def get_key_vals(self, area: str, keys: list[str]) -> dict[str, Value]:
        st = self.areas[area]
        return {k: st.kv[k] for k in keys if k in st.kv}

    async def dump_all(
        self, area: str, prefix: str = ""
    ) -> dict[str, Value]:
        st = self.areas[area]
        filters = KvStoreFilters(key_prefixes=(prefix,) if prefix else ())
        return dump_all_with_filters(area, st.kv, filters).key_vals

    async def set_key_vals(self, area: str, key_vals: dict[str, Value]) -> None:
        """Locally-originated write (ctrl API path)."""
        self._merge_and_flood(Publication(key_vals=dict(key_vals), area=area))

    async def dump_hashes(self, area: str, prefix: str = "") -> dict[str, Value]:
        """Hash-only view (the anti-entropy comparison dump) — same
        stripping the peer-facing kvstore.dump_hashes RPC uses."""
        st = self.areas[area]
        filters = KvStoreFilters(key_prefixes=(prefix,) if prefix else ())
        return dump_hash_with_filters(area, st.kv, filters).key_vals

    async def divergence_report(self, resolve: bool = True) -> dict:
        """Fresh divergence verdict (ctrl.kvstore.divergence). With
        `resolve`, each suspect peer's mismatch is attributed to its
        first-divergent key by pulling that peer's hash dump — an RPC
        per suspect, so resolution runs on demand, not on the beacon
        tick."""
        report = self._check_divergence()
        if not resolve or not report["diverged"]:
            return report
        for area, entry in report["areas"].items():
            st = self.areas[area]
            for mm in entry["mismatched"]:
                peer = st.peers.get(mm["peer"])
                if peer is None:
                    mm["resolution"] = {"error": "suspect is not a peer"}
                    continue
                try:
                    mm["resolution"] = await self._first_divergent_key(
                        st, peer
                    )
                except asyncio.CancelledError:
                    raise
                # the failure is surfaced to the ctrl caller in the
                # report row itself, not swallowed
                # lint: allow(broad-except) error returned in the report
                except Exception as e:
                    mm["resolution"] = {"error": str(e)}
        return report

    def get_area_summary(self) -> dict[str, dict]:
        """ref getKvStoreAreaSummary: per-area key count, payload bytes,
        peer names."""
        return {
            area: {
                "key_count": len(st.kv),
                "size_bytes": sum(
                    len(v.value or b"") for v in st.kv.values()
                ),
                "peers": sorted(st.peers),
            }
            for area, st in self.areas.items()
        }

    def get_peers(self, area: str) -> dict[str, PeerSpec]:
        st = self.areas[area]
        return {
            name: PeerSpec(
                peer_addr=p.spec.peer_addr,
                ctrl_port=p.spec.ctrl_port,
                state=p.state,
            )
            for name, p in st.peers.items()
        }
