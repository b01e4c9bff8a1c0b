"""DUAL flood-topology optimization — SPT flooding for KvStore.

Role of the reference's openr/kvstore/Dual.{h,cpp} (:27-100): full-mesh
flooding costs O(peers²) messages per publication; the Diffusing Update
Algorithm (EIGRP-style) computes a spanning tree per flood root over the
live peer graph, and publications then travel only tree edges
(parent + children), reaching every node exactly once.

Per root, each node runs the classic DUAL state machine:

  PASSIVE  route believed loop-free; successor (parent toward the root)
           satisfies the feasibility condition FC: the neighbor's
           reported distance is strictly below this node's feasible
           distance FD (so routing through it can never loop back).
  ACTIVE   the successor was lost/worsened and no neighbor satisfies
           FC: the node freezes its route, QUERYs every neighbor, and
           the computation DIFFUSES — a queried neighbor whose own
           successor is invalidated goes ACTIVE itself and defers its
           REPLY until its own subtree settles. When all replies are
           in, FD resets and the best neighbor is adopted (ref Dual.h
           PASSIVE/ACTIVE0-3; this implementation collapses the three
           ACTIVE sub-states into reply bookkeeping).

Parent adoption is signalled with FLOOD_TOPO_SET child add/remove
commands (ref KvStore.h:438-456), giving each node its child set; the
flood set is {parent} | children. Nodes with no reachable root fall
back to full-mesh flooding (and KvStore's periodic full sync + TTL
refresh heal any transient tree breakage during reconvergence).

Messages ride the existing peer RPC sessions ("kvstore.dual"), like the
reference rides its thrift sessions.
"""

from __future__ import annotations

import enum
import logging
from dataclasses import dataclass, field
from typing import Callable, Optional

log = logging.getLogger(__name__)

INF = 1 << 30
_LINK_COST = 1  # peer-graph edges are unit cost (ref Dual unit metric)


class DualState(enum.Enum):
    PASSIVE = 0
    ACTIVE = 1


@dataclass
class _RootState:
    """Per-root DUAL bookkeeping on one node."""

    root: str
    dist: int = INF
    feasible_dist: int = INF
    successor: Optional[str] = None
    state: DualState = DualState.PASSIVE
    reported: dict = field(default_factory=dict)  # peer -> its distance
    pending_replies: set = field(default_factory=set)
    # peers whose QUERY we must answer once we go PASSIVE again
    deferred_replies: set = field(default_factory=set)
    children: set = field(default_factory=set)


class Dual:
    """One per KvStore area. `send(peer, msg)` delivers a dual message
    over that peer's session (fire-and-forget; losses are healed by the
    next update), `is_root` marks this node as a flood-root candidate
    (ref flood_root_id config)."""

    def __init__(
        self,
        node_name: str,
        send: Callable[[str, dict], None],
        is_root: bool = False,
        on_parent_change: Optional[Callable[[str, Optional[str]], None]] = None,
    ):
        self.node_name = node_name
        self._send = send
        self.is_root = is_root
        # (root, new_parent) hook: KvStore full-syncs with a newly
        # adopted parent so publications flooded over the tree while it
        # was forming are caught up (ref dual parent-change sync)
        self._on_parent_change = on_parent_change
        self.peers: set[str] = set()
        self.roots: dict[str, _RootState] = {}
        if is_root:
            rs = self._root_state(node_name)
            rs.dist = 0
            rs.feasible_dist = 0

    # -- introspection -------------------------------------------------------

    def _root_state(self, root: str) -> _RootState:
        rs = self.roots.get(root)
        if rs is None:
            rs = self.roots[root] = _RootState(root=root)
        return rs

    def current_root(self) -> Optional[str]:
        """Lowest-id root with a loop-free PASSIVE route (ref
        getSptRootId: ordered preference across known roots)."""
        for root in sorted(self.roots):
            rs = self.roots[root]
            if rs.state is DualState.PASSIVE and rs.dist < INF:
                return root
        return None

    def flood_peers(self) -> Optional[set[str]]:
        """SPT peers to flood to, or None => full-mesh fallback (no
        converged root, or mid-diffusion)."""
        root = self.current_root()
        if root is None:
            return None
        rs = self.roots[root]
        out = set(rs.children) & self.peers
        if rs.successor is not None:
            out.add(rs.successor)
        return out

    def status(self) -> dict:
        return {
            root: {
                "state": rs.state.name,
                "dist": rs.dist,
                "parent": rs.successor,
                "children": sorted(rs.children),
            }
            for root, rs in sorted(self.roots.items())
        }

    # -- peer lifecycle ------------------------------------------------------

    def peer_up(self, peer: str) -> None:
        # NO early return for known peers: (re)introducing every root on
        # peer_up is idempotent and heals any messages lost while the
        # session was down or half-open — including our child claim on
        # the parent (a lost topo_set would otherwise silently detach
        # this node's subtree from the flood tree).
        self.peers.add(peer)
        self._introduce(peer)
        # and ask the peer for the same. The two ends of a session come
        # up one after the other: what the first says reaches an end
        # that does not track it yet and is dropped (handle_message),
        # and the second may know no root to say anything about — the
        # first would never speak again, and a node whose every peer
        # came up first would stay outside the tree for good, reached by
        # nobody's flooding.
        self._send(peer, {"type": "hello"})

    def _introduce(self, peer: str) -> None:
        for root, rs in self.roots.items():
            self._send(peer, self._update_msg(root, peer))
            if rs.successor == peer:
                self._send(
                    peer, {"type": "topo_set", "root": root, "child": True}
                )

    def peer_down(self, peer: str) -> None:
        self.peers.discard(peer)
        for rs in self.roots.values():
            rs.reported.pop(peer, None)
            rs.children.discard(peer)
            rs.deferred_replies.discard(peer)
            if peer in rs.pending_replies:
                rs.pending_replies.discard(peer)
                self._maybe_finish_active(rs)
            if rs.successor == peer:
                self._local_computation(rs)

    # -- message handling ----------------------------------------------------

    def handle_message(self, sender: str, msg: dict) -> None:
        mtype = msg.get("type")
        root = msg.get("root", "")
        if sender not in self.peers:
            # message from a peer we don't (or no longer) track — e.g.
            # one in flight across a peer deletion. Adopting it would
            # resurrect a ghost that no lifecycle event ever removes (and
            # that flooding can't reach); drop it — the sender's next
            # peer_up re-introduces state on both sides. This covers
            # topo_set too: an in-flight child claim from a removed peer
            # would leak a ghost child forever (peer_up re-sends the
            # claim and asks for the peer's, so dropping loses nothing).
            return
        if mtype == "hello":
            self._introduce(sender)
            return
        if mtype == "topo_set":
            rs = self._root_state(root)
            if msg.get("child"):
                rs.children.add(sender)
            else:
                rs.children.discard(sender)
            return
        rs = self._root_state(root)
        dist = int(msg.get("dist", INF))
        if mtype == "update":
            rs.reported[sender] = dist
            self._local_computation(rs)
        elif mtype == "query":
            rs.reported[sender] = dist
            was_passive = rs.state is DualState.PASSIVE
            self._local_computation(rs)
            if rs.state is DualState.PASSIVE:
                self._send(sender, self._reply_msg(root, rs, sender))
            elif was_passive:
                # this query invalidated our route: the computation
                # DIFFUSES — answer once our own subtree settles
                rs.deferred_replies.add(sender)
            else:
                # already mid-diffusion: reply with the frozen distance
                # immediately (EIGRP's non-successor-query rule) so two
                # mutually-querying nodes can never deadlock
                self._send(sender, self._reply_msg(root, rs, sender))
        elif mtype == "reply":
            rs.reported[sender] = dist
            if sender in rs.pending_replies:
                rs.pending_replies.discard(sender)
                self._maybe_finish_active(rs)

    # -- DUAL core -----------------------------------------------------------

    def _adv_dist(self, rs: _RootState, peer: str) -> int:
        """Split horizon with poisoned reverse: a node's distance is
        advertised as INF to its own successor — the neighbor a route
        goes THROUGH must never route back through us, and without this
        two mutually-dependent neighbors count to infinity one update at
        a time when the root disconnects."""
        return INF if rs.successor == peer else rs.dist

    def _update_msg(self, root: str, peer: str) -> dict:
        rs = self.roots[root]
        return {"type": "update", "root": root, "dist": self._adv_dist(rs, peer)}

    def _reply_msg(self, root: str, rs: _RootState, peer: str) -> dict:
        return {"type": "reply", "root": root, "dist": self._adv_dist(rs, peer)}

    def _best_neighbor(self, rs: _RootState, feasible_only: bool):
        """(neighbor, via-distance) minimizing reported+cost; ties break
        on name for determinism."""
        best = None
        for peer in sorted(rs.reported):
            if peer not in self.peers:
                continue
            rep = rs.reported[peer]
            if rep >= INF:
                continue
            if feasible_only and not rep < rs.feasible_dist:
                continue
            via = rep + _LINK_COST
            if best is None or via < best[1]:
                best = (peer, via)
        return best

    def _local_computation(self, rs: _RootState) -> None:
        """Re-evaluate the successor after any input change (ref
        Dual::processUpdate / peerDown)."""
        if rs.root == self.node_name:
            return  # we ARE the root: dist 0, no successor
        if rs.state is DualState.ACTIVE:
            return  # frozen until the diffusing computation completes
        old = (rs.dist, rs.successor)
        best = self._best_neighbor(rs, feasible_only=True)
        if best is not None:
            rs.successor, rs.dist = best[0], best[1]
            rs.feasible_dist = min(rs.feasible_dist, rs.dist)
        else:
            any_best = self._best_neighbor(rs, feasible_only=False)
            if any_best is None:
                # no path at all: converge on unreachable
                rs.successor, rs.dist = None, INF
                rs.feasible_dist = INF
            else:
                # reachable but no FEASIBLE successor: diffuse
                self._go_active(rs)
                return
        self._after_route_change(rs, old)

    def _go_active(self, rs: _RootState) -> None:
        rs.state = DualState.ACTIVE
        old = (rs.dist, rs.successor)
        best = self._best_neighbor(rs, feasible_only=False)
        assert best is not None
        rs.successor, rs.dist = best[0], best[1]
        rs.feasible_dist = rs.dist  # FD resets at the ACTIVE transition
        rs.pending_replies = set(self.peers)
        self._after_route_change(rs, old, send_updates=False)
        if not rs.pending_replies:
            self._finish_active(rs)
            return
        for peer in list(rs.pending_replies):
            self._send(
                peer,
                {
                    "type": "query",
                    "root": rs.root,
                    "dist": self._adv_dist(rs, peer),
                },
            )

    def _maybe_finish_active(self, rs: _RootState) -> None:
        if rs.state is DualState.ACTIVE and not rs.pending_replies:
            self._finish_active(rs)

    def _finish_active(self, rs: _RootState) -> None:
        rs.state = DualState.PASSIVE
        rs.feasible_dist = INF  # free choice now that the diffusion ended
        self._local_computation(rs)
        # answer neighbors that queried us mid-diffusion
        for peer in list(rs.deferred_replies):
            rs.deferred_replies.discard(peer)
            if peer in self.peers:
                self._send(peer, self._reply_msg(rs.root, rs, peer))

    def _after_route_change(
        self, rs: _RootState, old: tuple, send_updates: bool = True
    ) -> None:
        dist_changed = rs.dist != old[0]
        parent_changed = rs.successor != old[1]
        if parent_changed:
            if old[1] is not None and old[1] in self.peers:
                self._send(
                    old[1],
                    {"type": "topo_set", "root": rs.root, "child": False},
                )
            if rs.successor is not None:
                self._send(
                    rs.successor,
                    {"type": "topo_set", "root": rs.root, "child": True},
                )
            if self._on_parent_change is not None:
                self._on_parent_change(rs.root, rs.successor)
        # a successor change alone changes each peer's split-horizon view
        if (dist_changed or parent_changed) and send_updates:
            for peer in self.peers:
                self._send(peer, self._update_msg(rs.root, peer))
