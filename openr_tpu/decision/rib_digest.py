"""Per-epoch RIB digests: the replay/divergence fingerprint of one
route delta.

`delta_digest` hashes the SEMANTIC content of a DecisionRouteUpdate —
sorted (prefix, igp cost, sorted {neighbor/iface} next-hop identity)
rows plus sorted deletes — never backend representation (column
packing, device dtypes, nexthop object identity). That is what makes
the digest the cross-backend parity oracle the replay harness needs:
the solver tests already assert that cpu/tpu and column/host deltas
materialize to EQUAL entry dicts, so any two
correct builds of the same epoch hash identically, while a wrong row
on either side flips the digest.

Columnar deltas digest straight off the packed arrays (per-GROUP
next-hop decode, changed rows only — the "changed-row journal" path),
so steady-state churn epochs cost a few small-array ops plus one
blake2b update per changed row; object deltas hash their entries.
Both paths apply the same precedence as ColumnDelta.materialize
(segments in order, host extra_updates override), so the fast path and
the entry path agree byte-for-byte on the hashed payload.

`roll` chains per-epoch digests into the rolling fleet signal exported
through the counter fabric (decision.rib_digest.*): once one epoch
diverges, every later rolling value differs too, so a beacon compare
between replicas catches a divergence long after the offending epoch
scrolled out of any window. LFA backup sets and MPLS rows are outside
the digest (they ride the same delta; a divergence there without a
primary-row divergence has never been observed and would widen the
hashed payload for every epoch).
"""

from __future__ import annotations

import hashlib

import numpy as np

from openr_tpu.decision.column_delta import unpack_words

# 64-bit digests: small enough to stamp on every trace span and fold
# (truncated to 48 bits) into the float-valued counter fabric, large
# enough that a collision over a session's epochs is never the story
_DIGEST_SIZE = 8

# seed for epoch 0 / session start of the rolling chain
GENESIS = "0" * (2 * _DIGEST_SIZE)


def _entry_line(prefix: str, entry) -> bytes:
    nhs = sorted(
        f"{nh.neighbor_node_name}/{nh.if_name}" for nh in entry.nexthops
    )
    return f"{prefix}|{entry.igp_cost}|{','.join(nhs)}".encode()


def _segment_lines(view, rows: np.ndarray, out: dict) -> None:
    """Digest lines for `rows` of one RibView, written into `out`
    keyed by prefix (same last-writer-wins precedence as
    ColumnDelta.materialize_updates).

    Next-hop group decode is memoized per crib, keyed on the packed
    nhw row bytes: a churn storm re-sees the same handful of nexthop
    sets every epoch, so steady state never touches unpack_words or
    the link objects — just a bytes-dict lookup per changed row. The
    cache lives on the crib (links are fixed per crib instance) and
    dies with it on any topology rebuild."""
    crib = view.crib
    cols = view.cols
    cache = getattr(crib, "_digest_nh_keys", None)
    if cache is None:
        cache = {}
        crib._digest_nh_keys = cache
    elif len(cache) > 4096:  # pathological pattern churn backstop
        cache.clear()
    nhw = np.ascontiguousarray(cols.nhw[rows])
    row_bytes = nhw.tobytes()
    w = nhw.shape[1] * nhw.dtype.itemsize
    d_n = max(len(crib.links), 1)
    me = crib.my_node_name
    plist = crib.matrix.prefix_list
    mets = cols.met[rows].tolist()
    for j, r in enumerate(rows.tolist()):
        key = row_bytes[j * w:(j + 1) * w]
        gk = cache.get(key)
        if gk is None:
            bits = unpack_words(nhw[j:j + 1], d_n)[0]
            nhs = sorted(
                f"{crib.links[d].other_node(me)}/{crib.links[d].iface_from_node(me)}"
                for d in np.flatnonzero(bits).tolist()
            )
            gk = cache[key] = ",".join(nhs).encode()
        p = plist[r]
        out[p] = p.encode() + b"|" + b"%d" % int(mets[j]) + b"|" + gk


def delta_digest(update) -> str:
    """Hex digest of one DecisionRouteUpdate's semantic content."""
    lines: dict[str, bytes] = {}
    cols = getattr(update, "columns", None)
    if cols is not None:
        for view, rows in cols.segments:
            if len(rows):
                _segment_lines(view, rows, lines)
        for p, e in cols.extra_updates.items():
            lines[p] = _entry_line(p, e)
        deletes = cols.deletes
    else:
        for p, e in update.unicast_routes_to_update.items():
            lines[p] = _entry_line(p, e)
        deletes = update.unicast_routes_to_delete
    h = hashlib.blake2b(digest_size=_DIGEST_SIZE)
    for p in sorted(lines):
        h.update(lines[p])
        h.update(b"\n")
    h.update(b"|deletes|")
    for p in sorted(deletes):
        h.update(p.encode())
        h.update(b"\n")
    return h.hexdigest()


def roll(prev_hex: str, digest_hex: str) -> str:
    """Chain one epoch digest onto the rolling session digest."""
    h = hashlib.blake2b(digest_size=_DIGEST_SIZE)
    h.update(bytes.fromhex(prev_hex or GENESIS))
    h.update(bytes.fromhex(digest_hex))
    return h.hexdigest()


def as_counter_value(digest_hex: str) -> int:
    """Low 48 bits of the digest as an int — exactly representable in
    the counter fabric's float64 values."""
    return int(digest_hex, 16) & ((1 << 48) - 1)
