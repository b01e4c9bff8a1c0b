"""One prefix of one switch is advertised or withdrawn per event, and the
links stay up.

Parameters (traffic/<name>.json, overlaid by traffic/<name>.<config>.json):

  strata   a list of strata, each {"name", "between": [switches, ...]}:
           the form link_flap's strata have, so that a deployment's two
           mixes can share theirs word for word. Here a stratum is the
           switches whose name matches the FIRST expression of `between`
           (the rack switches of a range of pods) and that advertise a
           prefix.
  fresh    the range fresh prefixes are drawn from, "fd00:c::/48": /128s
           the store has never held.

A rotation visits every stratum four times, all the first visits, then
all the second, third and fourth:

  1  withdraw prefix p of switch A     the seed draws A in the stratum and
                                       p among the prefixes A advertises
  2  advertise p back at A             the advertisement as it was
  3  advertise a fresh /128 q at A     drawn from the seed, never repeated
  4  withdraw q

so half the advertisements are of a prefix the program has never held and
half the withdrawals are of one, every event adds or deletes exactly one
prefix (one per-prefix key, one write), and at most one prefix a stratum
is out, or one fresh one in, at a time. Every event is timed; the classes
are `withdraw` and `advertise`, in equal number. The order of the strata,
the classes and the count of events are the same whatever the seed.
"""

from __future__ import annotations

import ipaddress
import random
import re
from dataclasses import replace

VISITS = (
    ("withdraw", "held"), ("advertise", "held"),
    ("advertise", "fresh"), ("withdraw", "fresh"),
)


def rotation_events(params: dict) -> int:
    return len(VISITS) * len(params["strata"])


def _switches(spec: dict, entries: dict) -> list[str]:
    first = re.compile(spec["between"][0])
    picked = sorted(n for n in entries if first.search(n))
    if not picked:
        raise ValueError(f"no advertising switch matches {spec['between'][0]}")
    return picked


def plan(lsdb, params: dict, seed: int):
    """Yields events without end: {"ops": [...], "class": str, "stratum":
    str}."""
    specs = params["strata"]
    if len(specs) < max(params.get("warmup_bursts", []), default=1):
        raise ValueError("prefix_churn: fewer strata than the longest burst")
    entries: dict[str, list] = {}
    for db in lsdb.prefix_dbs:
        entries.setdefault(db.this_node_name, []).extend(db.prefix_entries)
    switches = [_switches(spec, entries) for spec in specs]
    names = [spec.get("name", str(k)) for k, spec in enumerate(specs)]
    rngs = [random.Random(f"{seed}/{k}") for k in range(len(specs))]
    fresh = ipaddress.ip_network(params["fresh"])
    if fresh.version != 6 or fresh.prefixlen > 64:
        raise ValueError("prefix_churn: `fresh` is an IPv6 range of /64 or wider")
    base, seen = int(fresh.network_address), set()
    held: list = [None] * len(specs)  # (switch, the entry in hand)
    while True:
        for visit, (what, _) in enumerate(VISITS):
            for k in range(len(specs)):
                if visit == 0:
                    node = rngs[k].choice(switches[k])
                    held[k] = (node, rngs[k].choice(entries[node]))
                elif visit == 2:
                    node, entry = held[k]
                    while True:  # the stratum in the high word, then the draw
                        host = (k << 56) | rngs[k].getrandbits(48) or 1
                        if host not in seen:
                            break
                    seen.add(host)
                    prefix = str(ipaddress.ip_network((base + host, 128)))
                    held[k] = (node, replace(entry, prefix=prefix))
                node, entry = held[k]
                op = (
                    ("withdraw", node, entry.prefix) if what == "withdraw"
                    else ("advertise", node, entry)
                )
                yield {"ops": [op], "class": what, "stratum": names[k]}
