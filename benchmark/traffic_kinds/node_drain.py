"""One switch is drained by its overload bit per timed event, and given
back before the next one goes: device maintenance, one switch after
another through a fabric (`breeze lm set-node-overload`, the work, `unset`).
Every link stays up. The operations are lsdbs/node_drain.py's:
("drain", node) and ("undrain", node), each the switch's one `adj:` key.

Parameters (traffic/<name>.json, overlaid by traffic/<name>.<config>.json):

  strata         a list of strata, each {"name", "between": [a, b]}: the
                 form link_flap's strata have, so that a deployment's
                 mixes can share theirs word for word. Here a stratum is
                 the switches whose name matches the SECOND expression of
                 `between` (link_flap's links run from the rack switches
                 of a range of pods to its fabric switches: the fabric
                 switches are what is drained).
  restore_after  the fraction of a period, over 0 and under 1, after which
                 the switch that a timed event drained is given back.

A rotation visits every stratum once, in the list's order: drain switch F
of the stratum (timed), give F back `restore_after` of a period later
(untimed; acked and compared like any event, its class's median printed in
every run). The seed draws F. Within a stratum no switch is drawn a second
time until every one has been drawn once, so that no `adj:` key comes back
inside the flap damper's memory while the stratum has others. Draining and
giving back cost the program differently in principle (edges that grow
seed a cone, edges that shrink do not), so the window times one of the two,
as group_drain does. The order of the strata, the classes and the count of
events are the same whatever the seed; one switch is out at a time, but in
a burst of the warm-up, which drains as many at once as it has events, all
of different strata.
"""

from __future__ import annotations

import random
import re


def rotation_events(params: dict) -> int:
    """Timed events in one rotation: one drain a stratum."""
    return len(params["strata"])


def switches(lsdb, spec: dict) -> list[str]:
    """The stratum's switches, sorted."""
    second = re.compile(spec["between"][1])
    picked = sorted(n for n in lsdb.index if second.search(n))
    if not picked:
        raise ValueError(f"no switch matches {spec['between'][1]}")
    return picked


def plan(lsdb, params: dict, seed: int):
    """Yields events without end: {"ops": [...], "class": str, "stratum":
    str}, the give-backs with "timed": False and "after"."""
    specs = params["strata"]
    if len(specs) < max(params.get("warmup_bursts", []), default=1):
        raise ValueError("node_drain: fewer strata than the longest burst")
    after = params["restore_after"]
    if not 0.0 < after < 1.0:
        raise ValueError("node_drain: restore_after is over 0 and under 1")
    pools = [switches(lsdb, spec) for spec in specs]
    names = [spec.get("name", str(k)) for k, spec in enumerate(specs)]
    rngs = [random.Random(f"{seed}/{k}") for k in range(len(specs))]
    left: list[list] = [[] for _ in specs]  # not drawn yet, this time round
    while True:
        for k, name in enumerate(names):
            if not left[k]:
                left[k] = list(pools[k])
                rngs[k].shuffle(left[k])
            node = left[k].pop()
            yield {"ops": [("drain", node)], "class": "drain",
                   "stratum": name}
            yield {"ops": [("undrain", node)], "class": "undrain",
                   "stratum": name, "timed": False, "after": after}
