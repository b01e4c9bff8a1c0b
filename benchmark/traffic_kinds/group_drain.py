"""A whole group of nodes is taken out at once, and given back before the
next group goes: a spine plane drained for maintenance, or lost, and then
returned, plane after plane.

Parameters:

  group          a regular expression with one capture; the nodes it
                 matches are grouped by what it captures
                 ("^zspine(\\d+)-ssw\\d+$": one group to a spine plane).
                 At least as many groups as the longest of the mix's
                 `warmup_bursts`, which takes that many out together.
  restore_after  the fraction of a period, over 0 and under 1, after which
                 the group that a timed event took out is given back.

The plan is drain k, restore k, drain k+1, restore k+1, ...; the seed draws
the order of the rotation. The window times the drains. The restores go
out untimed between them, and are acked and compared like any event.
Taking a group out and giving it back cost the program differently (456
and 689 ms at fabric10k when both were timed, PERF.md section 6), so a
median over both falls between two modes; timed this way every timed
event is the same event, the whole network losing one group, and each
class's median is printed in every run.
"""

from __future__ import annotations

import random
import re


def rotation_events(params: dict) -> int:
    """Timed events in one rotation of the warm-up: two groups, each taken
    out and given back."""
    return 2


def plan(lsdb, params: dict, seed: int):
    """Yields events without end: {"ops": [...], "class": str, "stratum":
    str}, the restores with "timed": False and "after"."""
    expr = re.compile(params["group"])
    groups: dict[str, list] = {}
    for node in lsdb.index:
        m = expr.search(node)
        if m:
            groups.setdefault(m.group(1), []).append(node)
    if len(groups) < max(params.get("warmup_bursts", []), default=2):
        raise ValueError(f"{params['group']} makes {len(groups)} groups")
    order = sorted(groups)
    random.Random(seed).shuffle(order)
    # a link between two nodes of a group goes down once
    links = {
        key: sorted({
            tuple(sorted((node, other)))
            for node in groups[key] for other in lsdb.neighbors(node)
        })
        for key in order
    }
    while True:
        for key in order:
            yield {"ops": [("down", a, b) for a, b in links[key]],
                   "class": "drain", "stratum": key}
            yield {"ops": [("up", a, b) for a, b in links[key]],
                   "class": "restore", "stratum": key, "timed": False,
                   "after": params["restore_after"]}
