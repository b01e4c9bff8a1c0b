"""One link changes per event, and a later event of the same stratum
changes it back.

Parameters (traffic/<name>.json, overlaid by traffic/<name>.<config>.json):

  strata   a list of strata, or a list of levels, each a list of strata.
           One cycle of the plan changes one link in every stratum, level
           by level and within a level in the list's order, and then
           changes them back, the levels in reverse: the last level
           changed is the first restored. Strata that lie along one path
           from the vantage go into levels, the farthest first: a link is
           then changed while the path up to it is whole and restored
           when it is whole again, so every event moves the routes between
           its link and the next changed one. The events of one run of
           changes (or of restores) are all of different strata, so as
           many of them as share a solve epoch change as many links.
           A stratum selects links in one of two ways:
             {"path": {"template": "node-158-{i}", "from": 158, "to": 315}}
               the links between consecutive nodes of the path;
             {"between": ["^pod(?!000)...-rsw", "^pod...-fsw"]}
               every link with one end matching each expression.
  op       "metric": the link's metric goes to a value drawn from
           `metric_range`, and back to the generator's;
           "updown": the link goes down, and comes back.

Within a stratum the seed draws which link; the order of the strata, the
classes and the count of events are the same whatever the seed. A stratum
has at most one changed link at a time.
"""

from __future__ import annotations

import random
import re


def _levels(params: dict) -> list[list[dict]]:
    strata = params["strata"]
    return strata if isinstance(strata[0], list) else [strata]


def rotation_events(params: dict) -> int:
    """Events in one cycle: every stratum changed and restored."""
    return 2 * sum(len(level) for level in _levels(params))


def _stratum_links(lsdb, spec: dict) -> list[tuple[str, str]]:
    links = lsdb.links()
    if "path" in spec:
        p = spec["path"]
        step = 1 if p["to"] >= p["from"] else -1
        nodes = [
            p["template"].format(i=i)
            for i in range(p["from"], p["to"] + step, step)
        ]
        picked = list(zip(nodes, nodes[1:]))
        absent = [l for l in picked if tuple(sorted(l)) not in links]
        if absent:
            raise ValueError(f"path has no link {absent[0]}")
        return picked
    first, second = (re.compile(e) for e in spec["between"])
    picked = [
        (a, b) if first.search(a) and second.search(b) else (b, a)
        for a, b in sorted(links)
        if (first.search(a) and second.search(b))
        or (first.search(b) and second.search(a))
    ]
    if not picked:
        raise ValueError(f"no link between {spec['between']}")
    return picked


def plan(lsdb, params: dict, seed: int):
    """Yields events without end: {"ops": [...], "class": str, "stratum":
    str}."""
    levels = _levels(params)
    specs = [spec for level in levels for spec in level]
    if len(specs) < max(params.get("warmup_bursts", []), default=2):
        raise ValueError("link_flap: fewer strata than the longest burst")
    op = params["op"]
    if op not in ("metric", "updown"):
        raise ValueError(f"link_flap: unknown op {op!r}")
    links = [_stratum_links(lsdb, spec) for spec in specs]
    names = [spec.get("name", str(k)) for k, spec in enumerate(specs)]
    rngs = [random.Random(f"{seed}/{k}") for k in range(len(specs))]
    # the strata's numbers, level by level: forwards to change, and with
    # the levels reversed to restore
    numbered, k = [], 0
    for level in levels:
        numbered.append(list(range(k, k + len(level))))
        k += len(level)
    forwards = [k for level in numbered for k in level]
    back = [k for level in reversed(numbered) for k in level]
    held: list = [None] * len(specs)
    while True:
        for k in forwards:
            a, b = held[k] = rngs[k].choice(links[k])
            if op == "metric":
                lo, hi = params["metric_range"]
                ops = [("metric", a, b, rngs[k].randint(lo, hi))]
            else:
                ops = [("down", a, b)]
            yield {"ops": ops, "class": "change", "stratum": names[k]}
        for k in back:
            a, b = held[k]
            if op == "metric":
                ops = [("metric", a, b, params["base_metric"])]
            else:
                ops = [("up", a, b)]
            yield {"ops": ops, "class": "restore", "stratum": names[k]}
