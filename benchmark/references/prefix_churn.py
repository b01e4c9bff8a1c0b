"""The plain reference of a deployment whose prefixes come and go
(lsdbs/prefix_churn.py): reference.py — Dijkstra, ECMP first hops, RFC
5286 — on the prefix databases as they stood: the generator's, less what
was withdrawn, and what was advertised since. A withdrawn prefix has no
route; an advertised one has its advertiser's. reference.py refuses a
prefix that two switches advertise at once (`Unsupported`), and so does
this: the deployment has one advertiser a prefix."""

from __future__ import annotations

import reference

compare = reference.compare


def prefix_dbs(lsdb) -> list:
    """The prefix databases as they stood in the model `lsdb`."""
    gone = lsdb.withdrawn
    return [
        db for db in lsdb.prefix_dbs
        if not any(
            (db.this_node_name, e.prefix) in gone for e in db.prefix_entries
        )
    ] + list(lsdb.advertised.values())


def routes(lsdb, me: str, config: dict) -> dict:
    lfa = bool(config.get("decision_config", {}).get("enable_lfa"))
    return reference.routes(lsdb.adj_dbs, prefix_dbs(lsdb), me, lfa)


def programmed(snapshot: dict) -> dict:
    return reference.programmed(snapshot["unicast"])
