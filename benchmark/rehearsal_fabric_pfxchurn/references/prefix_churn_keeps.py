"""A WRONG reference, for a control: references/prefix_churn.py that keeps
what was withdrawn. A cell compared by it must not come out correct
(benchmark/tests/test_fabric10k_pfxchurn.py): the comparison sees a
withdrawn prefix's route, or its absence."""

from __future__ import annotations

import reference

compare = reference.compare


def routes(lsdb, me: str, config: dict) -> dict:
    lfa = bool(config.get("decision_config", {}).get("enable_lfa"))
    kept = (
        list(lsdb.prefix_dbs) + list(lsdb.advertised.values())
        + list(lsdb.retired.values())
    )
    return reference.routes(lsdb.adj_dbs, kept, me, lfa)


def programmed(snapshot: dict) -> dict:
    return reference.programmed(snapshot["unicast"])
