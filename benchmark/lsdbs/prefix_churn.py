"""An LSDB model whose prefixes come and go: the default model
(lsdbs/one_area.py: one area, link operations) plus

    ("advertise", node, entry)   `node` advertises the PrefixEntry
    ("withdraw", node, prefix)   `node` withdraws the prefix

each one per-prefix key, as upstream's per-prefix keys: a changed
advertisement is the key's next version with the one entry, a withdraw is
the key's next version with the entry's prefix and `delete_prefix` set
(Decision reads that as the advertisement gone; the key itself stays in
the store, as a withdrawn key does until its ttl).

The generator's prefix databases are never changed. What the plan did to
them is kept beside them, `withdrawn` and `advertised`, and `replay` gives
the model as it stood after any batch, so the reference
(references/prefix_churn.py) composes the prefix databases as they stood
itself: the generator's less `withdrawn`, and `advertised` (`retired` is
what was advertised and withdrawn again: nobody's route). One advertiser a prefix is the deployment's premise: a second one
is refused here, where it would be made, and again by the reference.
"""

from __future__ import annotations

import files
import lsdb

one_area = files.lsdb_module({})


class PrefixChurn(one_area.OneArea):
    def __init__(self, adj_dbs: list, prefix_dbs: list, base=None):
        super().__init__(adj_dbs, prefix_dbs)
        # what the generator made, untouched and shared by every replay
        self.prefix_dbs = prefix_dbs
        self._generated = base  # prefix -> (node, entry), made when asked
        self.withdrawn: set = set()  # (node, prefix) of the generator's
        self.advertised: dict = {}  # (node, prefix) -> PrefixDatabase
        self.retired: dict = {}  # what was advertised and withdrawn again
        self._prefix_version: dict = {}

    def generated(self) -> dict:
        if self._generated is None:
            self._generated = {
                e.prefix: (db.this_node_name, e)
                for db in self.prefix_dbs for e in db.prefix_entries
            }
        return self._generated

    def owner(self, prefix: str):
        """The node that advertises the prefix now, or None."""
        made = self.generated().get(prefix)
        if made is not None and (made[0], prefix) not in self.withdrawn:
            return made[0]
        return next((n for n, p in self.advertised if p == prefix), None)

    def apply(self, ops: list):
        """-> (nodes whose adjacencies changed, the prefix operations):
        what `publication` serializes."""
        from openr_tpu.types import PrefixDatabase

        mine = [op for op in ops if op[0] in ("advertise", "withdraw")]
        nodes = super().apply([op for op in ops if op not in mine])
        self.log[-1] = ops
        for op, node, what in mine:
            if op == "withdraw":
                if self.owner(what) != node:
                    raise ValueError(f"{node} does not advertise {what}")
                db = self.advertised.pop((node, what), None)
                if db is None:
                    self.withdrawn.add((node, what))
                else:
                    self.retired[(node, what)] = db
                continue
            if self.owner(what.prefix) is not None:
                raise ValueError(
                    f"{what.prefix} has an advertiser: "
                    f"{self.owner(what.prefix)}"
                )
            if self.generated().get(what.prefix) == (node, what):
                # the generator's own advertisement, given back
                self.withdrawn.discard((node, what.prefix))
            else:
                self.advertised[(node, what.prefix)] = PrefixDatabase(
                    node, (what,), lsdb.AREA
                )
        return nodes, mine

    def replay(self, batches: int) -> "PrefixChurn":
        then = type(self)(
            list(self._base.values()), self.prefix_dbs, self.generated()
        )
        for ops in self.log[:batches]:
            then.apply(ops)
        return then

    def publication(self, changed) -> dict:
        from openr_tpu.serde import serialize
        from openr_tpu.types import (
            PrefixDatabase, PrefixEntry, Value, prefix_key,
        )

        nodes, mine = changed
        out = super().publication(nodes)
        for op, node, what in mine:
            gone = op == "withdraw"
            entry = PrefixEntry(prefix=what) if gone else what
            key = prefix_key(node, lsdb.AREA, entry.prefix)
            version = self._prefix_version[key] = (
                self._prefix_version.get(key, 1) + 1
            )
            out[lsdb.AREA][key] = Value(
                version=version, originator_id=node, value=serialize(
                    PrefixDatabase(node, (entry,), lsdb.AREA, gone)
                ),
            )
        return out


def build(config: dict) -> PrefixChurn:
    return lsdb.build(config, PrefixChurn)
