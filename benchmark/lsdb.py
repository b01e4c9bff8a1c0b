"""The deployment as the benchmark holds it: the generator's adjacency
and prefix databases, the link operations a traffic plan applies to
them, and the KvStore keys and values that carry each change.

The benchmark keeps this copy so that the plain reference
(reference.py) can be run on the final LSDB without asking the program
what it holds. `lsdb_key_vals` and the metric change follow
chip_smoke.py's `lsdb_key_vals`, `adj_kv` and `set_metric`.
"""

from __future__ import annotations

from dataclasses import replace

from files import ROOT, find, load_json

AREA = "0"


def load_config(name: str, root: str = ROOT) -> dict:
    return load_json(find(root, "configs", f"{name}.json"))


def build(config: dict) -> "Lsdb":
    """Call the generator the configuration file names."""
    from openr_tpu.models import topologies

    gen = config["generator"]
    adj_dbs, prefix_dbs = getattr(topologies, gen["call"])(
        *gen.get("args", []), **gen.get("kwargs", {})
    )
    lsdb = Lsdb(adj_dbs, prefix_dbs)
    if config["vantage"] not in lsdb.index:
        raise ValueError(f"vantage {config['vantage']} is not in the LSDB")
    return lsdb


class Lsdb:
    """Adjacency databases by node, with the links a plan has taken down
    and the metrics it has changed kept apart from the generator's own
    adjacencies, so that `up` and a restored metric give back exactly
    the database the generator made."""

    def __init__(self, adj_dbs: list, prefix_dbs: list):
        self.adj_dbs = list(adj_dbs)
        self.prefix_dbs = list(prefix_dbs)
        self.index = {db.this_node_name: i for i, db in enumerate(adj_dbs)}
        self._base = {db.this_node_name: db for db in adj_dbs}
        self._down: dict[str, set] = {}
        self._metric: dict[str, dict] = {}
        self._version = dict.fromkeys(self.index, 1)
        self._serialized: dict = {}
        self.log: list[list] = []  # every batch of operations applied

    def links(self) -> set[tuple[str, str]]:
        """Every link of the generator's topology as a sorted name pair."""
        return {
            tuple(sorted((db.this_node_name, adj.other_node_name)))
            for db in self._base.values() for adj in db.adjacencies
        }

    def neighbors(self, node: str) -> list[str]:
        return [a.other_node_name for a in self._base[node].adjacencies]

    def apply(self, ops: list) -> list[str]:
        """Apply link operations, each to both directions of its link:
        ("metric", a, b, m), ("down", a, b) or ("up", a, b). Returns the
        nodes whose adjacency database changed, in a fixed order."""
        self.log.append(ops)
        touched = set()
        for op, a, b, *arg in ops:
            for me, other in ((a, b), (b, a)):
                if op == "metric":
                    self._metric.setdefault(me, {})[other] = arg[0]
                elif op == "down":
                    self._down.setdefault(me, set()).add(other)
                elif op == "up":
                    self._down[me].remove(other)
                else:
                    raise ValueError(f"unknown link operation {op!r}")
                touched.add(me)
        for node in touched:
            base = self._base[node]
            down = self._down.get(node, ())
            metric = self._metric.get(node, {})
            self.adj_dbs[self.index[node]] = replace(
                base, adjacencies=tuple(
                    replace(adj, metric=metric[adj.other_node_name])
                    if adj.other_node_name in metric else adj
                    for adj in base.adjacencies
                    if adj.other_node_name not in down
                )
            )
        return sorted(touched)

    def replay(self, batches: int) -> "Lsdb":
        """The LSDB as it stood after the first `batches` batches."""
        then = Lsdb(list(self._base.values()), self.prefix_dbs)
        for ops in self.log[:batches]:
            then.apply(ops)
        return then

    def publication(self, nodes: list[str]) -> dict:
        """The next version of each node's adj: key, as one write."""
        from openr_tpu.serde import serialize
        from openr_tpu.types import Value, adj_key

        out = {}
        for node in nodes:
            db = self.adj_dbs[self.index[node]]
            value = self._serialized.get(db)
            if value is None:
                value = self._serialized[db] = serialize(db)
            self._version[node] += 1
            out[adj_key(node)] = Value(
                version=self._version[node], originator_id=node, value=value
            )
        return out

    def key_vals(self) -> dict:
        """The whole LSDB at version 1, as a peer's full sync carries it."""
        from openr_tpu.serde import serialize
        from openr_tpu.types import Value, adj_key, prefix_key

        kvs = {
            adj_key(db.this_node_name): Value(
                version=1, originator_id=db.this_node_name,
                value=serialize(db),
            )
            for db in self.adj_dbs
        }
        for db in self.prefix_dbs:
            value = serialize(db)
            for entry in db.prefix_entries:
                kvs[prefix_key(db.this_node_name, db.area, entry.prefix)] = (
                    Value(
                        version=1, originator_id=db.this_node_name,
                        value=value,
                    )
                )
        return kvs
