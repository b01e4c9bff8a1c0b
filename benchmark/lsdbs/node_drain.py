"""An LSDB model whose switches are drained and given back by their
overload bit: the default model (lsdbs/one_area.py: one area, link
operations) plus

    ("drain", node)     `node` sets `AdjacencyDatabase.is_overloaded`
    ("undrain", node)   `node` clears it

as an operator does with `breeze lm set-node-overload` /
`unset-node-overload` (upstream LinkMonitor.h:158-193,
`semifuture_setNodeOverload`): the switch advertises its ONE `adj:` key
again, every adjacency as it stood (the generator's, less what a link
operation of the plan holds down, at the metrics it holds), with the bit
flipped. No link goes down. Every other node stops routing through the
switch and keeps routing to it; that rule is the reference's
(references/node_drain.py), not the model's: the model only says who is
drained.

`drained` is the set of switches out; `replay` gives the model as it stood
after any batch. Draining a drained switch, or giving back one that is not
out, is a fault of the plan and raises.
"""

from __future__ import annotations

from dataclasses import replace

import files
import lsdb

one_area = files.lsdb_module({})

DRAIN_OPS = ("drain", "undrain")


class NodeDrain(one_area.OneArea):
    def __init__(self, adj_dbs: list, prefix_dbs: list):
        super().__init__(adj_dbs, prefix_dbs)
        self.drained: set[str] = set()

    def apply(self, ops: list) -> list[str]:
        """-> the nodes whose adjacency database changed, in a fixed
        order: the ends of the links operated on and the switches drained
        or given back."""
        mine = [op for op in ops if op[0] in DRAIN_OPS]
        touched = set(super().apply([op for op in ops if op not in mine]))
        self.log[-1] = ops
        for op, node in mine:
            if node not in self.index:
                raise ValueError(f"{node} is not in the LSDB")
            if (op == "drain") == (node in self.drained):
                raise ValueError(f"{op}: {node} is "
                                 f"{'' if op == 'drain' else 'not '}drained")
            (self.drained.add if op == "drain" else self.drained.remove)(node)
            touched.add(node)
        # a link operation rebuilds a database from the generator's, whose
        # bit is clear: every database touched carries the bit as it stands
        for node in touched:
            i = self.index[node]
            if self.adj_dbs[i].is_overloaded != (node in self.drained):
                self.adj_dbs[i] = replace(
                    self.adj_dbs[i], is_overloaded=node in self.drained
                )
        return sorted(touched)


def build(config: dict) -> NodeDrain:
    return lsdb.build(config, NodeDrain)
