"""A WRONG reference, for a control: references/region.py with the
drained-advertiser filter switched off (SpfSolver.cpp:709-731,
maybeFilterDrainedNodes: the last step of `select`). A drained border
router stays among the advertisers forwarding may use, so while one is
drained this reference keeps the exit through it and the program does not:
a cell compared by it must not come out correct
(benchmark/tests/test_wan50k_region.py). It breaks the selection among
advertisers and nothing else: the graph still lacks a drained router's
out-edges, as references/node_drain.py has it."""

from __future__ import annotations

import files

# a module of its own (files.load_module makes one a call): what is
# replaced in it is replaced for this reference alone
region = files.reference_module({"reference_module": "region"})
_select = region.select
region.select = lambda entries, dist_me, drained: _select(
    entries, dist_me, set()
)

routes = region.routes
programmed = region.programmed
compare = region.compare
