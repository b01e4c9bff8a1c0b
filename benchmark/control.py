#!/usr/bin/env python3
"""A control: one run of a cell with one guarantee of its configuration
broken underneath the harness. `correct` has to come out false.

    python3 benchmark/control.py --control <name> --workload ... --seed ... \\
        --seconds ... --trace 0

  host_solver    the configuration's "no host-computed route" is broken:
                 Decision is built with solver_backend="cpu", the step a
                 later PR could be tempted to take for a small deployment.
                 The tables stay right; the no-hiding conditions must fail.
  altered_route  the configuration's "the programmed table equals the
                 reference" is broken where the answer is produced: in
                 every batch Fib hands its service, the first route's
                 metric is one too high. One route in the table is wrong
                 at any time; the whole-table comparison must see it.

Everything else is run.py: the same set-up, window and comparison. The
benchmark's own runs never come here.
"""

from __future__ import annotations

import argparse
import os
import sys
from dataclasses import replace

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_HERE, os.path.dirname(_HERE)]

import harness  # noqa: E402
import lsdb  # noqa: E402
import run  # noqa: E402


def host_solver() -> None:
    load_config = lsdb.load_config

    def with_host_solver(name, root=lsdb.ROOT):
        return {**load_config(name, root), "solver_backend": "cpu"}

    lsdb.load_config = with_host_solver


def altered_route() -> None:
    start = harness.ServedStack.start

    async def start_with_altered_routes(self):
        add = self.fib_service.add_unicast_routes

        async def add_altered(client_id, routes):
            routes = list(routes)
            if routes:
                routes[0] = replace(
                    routes[0], igp_cost=routes[0].igp_cost + 1
                )
            await add(client_id, routes)

        self.fib_service.add_unicast_routes = add_altered
        await start(self)

    harness.ServedStack.start = start_with_altered_routes


CONTROLS = {"host_solver": host_solver, "altered_route": altered_route}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--control", required=True, choices=sorted(CONTROLS))
    args, rest = p.parse_known_args(argv)
    CONTROLS[args.control]()
    run.emit(control=args.control)
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main())
