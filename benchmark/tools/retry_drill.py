#!/usr/bin/env python3
"""On the chip: does a cell survive the loss of its first cluster?

    python3 benchmark/tools/retry_drill.py --workload <train cell> --seed <n> --seconds <s> --trace 0

Runs ``run.py``'s ``main`` with the cell's kind made to fail ONCE the way a
lost worker does: the first attempt asks for an optimizer the loop does
not know, so the worker raises after it has opened the chip. The second
attempt is the cell as committed. The last line must be a result with
``"retried": 1``; if the first worker still held the chip, it is not.
"""

import copy
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run                      # noqa: E402
from benchmark.kinds import train              # noqa: E402

real, calls = train.run, []


def fails_once(cell, args, ctx):
    calls.append(1)
    if len(calls) == 1:
        cell = copy.deepcopy(cell)
        cell["train"]["optimizer"] = "the drill's first attempt"
    return real(cell, args, ctx)


train.run = fails_once
sys.exit(run.main())
