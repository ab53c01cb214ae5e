"""perf/tools/sweep.py on ling3_serve_reason as the cell runs it: weights by
leaf, the ONE dealt order of the traffic file's shape_seed
(perf/lib/dealt.py), and a drain long enough for a 3,072-token answer
(`tools/by_leaf.py sweep` knows neither the cell's driver nor its order, and
the tool's own drain is 60 s). It stops after the first rate that fails
the rule (90% of requests inside the file's slo, the backlog at the window's
end no larger than at its middle) once a lower rate has passed: the knee is
bracketed then. Scratch: never part of the benchmark.

    python3 experiments/chip_calls/pr47_sweep.py --rates 2.5,3,3.5,4,4.5,5 \
        --seconds 60 [--drain 120]
"""
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import run as harness  # noqa: E402
from perf.drivers import serve_by_leaf  # noqa: E402
from perf.lib import dealt  # noqa: E402
from perf.tools import sweep  # noqa: E402

argv = sys.argv[1:]
drain = 120.0
if "--drain" in argv:
    at = argv.index("--drain")
    drain = float(argv[at + 1])
    del argv[at:at + 2]
open_cell = harness.open_cell


def as_serving(name):
    opened = open_cell(name)
    if isinstance(opened, int):
        return opened
    opened = list(opened)
    opened[3] = dict(opened[3], driver="serve", drain_limit_s=drain)
    return tuple(opened)


class Bracketed(Exception):
    pass


passed = []


def dumps(row):
    """`json.dumps` for the tool's rows: the row that fails after one that
    passed is printed here and ends the sweep."""
    line = json.dumps(row)
    if row["met_share"] >= 0.9 and row["queue_end"] <= row["queue_mid"]:
        passed.append(row["rate_rps"])
    elif passed:
        print(line, flush=True)
        raise Bracketed
    return line


harness.open_cell = as_serving
sweep.json = types.SimpleNamespace(dumps=dumps, load=json.load)
with serve_by_leaf.by_leaf(), dealt.one_order():
    try:
        sys.exit(sweep.main(["--workload", "ling3_serve_reason"] + argv))
    except Bracketed:
        sys.exit(0)
