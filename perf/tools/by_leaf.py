#!/usr/bin/env python3
"""`sweep.py` and `calibrate.py` for a cell whose driver is `serve_by_leaf`.

    python3 perf/tools/by_leaf.py sweep --workload nemo3s_serve_flood \
        --rates 4,6,8,10 --seconds 20
    python3 perf/tools/by_leaf.py calibrate --workload nemo3s_serve_flood \
        --seeds 6 --control-seeds 3

The two tools build their engine with `drivers/serve.py build_engine` and
tell a serving cell by `driver == "serve"`. This runs either with the
weights drawn a leaf at a time and the traffic's driver read as the serving
one; nothing else of them changes.
"""

from __future__ import annotations

import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import run as harness  # noqa: E402
from perf.drivers import serve_by_leaf  # noqa: E402


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] not in ("sweep", "calibrate"):
        raise SystemExit("usage: by_leaf.py sweep|calibrate <the tool's "
                         "own arguments>")
    tool = importlib.import_module(f"perf.tools.{argv[0]}")
    with serve_by_leaf.by_leaf(harness=harness):
        return tool.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
