#!/usr/bin/env python3
"""`perf/run.py` with the program's TraceRecorder ATTACHED and no profiler
(PR 49: what tracing costs when it is on and nobody profiles).

    python3 experiments/chip_calls/pr49_recorder.py --workload <cell> \
        --seed <n> --seconds 45 --trace 0

A `--trace 0` run builds no recorder (`perf/drivers/serve.py`). This hands
the engine and the scheduler one anyway, as a `--trace 1` run would, and
leaves the profiler shut: every span is recorded and mirrored into an
annotation that is the profiler's no-op, and (the point) no attribute is
handed to it. Run from the root of a checkout, the parent's too; after the
result line it prints how many spans the recorder took.
"""

import os
import sys

sys.path.insert(0, os.getcwd())

from perf import run as harness  # noqa: E402
from perf.drivers import serve  # noqa: E402

from ddp_practice_tpu.serve import scheduler  # noqa: E402
from ddp_practice_tpu.utils.trace import TraceRecorder  # noqa: E402

REC = TraceRecorder(max_events=1 << 20)
_build, _init = serve.build_engine, scheduler.Scheduler.__init__


def build_engine(ctx, tracer=None):
    return _build(ctx, tracer if tracer is not None else REC)


def init(self, *args, **kw):
    if kw.get("tracer") is None:
        kw["tracer"] = REC
    _init(self, *args, **kw)


serve.build_engine = build_engine
scheduler.Scheduler.__init__ = init

if __name__ == "__main__":
    rc = harness.main()
    print(f"recorder: {len(REC)} records, {REC.dropped} dropped",
          file=sys.stderr)
    sys.exit(rc)
