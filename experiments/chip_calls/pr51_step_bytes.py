#!/usr/bin/env python3
"""`perf/run.py` with `ops/ssm.py _STEP_BYTES` set by hand (PR 51: what a
2 MiB cell of `ssm_step` does to a cell's set-up and tokens, without a
second form in the tree).

    python3 experiments/chip_calls/pr51_step_bytes.py <KiB> --workload <cell> \\
        --seed <n> --seconds 45 --trace 0

Run from the root of a checkout that has the byte rule; everything after
the KiB is `perf/run.py`'s. The budget is read while `ssm_step_kernel` is
traced, so setting the module's constant before the harness starts is the
whole of it.
"""

import os
import sys

sys.path.insert(0, os.getcwd())

from perf import run as harness  # noqa: E402

from ddp_practice_tpu.ops import ssm  # noqa: E402

if __name__ == "__main__":
    ssm._STEP_BYTES = int(sys.argv.pop(1)) * 1024
    print(f"step_bytes: {ssm._STEP_BYTES}", file=sys.stderr)
    sys.exit(harness.main())
