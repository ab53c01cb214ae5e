"""Share of chip 0's busy time in the traced slice spent in the flash
attention kernels (leaf ops named `flash_*`, the kernels' own `name=`).
"""

from perf.lib import spans

UNIT = "%"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "train_mfu_pct"


def read(obs: dict):
    return spans.kernel_dev_pct(obs, "flash_")
