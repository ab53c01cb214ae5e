"""Due to admitted, 95th percentile over requests (the scheduler's flight
record: stall + queue).
"""

from perf.lib import readers

UNIT = "ms"
LAYER = "serve host loop"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(obs: dict):
    return readers.request_percentile(obs, "wait_ms", 95.0)
