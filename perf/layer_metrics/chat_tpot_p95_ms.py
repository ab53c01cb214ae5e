"""Time per output token: per request (last token - first token) / (tokens -
1), the scheduler's own `Completion.tpot`, 95th percentile over requests. A
mean over a request's gaps, so burst delivery does not read as one long gap and
seven of zero. Kept per-layer: on the chip its runs spread 7% of the median at
the cell's rate (PERF.md), too wide for a bound of its own.
"""

from perf.lib import readers

UNIT = "ms"
LAYER = "serve host loop"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(obs: dict):
    return readers.request_percentile(obs, "tpot_ms", 95.0)
