"""How late the generator handed requests over (sent - due), 99th percentile: a
starved generator must not read as a fast server.
"""

from perf.lib import readers

UNIT = "ms"
LAYER = "load generator"
SOURCE = "host_clock"
MOVES = "ttft_p95_ms"


def read(obs: dict):
    return readers.request_percentile(obs, "late_ms", 99.0)
