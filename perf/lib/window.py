"""What the readers of a window-attention serving cell share: the slice's
records of the driver `serve_window_by_leaf` (`obs["window_bursts"]`; the
chunks are `lib/sparse.py`'s, the same key of `obs`).

The kernels are found by NAME, `window_walk` and `window_prefill`
(`lib/sparse.py kernel_seconds`, `lib/hybrid.py decode_kernel`). Where the
program has no such op or the driver no such record (a program before the
window group), every reader here reads nothing and the line leaves its
metric out.
"""

from __future__ import annotations

from perf.lib import readers


def bursts_in_slice(obs: dict) -> list:
    """[pages the window walks read, pages whole walks would have] of the
    slice's decode bursts, as the decode program counted them."""
    sl = readers._slice(obs)
    if sl is None:
        return []
    _, t0, t1, off = sl
    return [row[1:] for row in obs.get("window_bursts", [])
            if t0 <= row[0] + off <= t1]
