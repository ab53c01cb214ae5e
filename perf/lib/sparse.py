"""What the readers of a block-sparse serving cell share: the device time of
the ops of one name inside the traced slice, and the slice's records of the
driver `serve_long_by_leaf` (`obs["chunks"]`, `obs["sparse_bursts"]`).

A Pallas kernel is found by its NAME (`sparse_walk`, `sparse_prefill`), as
`lib/hybrid.py` finds `ssm_step`; the selection (`sparse_select`) is plain
XLA, fusions under a scope of that name, and is found by the scope path of
each op (`lib/scopes.py`).
"""

from __future__ import annotations

from perf.lib import hybrid, readers, scopes, xtrace

# a chunk dispatched this long before the slice ends has run inside it
LAG_S = 0.5


def named_seconds(obs: dict, name: str):
    """Seconds of chip 0's leaf ops inside the traced slice whose own name
    or whose scope path holds `name`; None without a trace."""
    sl = readers._slice(obs)
    if sl is None:
        return None
    trace, t0, t1, _ = sl
    events = scopes.events_with_paths(obs, trace)
    if events is None:
        plane = xtrace.device_planes(trace)[0]
        events = xtrace.line_events(plane, xtrace.OPS_LINE)
    return sum(secs for e, secs in scopes.leaf_seconds(events, t0, t1)
               if name in xtrace.op_name(e[0])
               or name in (scopes.scope_of(e) or ""))


def kernel_seconds(obs: dict, name: str):
    """Seconds of the kernel `name` inside the traced slice, by its events
    (a custom call has no children: `lib/hybrid.py`)."""
    sl = readers._slice(obs)
    if sl is None:
        return None
    trace, t0, t1, _ = sl
    return sum(b - a for a, b in xtrace.clip(
        [[name, s, d] for s, d in hybrid.kernel_events(
            xtrace.device_planes(trace)[0], name)], t0, t1))


def chunks_in_slice(obs: dict) -> list:
    """[first position, real tokens] of the prefill chunks dispatched inside
    the slice and at least `LAG_S` before its end."""
    sl = readers._slice(obs)
    if sl is None:
        return []
    _, t0, t1, off = sl
    return [[first, n] for a, b, first, n in obs.get("chunks", [])
            if t0 <= a + off and b + off <= t1 - LAG_S]


def bursts_in_slice(obs: dict) -> list:
    """[pages walked, pages held, sparse slots] of the slice's bursts."""
    sl = readers._slice(obs)
    if sl is None:
        return []
    _, t0, t1, off = sl
    return [row[1:] for row in obs.get("sparse_bursts", [])
            if t0 <= row[0] + off <= t1]
