"""Device time by the program's own modules: each device op's scope path
(the `op_name` JAX writes into the HLO's metadata) to a class and a direction.

The program's side of the contract (tests/test_scopes.py holds it): every
device op that does model or optimizer work carries, in its `op_name`, a path
with one token of the vocabulary below. Flax runs each module method under
`jax.named_scope(<module name>)`, the hand-made scopes of `ops/` and of
`train/steps.py` / `serve/engine.py` (`loss`, `optimizer`, `sample`) are plain
`jax.named_scope`s. The benchmark's side is here: `scope_of` finds the path of
one trace event, `classify` reads it, `seconds_by` sums chip 0's busy time in
the traced slice by (class, direction).

A path reads `jit(step)/transpose(jvp(LM))/block3/block3._unfused/mlp/fc_in/
dot_general`. It is split at "/", each segment is unwrapped (`jvp(loss)` and
`transpose(jvp(loss))` read `loss`; `attn._project` reads `attn`), and the
LAST segment that is a token of the vocabulary decides the class: the
innermost module that owns the op. A fusion that spans two modules has ONE
`op_name`, that of the op XLA kept as its root, and all of its time goes
there; nothing is split.

    class      tokens (a whole segment, `\\d*` = a layer index)
    attn       attn\\d*
    mlp        mlp\\d*  moe\\d*  moe_route  moe_combine  moe_gmm\\w*
    mixer      mamba\\d*  ssm_\\w+  sel_\\w+
    norm       ln\\d*  ln_f  norm\\w*  kv_norm
    head       lm_head  head  classifier  \\w*embed.attend (a tied head)
    embed      \\w*embed
    loss       loss
    optimizer  optimizer
    sample     sample

Direction: `opt` for the class `optimizer`; else `bwd` where the path holds
`transpose(` (a rematerialised forward inside it too: it is what the backward
pass costs); else `fwd`.
"""

from __future__ import annotations

import functools
import glob
import os
import re

from perf.lib import readers, xtrace
from perf.lib.stats import union_seconds

VOCABULARY = (
    ("attn", r"attn\d*"),
    ("mlp", r"mlp\d*|moe\d*|moe_route|moe_combine|moe_gmm\w*"),
    ("mixer", r"mamba\d*|ssm_\w+|sel_\w+"),
    ("norm", r"ln\d*|ln_f|norm\w*|kv_norm"),
    ("head", r"lm_head|head|classifier|\w*embed\.attend"),
    ("embed", r"\w*embed"),
    ("loss", r"loss"),
    ("optimizer", r"optimizer"),
    ("sample", r"sample"),
)
CLASSES = tuple(c for c, _ in VOCABULARY)
DIRECTIONS = ("fwd", "bwd", "opt")
UNSCOPED = "unscoped"
_TOKEN = re.compile("|".join(f"(?P<{c}>{rx})" for c, rx in VOCABULARY))
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CACHE = "_seconds_by_scope"


def scope_of(event):
    """The `op_name` path of one "XLA Ops" event, or None. An event is
    [name, start_s, dur_s] and may carry a fourth item, the path a joined
    xplane gave it (`paths_from_xplane`); else the path is looked for in
    the name, where it is the instruction's full text (`... metadata={
    op_name="..."}`)."""
    if len(event) > 3:
        return event[3] or None
    m = _OP_NAME.search(event[0])
    return m.group(1) if m and m.group(1) else None


def _segment(seg: str) -> str:
    """`transpose(jvp(loss))` -> `loss`; `attn._project` -> `attn`;
    `tok_embed.attend` stays (a token of its own)."""
    while True:
        m = _WRAPPED.match(seg)
        if not m:
            break
        seg = m.group(1)
    if "." in seg and not seg.endswith("embed.attend"):
        seg = seg.split(".")[0]
    return seg


@functools.lru_cache(maxsize=None)
def classify(path):
    """(class, direction) of a path, or None where no segment of it is a
    token of the vocabulary. Of several paths joined by ";" the first is
    read. (Cached: a slice's million events share a few thousand paths.)"""
    if not path:
        return None
    path = path.split(";")[0]
    cls = None
    for seg in path.split("/"):
        m = _TOKEN.fullmatch(_segment(seg))
        if m:
            cls = m.lastgroup
    if cls is None:
        return None
    if cls == "optimizer":
        return cls, "opt"
    return cls, ("bwd" if "transpose(" in path else "fwd")


def leaf_seconds(events: list, t0: float, t1: float):
    """(event, seconds inside [t0, t1]) of the leaf ops of one chip's
    "XLA Ops" line: the rule of `xtrace.op_seconds`."""
    for e in xtrace.leaves(events):
        a, b = max(e[1], t0), min(e[1] + e[2], t1)
        if b > a:
            yield e, b - a


def newest_xplane(root: str | None = None):
    """The newest `*.xplane.pb` a `--trace 1` run of this checkout wrote
    (`perf_out/<cell>/seed<n>_trace1/xplane/`: the process that asks wrote
    exactly one), or None."""
    root = root or os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "perf_out")
    hits = glob.glob(os.path.join(
        root, "*", "seed*_trace1", "xplane", "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(hits, key=os.path.getmtime) if hits else None


# ------------------------------------------------- the xplane, by the wire
# On this installation an op's path is the stat `tf_op` ("<op_name>:<type>")
# of its XEventMetadata. `jax.profiler.ProfileData` yields an event's OWN
# stats alone (device_offset_ps, device_duration_ps), so the file is read
# as protobuf wire, these fields of it and no other:
#   XSpace.planes = 1
#   XPlane.name = 2, .lines = 3, .event_metadata = 4 (map: key 1, value 2),
#          .stat_metadata = 5 (map)
#   XEventMetadata.id = 1, .name = 2, .stats = 5;  XStatMetadata.id 1, .name 2
#   XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (a stat-metadata id)
#   XLine.name = 2, .events = 4;  XEvent.metadata_id = 1
# It goes when `xtrace.load` keeps the path as an event's fourth item
# (PERF.md section 7): `scope_of` reads that form already.
PATH_STAT = "tf_op"


def _varint(buf, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if not b & 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint or a fixed
    field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v = int.from_bytes(buf[i:i + size], "little")
            i += size
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, v


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_values(entries: list):
    """The `value` message (field 2) of each entry of a protobuf map."""
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def xplane_paths(path: str, plane_name: str, line_name: str):
    """[(event name, op path or "")] of the events of one line of one
    plane of an `.xplane.pb`, in the file's order, or None without such a
    plane or line."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for f, plane in _fields(space):
        if f != 1:
            continue
        parts = {2: [], 3: [], 4: [], 5: []}
        for g, v in _fields(plane):
            if g in parts:
                parts[g].append(v)
        if not parts[2] or _text(parts[2][0]) != plane_name:
            continue
        stat_names = {}
        for meta in _map_values(parts[5]):
            row = dict(_fields(meta))
            stat_names[row.get(1, 0)] = _text(row.get(2, b""))
        metas = {}
        for meta in _map_values(parts[4]):
            mid, name, found = 0, "", ""
            for g, v in _fields(meta):
                if g == 1:
                    mid = v
                elif g == 2:
                    name = _text(v)
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == PATH_STAT:
                        found = _text(stat[5]) if 5 in stat \
                            else stat_names.get(stat.get(7), "")
            metas[mid] = (name, found.rsplit(":", 1)[0])
        for line in parts[3]:
            name, events = "", []
            for g, v in _fields(line):
                if g == 2:
                    name = _text(v)
                elif g == 4:
                    events.append(v)
            if name == line_name:
                return [metas.get(_metadata_id(e), ("", "")) for e in events]
    return None


def _metadata_id(event) -> int:
    """XEvent.metadata_id; it is the first field as protobuf writes one."""
    if event[0] == 0x08:
        return _varint(event, 1)[0]
    return next((v for g, v in _fields(event) if g == 1), 0)


def paths_from_xplane(path: str, trace: dict):
    """Chip 0's "XLA Ops" events of the loaded `trace` with a fourth item
    each, the op's path from the xplane file the trace was loaded from, or
    None where the file's events do not pair with the loaded ones one to
    one: by count, and then by name, event for event."""
    plane = xtrace.device_planes(trace)[0]
    mine = xtrace.line_events(plane, xtrace.OPS_LINE)
    theirs = xplane_paths(path, plane["name"], xtrace.OPS_LINE)
    if theirs is None or len(theirs) != len(mine):
        return None
    out = []
    for e, (name, found) in zip(mine, theirs):
        if name != e[0]:
            return None
        out.append([e[0], e[1], e[2], found])
    return out


def events_with_paths(obs: dict, trace: dict):
    """Chip 0's ops with their paths: the loaded events themselves where
    any of them holds one, else joined to the xplane file this run wrote.
    None where there is no device plane or neither gives a path."""
    planes = xtrace.device_planes(trace)
    if not planes:
        return None
    events = xtrace.line_events(planes[0], xtrace.OPS_LINE)
    if any(len(e) > 3 or 'op_name="' in e[0] for e in events):
        return events
    path = obs.get("xplane") or newest_xplane()
    if path is None:
        return None
    joined = paths_from_xplane(path, trace)
    if joined is None or not any(scope_of(e) for e in joined):
        return None
    return joined


def seconds_by(obs: dict):
    """{(class, direction): seconds, "unscoped": seconds, "busy": seconds}
    of chip 0 inside the traced slice, or None without a trace or where no
    op of it carries a path. The classes' seconds are leaf ops' (as
    `train_flash_dev_pct` counts a kernel's); `unscoped` is the rest of the
    chip's busy time: leaf ops without a path or with none of the
    vocabulary's tokens in it, and what a parent op runs between its
    children. Parsed once a run: the result is kept on `obs`."""
    if _CACHE in obs:
        return obs[_CACHE]
    obs[_CACHE] = out = _seconds_by(obs)
    return out


def _seconds_by(obs: dict):
    sl = readers._slice(obs)
    if sl is None:
        return None
    trace, t0, t1, _ = sl
    events = events_with_paths(obs, trace)
    if events is None:
        return None
    out, dark = {}, {}
    for e, secs in leaf_seconds(events, t0, t1):
        key = classify(scope_of(e))
        if key is None:
            name = xtrace.op_name(e[0])
            dark[name] = dark.get(name, 0.0) + secs
        else:
            out[key] = out.get(key, 0.0) + secs
    # chip 0's busy time as `xtrace.busy` has it: the union of its ops
    busy = union_seconds(xtrace.clip([e[:3] for e in events], t0, t1))
    out[UNSCOPED] = busy - sum(out.values())
    out["busy"] = busy
    out["unscoped_ops"] = dark
    return out


def _pct(obs: dict, pick):
    by = seconds_by(obs)
    if by is None or by["busy"] <= 0:
        return None
    return 100.0 * sum(v for k, v in by.items()
                       if isinstance(k, tuple) and pick(*k)) / by["busy"]


def class_pct(obs: dict, *classes: str):
    """Share of chip 0's busy time in the traced slice under the scopes of
    `classes`, forward and backward together, in percent."""
    return _pct(obs, lambda c, d: c in classes)


def direction_pct(obs: dict, direction: str):
    return _pct(obs, lambda c, d: d == direction)


def unscoped_pct(obs: dict):
    by = seconds_by(obs)
    if by is None or by["busy"] <= 0:
        return None
    return 100.0 * by[UNSCOPED] / by["busy"]


# --------------------------------------------------- the same, off HLO text
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = (?:\([^=]*\)|\S+) ([\w\-]+)\(")
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")


def hlo_ops(text: str) -> list:
    """[(opcode, instruction name, op_name path or None)] of the
    instructions of an optimized HLO module (`compiled.as_text()`) that
    run as device ops of their own: those of every computation that is not
    the body of a fusion. A fusion that carries no `op_name` itself (XLA:CPU
    wraps single ops so) reads that of the last instruction of its body
    that has one (its root's, where the root has). What the tests of the
    contract read, where no chip gives a trace."""
    fused = set(_FUSED.findall(text))
    body_path, rows, inside = {}, [], None
    for ln in text.splitlines():
        m = _COMPUTATION.match(ln)
        if m:
            inside = m.group(1)
            continue
        m = _INSTRUCTION.match(ln) if inside else None
        if not m:
            continue
        p = _OP_NAME.search(ln)
        path = p.group(1) if p and p.group(1) else None
        if inside in fused:
            if path:
                body_path[inside] = path
        else:
            calls = _FUSED.search(ln) if m.group(2) == "fusion" else None
            rows.append((m.group(2), m.group(1), path,
                         calls.group(1) if calls else None))
    return [(op, name, path or body_path.get(calls))
            for op, name, path, calls in rows]
