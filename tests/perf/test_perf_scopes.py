"""Device time by module (`perf/lib/scopes.py`): a path to a class and a
direction, over names recorded on the chip; the sums that have to close; the
xplane read by the wire; and what a trace without paths gives."""

import importlib
import json
import os

import pytest

from perf.lib import scopes, xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ["train_fwd_dev_pct", "train_bwd_dev_pct", "train_opt_dev_pct",
       "train_attn_dev_pct", "train_mlp_dev_pct", "train_norm_dev_pct",
       "train_head_loss_dev_pct", "train_unscoped_dev_pct",
       "flood_attn_dev_pct", "flood_mlp_dev_pct", "flood_mixer_dev_pct",
       "flood_head_sample_dev_pct", "flood_unscoped_dev_pct"]


@pytest.fixture(scope="module")
def recorded():
    """Two heads of traces taken on the chip (my chip runs, PR 34), names
    UNCUT, each "XLA Ops" event with the path the xplane's event metadata
    gave it as a fourth item: one train step of `gpt2s_train_2k_dp4`
    (chip 0) and the start of a decode burst of `jamba2_serve_batch`, both
    thinned (the file's `recorded` says how)."""
    with open(os.path.join(HERE, "data", "xplane_scopes_head.json")) as f:
        return json.load(f)


def obs_of(trace: dict) -> dict:
    return {"kind": "train", "trace": trace, "traced": (100.0, 110.0)}


def event(trace: dict, name: str) -> list:
    """The first op event whose instruction is called `name`."""
    for e in xtrace.line_events(xtrace.device_planes(trace)[0],
                                xtrace.OPS_LINE):
        if e[0].startswith(f"%{name} = "):
            return e
    raise KeyError(name)


# (which head, the instruction's name, its class and direction)
RECORDED = [
    # -- one train step: forward, loss, backward, optimizer
    ("train", "fusion", None),                   # the resident batch gather
    ("train", "convert_element_type.2849", ("attn", "fwd")),
    ("train", "fusion.1", ("embed", "fwd")),
    ("train", "convert_reduce_fusion.24", ("norm", "fwd")),    # ln1, block 0
    ("train", "convolution_add_fusion.23", ("attn", "fwd")),   # qkv
    ("train", "rope_flat_qk.12", ("attn", "fwd")),
    ("train", "flash_fwd_packed.12", ("attn", "fwd")),
    # the out projection with the next LayerNorm's statistics as epilogue
    ("train", "convert_reduce_fusion.23", ("attn", "fwd")),
    ("train", "copy-done.146", None),            # no path: the compiler's
    ("train", "convolution_add_fusion.11", ("mlp", "fwd")),    # fc_in
    ("train", "convert_reduce_fusion.22", ("mlp", "fwd")),     # fc_out
    ("train", "fusion.530", ("head", "fwd")),    # the tied head
    ("train", "fusion.2", ("loss", "fwd")),      # jvp(loss)
    ("train", "fusion.971", ("head", "bwd")),
    ("train", "multiply_reduce_fusion.1", ("head", "bwd")),
    ("train", "fusion.1430", ("mlp", "bwd")),
    ("train", "multiply_reduce_fusion.18", ("mlp", "bwd")),
    ("train", "convolution_bitcast_fusion.8", ("attn", "bwd")),
    ("train", "flash_bwd_packed.12", ("attn", "bwd")),
    ("train", "rope_flat_bwd.12", ("attn", "bwd")),
    ("train", "reduce.546", ("attn", "bwd")),
    ("train", "fusion.467", ("norm", "bwd")),
    ("train", "fusion.3", ("embed", "bwd")),
    ("train", "multiply_add_fusion.42", ("optimizer", "opt")),
    # -- the start of one decode burst
    ("serve", "copy.1234", None),
    # the first step's check of the carried logits, and every step's argmax
    ("serve", "is-finite_reduce_fusion.1", ("sample", "fwd")),
    ("serve", "iota_reduce_fusion.2", ("sample", "fwd")),
    ("serve", "slice-done.745", None),
    ("serve", "fusion.3078", ("embed", "fwd")),
    ("serve", "convolution_bitcast_fusion.52", ("mixer", "fwd")),  # in_proj
    ("serve", "convert_bitcast_fusion.52", None),   # a relayout, pathless
    ("serve", "sel_step.104", ("mixer", "fwd")),
    ("serve", "fusion.3084", ("mixer", "fwd")),     # out_proj
    ("serve", "fusion.3085", ("mlp", "fwd")),
    ("serve", "fusion.3138", ("attn", "fwd")),
    ("serve", "fusion.3137", ("attn", "fwd")),      # the cache write
    ("serve", "paged_decode.12", ("attn", "fwd")),
    # the head's matmul with the NEXT step's finite check as its epilogue:
    # one op_name a fusion, the matmul's
    ("serve", "is-finite_reduce_fusion.3", ("head", "fwd")),
]


def test_recorded_picks_are_at_least_two_dozen():
    assert len(RECORDED) >= 24
    assert {w for w, _, _ in RECORDED} == {"train", "serve"}


@pytest.mark.parametrize("which,name,want", RECORDED,
                         ids=[f"{w}-{n}" for w, n, _ in RECORDED])
def test_recorded_name_reads_its_class_and_direction(recorded, which, name,
                                                     want):
    e = event(recorded[which], name)
    assert len(e) == 4 and " = " in e[0] and len(e[0]) > 40   # uncut
    path = scopes.scope_of(e)
    assert scopes.classify(path) == want, path


PATHS = [
    ("jit(step)/jvp(LM)/block0/mlp/fc_in/dot_general", ("mlp", "fwd")),
    ("jit(step)/transpose(jvp(LM))/block0/ln1/reduce_sum", ("norm", "bwd")),
    ("jit(step)/jvp(loss)/reduce_max", ("loss", "fwd")),
    ("jit(step)/transpose(jvp(loss))/mul", ("loss", "bwd")),
    ("jit(step)/optimizer/add", ("optimizer", "opt")),
    # a transposed path under the optimizer's scope is still the update
    ("jit(step)/optimizer/transpose(x)/mul", ("optimizer", "opt")),
    ("jit(f)/jvp(LM)/block3/block3._unfused/attn/attn._project/qkv/"
     "dot_general", ("attn", "fwd")),
    # the LAST token wins: the innermost module owns the op
    ("jit(f)/MLALM/attn2/kv_norm/mul", ("norm", "fwd")),
    ("jit(f)/HybridLM/mamba4/norm/rsqrt", ("norm", "fwd")),
    ("jit(f)/HybridLM/mamba4/in_proj/dot_general", ("mixer", "fwd")),
    ("jit(f)/HybridLM/mamba4/ssm_step/pallas_call", ("mixer", "fwd")),
    ("jit(f)/HybridLM/mamba0/sel_scan/pallas_call", ("mixer", "fwd")),
    ("jit(f)/HybridLM/moe1/moe_route/sort", ("mlp", "fwd")),
    ("jit(f)/HybridLM/moe1/moe_gmm_glu/pallas_call", ("mlp", "fwd")),
    ("jit(f)/HybridLM/norm_attn7/mul", ("norm", "fwd")),
    ("jit(f)/HybridLM/norm_f/mul", ("norm", "fwd")),
    ("jit(f)/TransformerLM/ln_f/sub", ("norm", "fwd")),
    ("jit(f)/TransformerLM/lm_head/dot_general", ("head", "fwd")),
    ("jit(f)/TransformerLM/tok_embed.attend/dot_general", ("head", "fwd")),
    ("jit(f)/TransformerLM/tok_embed/jit(_take)/gather", ("embed", "fwd")),
    ("jit(f)/ViT/patch_embed/conv_general_dilated", ("embed", "fwd")),
    ("jit(f)/transpose(jvp(ViT))/head/dot_general", ("head", "bwd")),
    ("jit(_decode_burst)/while/body/closed_call/sample/reduce",
     ("sample", "fwd")),
    # several paths joined: the first is read
    ("jit(f)/optimizer/mul;jit(f)/jvp(LM)/block0/mlp/add",
     ("optimizer", "opt")),
    # no token of the vocabulary
    ("jit(resident_chunk)/while/body/closed_call/gather", None),
    ("jit(f)/jvp(TransformerLM)/block0/block0._unfused/add", None),
    ("state.params['block0']['attn']['out']['bias']", None),
    ("", None),
    (None, None),
]


@pytest.mark.parametrize("path,want", PATHS)
def test_classify(path, want):
    assert scopes.classify(path) == want


def test_scope_of_reads_a_name_that_ends_in_its_metadata():
    """Form (a): an event whose name is the instruction's whole text."""
    name = ('%fusion.7 = bf16[8,2048,768]{2,1,0} fusion(%p.1), kind=kOutput, '
            'calls=%fused_computation.7, metadata={op_name="jit(step)/jvp(LM)'
            '/block1/mlp/fc_out/dot_general" source_file="lm.py" '
            'source_line=3}')
    assert scopes.scope_of([name, 0.0, 1.0]) \
        == "jit(step)/jvp(LM)/block1/mlp/fc_out/dot_general"
    assert scopes.scope_of(["%copy.1 = s32[8] copy(%a)", 0.0, 1.0]) is None
    assert scopes.scope_of(["%copy.1 = s32[8] copy(%a)", 0.0, 1.0, ""]) \
        is None
    assert scopes.scope_of(["%c = copy()", 0.0, 1.0, "jit(f)/x"]) \
        == "jit(f)/x"


@pytest.mark.parametrize("which", ["train", "serve"])
def test_classes_and_directions_each_close_on_the_busy_time(recorded, which):
    obs = obs_of(recorded[which])
    by = scopes.seconds_by(obs)
    busy = by["busy"]
    t0, t1 = xtrace.window_of(recorded[which])
    assert busy == pytest.approx(      # `train_flash_dev_pct`'s divisor
        xtrace.busy(pathless(recorded[which]), t0, t1)["per_chip_s"][0])
    pairs = {k: v for k, v in by.items() if isinstance(k, tuple)}
    assert sum(pairs.values()) + by["unscoped"] == pytest.approx(busy)
    classes = sum(scopes.class_pct(obs, c) for c in scopes.CLASSES)
    directions = sum(scopes.direction_pct(obs, d) for d in scopes.DIRECTIONS)
    assert classes + scopes.unscoped_pct(obs) == pytest.approx(100.0)
    assert directions + scopes.unscoped_pct(obs) == pytest.approx(100.0)
    # what reads unscoped is named, and is no more than the remainder
    assert sum(by["unscoped_ops"].values()) <= by["unscoped"] + 1e-12
    assert 0.0 <= scopes.unscoped_pct(obs) < 50.0
    # parsed once: the second reader gets the first one's table
    assert scopes.seconds_by(obs) is by


def test_the_train_head_holds_every_direction_and_the_burst_one(recorded):
    by = scopes.seconds_by(obs_of(recorded["train"]))
    assert {k[1] for k in by if isinstance(k, tuple)} == {"fwd", "bwd", "opt"}
    by = scopes.seconds_by(obs_of(recorded["serve"]))
    assert {k[1] for k in by if isinstance(k, tuple)} == {"fwd"}
    assert ("mixer", "fwd") in by and ("sample", "fwd") in by


def test_a_kernels_share_is_inside_its_class(recorded):
    """`train_attn_dev_pct` holds `train_flash_dev_pct`'s kernels: the same
    leaf rule, the same busy time."""
    trace = recorded["train"]
    obs = obs_of(trace)
    t0, t1 = xtrace.window_of(trace)
    flash = sum(v for k, v in xtrace.op_seconds(trace, t0, t1).items()
                if k.startswith("flash_"))
    assert flash > 0
    busy = scopes.seconds_by(obs)["busy"]
    assert scopes.class_pct(obs, "attn") >= 100.0 * flash / busy


def pathless(trace: dict) -> dict:
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
            for ln in p["lines"]]} for p in trace["planes"]]}


@pytest.mark.parametrize("metric", NEW)
def test_every_reader_returns_none_without_paths(recorded, metric, tmp_path,
                                                 monkeypatch):
    """The parent commit's program under this benchmark, or a trace whose
    xplane is gone: nothing to read, nothing raised, the metric left out."""
    reader = importlib.import_module(f"perf.layer_metrics.{metric}")
    monkeypatch.setattr(scopes, "newest_xplane", lambda root=None: None)
    which = "train" if metric.startswith("train") else "serve"
    assert reader.read(obs_of(pathless(recorded[which]))) is None
    assert reader.read({"trace": None, "traced": None}) is None
    host_only = {"planes": [p for p in recorded[which]["planes"]
                            if p["name"].startswith("/host:")]}
    assert reader.read(obs_of(host_only)) is None
    # and with paths it reads a share of the busy time
    value = reader.read(obs_of(recorded[which]))
    assert value is not None and 0.0 <= value <= 100.0


# ------------------------------------------------------ the xplane, by wire
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 9 offset_ps: 0 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 1 uint64_value: 7 } }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8] fusion()"
    display_name: "fusion.1"
    stats { metadata_id: 4 double_value: 2.5 }
    stats { metadata_id: 2
            str_value: "jit(step)/jvp(LM)/block0/mlp/fc_in/dot_general:" } } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = s32[8] copy()" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[] fusion()"
    stats { metadata_id: 2 ref_value: 3 } } }
  event_metadata { key: 9 value { id: 9 name: "jit_step(1)" } }
  stat_metadata { key: 1 value { id: 1 name: "device_offset_ps" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "jit(step)/optimizer/add:" } }
  stat_metadata { key: 4 value { id: 4 name: "flops" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000000 } }
  event_metadata { key: 1 value { id: 1 name: "perf:traced" } } }
"""


@pytest.fixture()
def xplane(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(path)


def test_the_wire_reader_joins_an_xplane_to_its_loaded_trace(xplane):
    """`tf_op` is a stat of the event METADATA (a string, or a reference into
    the stat names), which `ProfileData` does not yield: the file is read as
    protobuf wire and paired with `xtrace.load`'s events one to one."""
    assert scopes.xplane_paths(xplane, "/device:TPU:0", "XLA Ops") == [
        ("%fusion.1 = bf16[8] fusion()",
         "jit(step)/jvp(LM)/block0/mlp/fc_in/dot_general"),
        ("%copy.2 = s32[8] copy()", ""),
        ("%fusion.3 = f32[] fusion()", "jit(step)/optimizer/add")]
    assert scopes.xplane_paths(xplane, "/device:TPU:1", "XLA Ops") is None
    assert scopes.xplane_paths(xplane, "/device:TPU:0", "Steps") is None
    trace = xtrace.load(xplane)
    obs = {"trace": trace, "traced": (0.0, 0.0), "xplane": xplane}
    by = scopes.seconds_by(obs)
    assert by["busy"] == pytest.approx(
        xtrace.busy(trace, *xtrace.window_of(trace))["per_chip_s"][0])
    assert by[("mlp", "fwd")] == pytest.approx(2e-6)
    assert by[("optimizer", "opt")] == pytest.approx(1e-6)
    assert by["unscoped"] == pytest.approx(1e-6)
    assert by["unscoped_ops"] == {"copy": pytest.approx(1e-6)}
    assert scopes.class_pct(obs, "mlp") == pytest.approx(50.0)
    assert scopes.direction_pct(obs, "opt") == pytest.approx(25.0)
    assert scopes.unscoped_pct(obs) == pytest.approx(25.0)


def test_a_join_that_does_not_pair_is_refused(xplane):
    trace = xtrace.load(xplane)
    ops = xtrace.line_events(xtrace.device_planes(trace)[0], xtrace.OPS_LINE)
    assert len(scopes.paths_from_xplane(xplane, trace)) == 3
    dropped = ops.pop()
    assert scopes.paths_from_xplane(xplane, trace) is None   # by count
    ops.append(["%other.9 = f32[] fusion()", dropped[1], dropped[2]])
    assert scopes.paths_from_xplane(xplane, trace) is None   # by name
    assert scopes.seconds_by(
        {"trace": trace, "traced": (0.0, 0.0), "xplane": xplane}) is None


def test_the_newest_xplane_of_a_traced_run_is_found(tmp_path):
    assert scopes.newest_xplane(str(tmp_path)) is None
    for i, cell in enumerate(("a_cell", "b_cell")):
        d = tmp_path / cell / f"seed{i}_trace1" / "xplane" / "plugins" \
            / "profile" / "2026"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        os.utime(d / "host.xplane.pb", (1000 + i, 1000 + i))
    (tmp_path / "a_cell" / "seed0_trace0").mkdir()
    assert scopes.newest_xplane(str(tmp_path)).startswith(
        str(tmp_path / "b_cell"))


# -------------------------------------------------------- the same, off HLO
HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p.1, %p.1), metadata={op_name="jit(step)/optimizer/mul"}
}

%fused_computation.2 (p.2: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  %neg.2 = f32[8]{0} negate(%p.2), metadata={op_name="jit(step)/jvp(LM)/block0/mlp/neg"}
  ROOT %bitcast.2 = f32[8]{0} bitcast(%neg.2)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(LM)/ln_f/mul"}
  %wrapped = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %flash_fwd.3 = (f32[8]{0}, f32[8]{0}) custom-call(%wrapped), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(LM)/block0/attn/flash_fwd/pallas_call"}
  ROOT %copy.4 = f32[8]{0} copy(%wrapped)
}
"""


def test_hlo_ops_lists_a_programs_own_ops_with_their_paths():
    """What the program's tests read the contract from: the instructions
    outside fusion bodies; a fusion without a path of its own reads its
    body's."""
    assert scopes.hlo_ops(HLO) == [
        ("parameter", "a", "a"),
        ("fusion", "fusion.1", "jit(step)/jvp(LM)/ln_f/mul"),
        ("fusion", "wrapped", "jit(step)/jvp(LM)/block0/mlp/neg"),
        ("custom-call", "flash_fwd.3",
         "jit(step)/jvp(LM)/block0/attn/flash_fwd/pallas_call"),
        ("copy", "copy.4", None)]
