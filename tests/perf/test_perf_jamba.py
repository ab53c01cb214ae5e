"""The Jamba configuration's benchmark files: the configuration against its
source (every key of the catalog row, nothing reduced), the family's bytes
and operations, the readers on hand-made observations, the reference against
the program and against its own control, and one toy run of the cell through
the harness with the driver that notes admissions."""

import copy
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jamba_toy
import perf_toy
from perf import run as harness
from perf.drivers import serve
from perf.families import jamba as family
from perf.lib import weights, weights_by_leaf
from perf.reference import jamba as reference

CFG = perf_toy.load("perf/configs/jamba2_3b.json")
TRAFFIC = perf_toy.load("perf/traffic/batch_flood_s256.json")
# the catalog row's `config` (model-configs guide, architectures.jsonl)
SOURCE = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}


def read(metric, obs):
    return importlib.import_module(f"perf.layer_metrics.{metric}").read(obs)


# ----------------------------------------------------------- configuration
def test_every_key_of_the_source_is_kept_and_nothing_is_reduced():
    assert {k: CFG[k] for k in SOURCE} == SOURCE
    assert CFG["reduced"] == {} and CFG["departures"] == {}
    entry = next(c for c in perf_toy.manifest()["configs"]
                 if c["name"] == "jamba2_3b")
    assert entry["reduced"] == [] and entry["source"] == CFG["source"] \
        == "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/" \
           "config.json"
    assert {"layer_order", "position_embedding", "ssm_state_dtype",
            "weights"} <= set(CFG["assumed"])
    assert "one v5e chip" in CFG["deployment"]


def test_family_reads_the_layers_bytes_and_operations_from_the_keys():
    assert family.mixers(CFG) == "SSSSSSS*SSSSSS" * 2
    assert family.counts(CFG) == {"S": 26, "*": 2, "D": 28}
    opts = family.model_options(CFG)
    assert opts["pattern"] == "".join(m + "D" for m in family.mixers(CFG))
    assert len(opts["pattern"]) == 56 and opts["tie_embeddings"]
    assert (opts["mamba_inner"], opts["ssm_state"], opts["dt_rank"],
            opts["head_dim"], opts["kv_heads"]) == (5120, 16, 160, 128, 1)
    # a cached token: 2 layers x (K + V) x 128 x 2 B; q + out a slot and step
    assert family.decode_bytes(CFG) == (1024, 2 * 20 * 128 * 2 * 2)
    assert family.ssm_state_bytes(CFG) == 327_680
    assert family.conv_state_bytes(CFG) == 30_720
    # u, dt in and y out in float32, B and C beside them: ~60 KB a token
    assert family.scan_bytes_per_token(CFG) == 4 * (3 * 5120 + 32)
    # 3.03 B parameters less the embedding's lookup, twice: ~6.06 GFLOP
    assert 6.0e9 < family.decode_flops_per_token(CFG) < 6.2e9
    with pytest.raises(ValueError, match="dense feed-forwards"):
        family.model_options(dict(CFG, num_experts=16))


def test_the_traffic_file_states_what_the_issue_asked_for():
    t, e = TRAFFIC["tenants"][0], TRAFFIC["engine"]
    assert len(TRAFFIC["tenants"]) == 1 and t["arrivals"] == "poisson"
    assert (t["prompt_len_median"], t["prompt_len_sigma"],
            t["prompt_len_cap"]) == (256, 0.8, 1024)
    assert (t["max_new_median"], t["max_new_sigma"], t["max_new_cap"]) \
        == (768, 0.6, 2048)
    assert TRAFFIC["rate_rule"].startswith("five times the knee")
    assert (e["max_slots"], e["burst"], e["buckets"]) \
        == (256, 8, [128, 256, 512, 1024])
    # a slot's table is 3,072 positions, every slot's backed
    assert e["page"] * e["max_blocks_per_slot"] == 3072 == 1024 + 2048
    assert e["num_blocks"] == 1 + 256 * e["max_blocks_per_slot"]
    assert not {"prefix_cache", "prefill_chunk", "spec_decode"} & set(e)
    assert TRAFFIC["drain_limit_s"] == 0
    assert TRAFFIC["check"]["pad_to"] == 3072
    assert TRAFFIC["driver"] == "serve_by_leaf_admits"


# --------------------------------------------------------------- reference
def test_reference_agrees_with_the_program_and_fp8_does_not():
    cfg = jamba_toy.config()
    model, params = jamba_toy.model_and_params(cfg, seed=11)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
    want = np.asarray(jax.jit(
        lambda p, t: reference.forward(p, t, cfg))(params, tokens))
    scale = np.abs(want).max()
    # the sub-layers after it amplify the attention layer's order of sums
    assert np.abs(got - want).max() <= 1e-4 * scale
    low = np.asarray(jax.jit(
        lambda p, t: reference.forward(p, t, cfg, "fp8"))(params, tokens))
    assert np.abs(low - want).max() > 100 * 1e-4 * scale
    loss = float(jax.jit(lambda p, t: reference.loss(
        p, {"tokens": t}, cfg))(params, tokens))
    assert np.isfinite(loss) and loss > 0


# ----------------------------------------------------------------- readers
def jamba_obs(slots=256, ops=None, admits=None):
    """A 10 s slice: two decode bursts of 8 steps (1.0 s and 1.2 s of device
    time) with a prefill between them; kernels by name inside."""
    ops = ops if ops is not None else [
        ["%while.1 = while(...)", 1.0, 1.0],                   # a parent
        ["%sel_step.3 = custom-call(...)", 1.0, 0.3],
        ["%fusion.9 = fusion(...)", 1.3, 0.5],
        ["%paged_decode.1 = custom-call(...)", 1.8, 0.1],
        ["%sel_scan.7 = custom-call(...)", 3.0, 0.4],          # the prefill's
        ["%fusion.11 = fusion(...)", 3.4, 0.6],
        ["%sel_step.3 = custom-call(...)", 5.0, 0.5],
        ["%copy-done.4 = copy-done(...)", 5.1, 0.1],   # overlaps the kernel
        ["%fusion.9 = fusion(...)", 5.5, 0.7]]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ["jit__decode_burst(1)", 1.0, 1.0],
                ["jit__prefill_admit(2)", 3.0, 1.0],
                ["jit__decode_burst(1)", 5.0, 1.2]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["perf:traced", 0.0, 10.0]]}]}]}
    return {"kind": "serve", "trace": trace, "traced": (100.0, 110.0),
            "window": (95.0, 140.0), "spans": [], "burst": 8,
            "t_origin": 95.0, "chips": 1, "config": CFG,
            "peaks": {"hbm_bytes_s": 819e9}, "expert_bursts": [],
            # [start, end, real prompt tokens]: warm-up before the slice,
            # two prompts inside it, one too near its end, one after it
            "admits": admits if admits is not None else [
                [90.0, 90.1, 1024], [102.9, 103.0, 300], [104.0, 104.1, 77],
                [109.7, 109.8, 500], [115.0, 115.1, 200]],
            "ticks": [{"t": 5.5, "dt": 2.0, "slots": slots, "live": 0,
                       "queue": 9},
                      {"t": 9.5, "dt": 2.0, "slots": slots, "live": 0,
                       "queue": 9},
                      {"t": 30.0, "dt": 2.0, "slots": 1, "live": 0,
                       "queue": 0}]}


def test_readers_on_a_hand_made_trace():
    obs = jamba_obs()
    busy = 0.3 + 0.5 + 0.1 + 0.4 + 0.6 + 0.5 + 0.1 + 0.7
    assert read("flood_sel_dev_pct", obs) == pytest.approx(
        100.0 * 1.2 / busy)
    # 16 decode steps, 256 slots, 26 Mamba layers, the state read and written
    least = 16 * 256 * 26 * 2 * 327_680 / 819e9
    assert read("flood_sel_step_roofline", obs) == pytest.approx(
        100.0 * least / 0.8)
    # the two prompts admitted inside the slice and 0.5 s before its end, at
    # their REAL lengths (a bucket's padding is not charged), 26 layers
    least = 26 * ((300 + 77) * 4 * (3 * 5120 + 32) + 2 * 2 * 327_680) / 819e9
    assert read("flood_sel_scan_roofline", obs) == pytest.approx(
        100.0 * least / 0.4)
    # no `ssm_*` op: the Mamba-2 readers stay silent beside these
    assert read("flood_ssm_dev_pct", obs) is None


def test_step_roofline_charges_the_decoding_slots_alone():
    assert read("flood_sel_step_roofline", jamba_obs(slots=100)) \
        == pytest.approx(read("flood_sel_step_roofline", jamba_obs())
                         * 100 / 256)


@pytest.mark.parametrize("metric", ["flood_sel_dev_pct",
                                    "flood_sel_step_roofline",
                                    "flood_sel_scan_roofline"])
def test_a_program_without_the_kernels_gives_nothing_and_does_not_raise(
        metric):
    """The parent commit's trace (no such op), a run of another driver (no
    `admits`) and another family's configuration: None, never an
    exception."""
    plain = [["%fusion.9 = fusion(...)", 1.0, 0.5],
             ["%paged_decode.1 = custom-call(...)", 1.5, 0.5]]
    assert read(metric, jamba_obs(ops=plain)) is None
    assert read(metric, dict(jamba_obs(), trace=None)) is None
    for file in ("gpt2_small", "nemotron3_super_ep4"):
        other = dict(jamba_obs(ops=plain),
                     config=perf_toy.load(f"perf/configs/{file}.json"))
        other.pop("admits")
        assert read(metric, other) is None
    if metric == "flood_sel_scan_roofline":
        assert read(metric, jamba_obs(admits=[])) is None


# ------------------------------------------------------------------- a run
def toy_cell():
    cell = {"name": "jamba2_serve_batch", "config": "jamba2_3b",
            "traffic": "toy", "chips": 1}
    traffic = copy.deepcopy(TRAFFIC)
    traffic["tenants"][0].update(rate_rps=40.0, prompt_len_median=10,
                                 prompt_len_cap=24, max_new_median=6,
                                 max_new_cap=12)
    traffic["engine"].update(max_slots=3, page=8, buckets=[24], burst=4,
                             max_blocks_per_slot=5, num_blocks=16)
    traffic["check"].update(pad_to=40, requests=6)
    traffic["limits"] = perf_toy.SERVE_LIMITS
    return cell, jamba_toy.config(source=CFG["source"]), traffic


@pytest.mark.parametrize("broken", [False, True])
def test_toy_run_is_correct_unless_a_token_is_altered(broken, tmp_path,
                                                      monkeypatch):
    cell, config, traffic = toy_cell()
    if broken:
        from ddp_practice_tpu.serve import engine

        real = engine.PagedEngine.step_burst
        monkeypatch.setattr(engine.PagedEngine, "step_burst",
                            lambda self: (real(self) + 1) % 96)
    build = serve.build_engine
    line = harness.measure(
        perf_toy.manifest(), cell, config, traffic, seed=3_000_000_019,
        seconds=0.5, trace=False, devices=jax.devices()[:1],
        chip_peaks=perf_toy.PEAKS, outroot=str(tmp_path))
    assert line["correct"] is not broken and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"  # never a device number
    # the drivers put back what they swapped
    assert weights.make_params is not weights_by_leaf.make_params
    assert serve.build_engine is build


def test_the_driver_notes_every_admission_with_its_real_length(tmp_path):
    """`obs["admits"]`: one row an `engine.admit` call (the warm-up's
    too, before the window), each with the prompt's own length."""
    from perf.drivers import serve_by_leaf_admits

    cell, config, traffic = toy_cell()
    ctx = harness.make_ctx(cell, config, traffic, seed=7, seconds=0.5,
                           trace=False, devices=jax.devices()[:1],
                           chip_peaks=perf_toy.PEAKS, outroot=str(tmp_path))
    result = serve_by_leaf_admits.run(ctx)
    admits = result["obs"]["admits"]
    w0 = result["obs"]["window"][0]
    inside = [n for a, b, n in admits if a >= w0]
    assert len(inside) >= result["attempted"] - 3 > 0   # 3 slots may wait
    assert all(a <= b and 1 <= n <= 24 for a, b, n in admits)
    series = json.load(open(os.path.join(ctx.outdir, "series.json"))) \
        if os.path.exists(os.path.join(ctx.outdir, "series.json")) else None
    assert series is None   # the harness writes it, not the driver
    prompts = sorted(r["prompt"] for r in result["obs"]["requests"])
    assert all(p in inside for p in prompts)
