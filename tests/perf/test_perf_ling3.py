"""The Ling-3.0-flash configuration's benchmark files: the configuration
against its source (every key of the catalog row; depth, experts held and
vocabulary reduced and nothing else), the family's bytes and operations, the
three `flood_kda_*` readers on hand-made observations, the driver's sample and
its shifted weights, the reference against its own two controls, and one toy
run of the cell through the harness."""

import copy
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ling3_toy
import perf_toy
from perf import run as harness
from perf.drivers import serve, serve_long_by_leaf, serve_window_by_leaf
from perf.drivers import serve_state_latent_by_leaf as driver
from perf.families import ling3 as family
from perf.lib import kda as kda_lib
from perf.lib import weights, weights_by_leaf
from perf.reference import ling3 as reference

CFG = perf_toy.load("perf/configs/ling3_flash_ep4.json")
TRAFFIC = perf_toy.load("perf/traffic/reason_docs_s128.json")
CELL = "ling3_serve_reason"
# the catalog row's `config` (model-configs guide, architectures.jsonl)
SOURCE = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
}
REDUCED = ["layers_run", "num_experts_held", "vocab_size"]


def read(metric, obs):
    return importlib.import_module(f"perf.layer_metrics.{metric}").read(obs)


# ----------------------------------------------------------- configuration
def test_every_width_of_the_source_is_kept_and_the_cut_is_stated():
    kept = {k: v for k, v in SOURCE.items() if k != "vocab_size"}
    assert {k: CFG[k] for k in kept} == kept
    assert sorted(CFG["reduced"]) == REDUCED
    assert (CFG["layers_run"], CFG["num_experts_held"], CFG["expert_offset"],
            CFG["vocab_size"], CFG["dt_bias_shift"]) \
        == (7, 128, 0, 39296, -8.0)
    assert CFG["published"]["vocab_size"] == SOURCE["vocab_size"] \
        == 4 * CFG["vocab_size"]
    # the guide's floors: a whole period and at least four layers after the
    # leading dense ones, eight experts, an eighth of the vocabulary
    assert CFG["layers_run"] >= CFG["layer_group_size"]
    assert CFG["layers_run"] - CFG["first_k_dense_replace"] >= 4
    assert CFG["num_experts_held"] >= 8
    # the swiglu limits clamp in no layer that is run
    assert not any(CFG[k][i] for i in range(CFG["layers_run"]) for k in (
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"))
    m = perf_toy.manifest()
    entry = next(c for c in m["configs"] if c["name"] == "ling3_flash_ep4")
    assert entry["reduced"] == REDUCED and entry["source"] == CFG["source"] \
        == "https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/" \
           "config.json"
    assert {"layer_order", "norms", "kimi_delta_attention", "safe_gate",
            "latent_attention", "experts", "tie_word_embeddings",
            "ssm_state_dtype", "cache_dtype", "weights"} \
        <= set(CFG["assumed"])
    assert {"vision_tower", "multi_token_prediction", "absent_experts",
            "swiglu_limits"} <= set(CFG["departures"])
    assert "24 v5e chips" in CFG["deployment"] \
        and "6 pipeline stages of 7 layers x 4" in CFG["deployment"] \
        and "4,454,368,704" in CFG["deployment"]


def test_family_reads_the_layers_bytes_and_operations_from_the_keys():
    assert family.mixers(CFG) == "KKKKKTK"
    assert family.counts(CFG) == {"K": 6, "T": 1, "D": 2, "U": 5}
    opts = family.model_options(CFG)
    assert (opts["layers"], opts["layer_group_size"], opts["first_dense"]) \
        == (7, 6, 2)
    assert (opts["n_group"], opts["topk_group"], opts["top_k"],
            opts["num_experts"], opts["experts_held"],
            opts["routed_scaling"]) == (8, 4, 8, 512, 128, 2.5)
    # a cached token: one layer x 576 useful values x 2 B
    assert family.decode_bytes(CFG) == (1152, 32 * (576 + 512) * 2)
    assert family.mla_decode_flops_per_token(CFG) == 2 * 32 * 1088
    assert family.ssm_state_bytes(CFG) == 2_097_152
    assert family.conv_state_bytes(CFG) == 73_728
    assert 6 * (family.ssm_state_bytes(CFG)
                + family.conv_state_bytes(CFG)) == 13_025_280
    assert family.expert_bytes(CFG) == 11_796_480
    assert family.param_count(CFG) == 4_454_368_704
    assert family.mixer_params(CFG) == {"K": 52_646_048, "T": 31_965_696}
    # mixers, two dense MLPs, the head and 2 of a token's 8 picks: 1.3 GFLOP
    assert 1.2e9 < family.decode_flops_per_token(CFG) < 1.4e9
    with pytest.raises(ValueError, match="safe gate"):
        family.model_options(dict(CFG, kda_lower_bound=-8))
    with pytest.raises(ValueError, match="safe gate"):
        family.model_options(dict(CFG, use_kda_lora=True))


def test_the_kernels_least_work_is_counted_from_the_sizes():
    # a decoding slot and step: 6 layers x (read + write) x 2 MB
    assert kda_lib.step_bytes(CFG, family) == 6 * 2 * 2_097_152
    # a token and layer: 32 heads x 2 x (2 x 64 x 128 + 64^2 / 3 + 64 x 256
    # + 3 x 128 x 128 + 64 x 128 / 2) multiply-adds
    per_head = 2 * 64 * 128 + 64 * 64 / 3 + 64 * 256 + 3 * 128 * 128 \
        + 64 * 128 / 2
    assert kda_lib.scan_flops_per_token(CFG, family) \
        == pytest.approx(2 * 32 * per_head)
    assert kda_lib.scan_bytes_per_token(CFG, family) == 4 * 32 * 5 * 128


def test_the_cell_and_its_metrics_are_appended_and_listed():
    m = perf_toy.manifest()
    names = [w["name"] for w in m["workloads"]]
    cell = m["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("ling3_flash_ep4", "reason_docs_s128", 1)
    # after every cell that was there, whatever comes after it
    assert names.index(CELL) > names.index("smallthinker_serve_shortlong")
    listed = {e["name"] for e in m["per_layer"]
              if CELL in e.get("workloads", [])}
    assert listed == {
        "flood_attn_dev_pct", "flood_mlp_dev_pct", "flood_mixer_dev_pct",
        "flood_head_sample_dev_pct", "flood_unscoped_dev_pct",
        "flood_mla_dev_pct", "flood_mla_decode_roofline",
        "flood_moe_glu_dev_pct", "flood_moe_glu_roofline",
        "flood_kda_dev_pct", "flood_kda_step_roofline",
        "flood_kda_scan_roofline"}
    order = [e["name"] for e in m["per_layer"]]
    assert order.index("flood_window_prefill_roofline") \
        < order.index("flood_kda_dev_pct") \
        < order.index("flood_kda_step_roofline") \
        < order.index("flood_kda_scan_roofline")
    for e in m["per_layer"] + m["end_to_end"]:
        if CELL in e.get("workloads", []):   # after the cells listed before
            before = e["workloads"][:e["workloads"].index(CELL)]
            assert all(names.index(w) < names.index(CELL) for w in before)
    assert CELL in next(e for e in m["end_to_end"]
                        if e["name"] == "serve_tok_s")["workloads"]


def test_the_traffic_file_states_what_the_issue_asked_for():
    t, e = TRAFFIC["tenants"][0], TRAFFIC["engine"]
    assert len(TRAFFIC["tenants"]) == 1 and t["arrivals"] == "poisson"
    assert (t["prompt_len_median"], t["prompt_len_sigma"],
            t["prompt_len_cap"]) == (1024, 1.0, 8192)
    assert (t["max_new_median"], t["max_new_sigma"], t["max_new_cap"]) \
        == (1024, 0.6, 3072)
    assert (e["max_slots"], e["page"], e["burst"], e["buckets"],
            e["prefill_chunk"]) == (128, 64, 8, [256, 512, 1024, 2048], 2048)
    assert e["prefill_chunks_per_tick"] == 4 and "prefix_cache" not in e
    assert e["max_blocks_per_slot"] * e["page"] == 8192 + 3072 == 11264
    assert e["num_blocks"] == 1 + 128 * 176          # every page backed
    assert TRAFFIC["drain_limit_s"] == 0 and TRAFFIC["driver"] \
        == "serve_state_latent_by_leaf" and TRAFFIC["shape_seed"] == 47
    assert TRAFFIC["rate_rule"].startswith("twice the knee")
    assert set(TRAFFIC["limits"]) == {"served_token_gap",
                                      "served_token_gap_past_bf16"}
    assert TRAFFIC["check"]["requests"] == 3 \
        and TRAFFIC["check"]["served_rows"] == t["max_new_cap"]


# ----------------------------------------------------------------- readers
def slice_obs(events, modules=(), **kw):
    """Observations of a traced slice [0, 10] with chip 0's ops `events`."""
    return dict({
        "kind": "serve", "traced": (100.0, 110.0), "t_origin": 100.0,
        "burst": 8, "config": CFG,
        "peaks": {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9},
        "trace": {"planes": [
            {"name": "/host:CPU", "lines": [{"name": "main", "events": [
                ["perf:traced", 0.0, 10.0]]}]},
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": events},
                {"name": "XLA Modules", "events": list(modules)}]}]},
    }, **kw)


def test_step_roofline_charges_the_decoding_slots_state():
    # 2 runs of 8 steps at 100 decoding slots; 50 ms of kda_step a run
    obs = slice_obs(
        [["%kda_step.1 = custom-call", 1.0, 5e-2],
         ["%kda_step.1 = custom-call", 3.0, 5e-2],
         ["%kda_step.1 = custom-call", 11.0, 5e-2],      # past the slice
         ["%kda_scan.1 = custom-call", 1.2, 5e-3]],      # another op
        modules=[["jit__decode_burst(1)", 0.9, 0.5],
                 ["jit__decode_burst(1)", 2.9, 0.5]],
        ticks=[{"t": 0.9, "dt": 0.5, "slots": 100},
               {"t": 2.9, "dt": 0.5, "slots": 100}])
    least = 16 * 100 * 6 * 2 * 2_097_152 / 819e9
    assert read("flood_kda_step_roofline", obs) \
        == pytest.approx(100 * least / 0.1)
    assert read("flood_kda_step_roofline", dict(obs, trace=None)) is None
    # a program without the op (the parent's), a family without the sizes
    assert read("flood_kda_step_roofline", slice_obs(
        [["%gdn_step.1 = custom-call", 1.0, 5e-2]],
        modules=[["jit__decode_burst(1)", 0.9, 0.5]],
        ticks=[{"t": 0.9, "dt": 0.5, "slots": 100}])) is None
    other = dict(CFG, family="qwen3_next")
    assert read("flood_kda_step_roofline", dict(obs, config=other)) is None


def test_scan_roofline_charges_real_tokens_of_the_chunks_in_the_slice():
    # a whole chunk and 300 real tokens dispatched in the slice; a third too
    # late to have run inside it
    obs = slice_obs(
        [["%kda_terms.2 = custom-call", 2.0, 4e-3],
         ["%kda_scan.3 = custom-call", 2.1, 2e-3],
         ["%kda_step.3 = custom-call", 2.2, 9e-3]],
        chunks=[[101.0, 101.2, 0, 2048], [104.0, 104.1, 2048, 300],
                [109.8, 109.9, 0, 2048]])
    a_token = max(kda_lib.scan_flops_per_token(CFG, family) / 197e12,
                  kda_lib.scan_bytes_per_token(CFG, family) / 819e9)
    least = 6 * (2348 * a_token + 2 * 2 * 2_097_152 / 819e9)
    assert read("flood_kda_scan_roofline", obs) \
        == pytest.approx(100 * least / 6e-3)
    assert read("flood_kda_scan_roofline", dict(obs, chunks=[])) is None
    assert read("flood_kda_scan_roofline", slice_obs(
        [["%fusion.1 = fusion", 2.0, 1e-3]],
        chunks=[[101.0, 101.2, 0, 2048]])) is None


def test_kda_share_counts_the_three_kernels_by_name():
    obs = slice_obs([
        ["%kda_step.1 = custom-call", 1.0, 1.0],
        ["%kda_terms.1 = custom-call", 2.0, 0.25],
        ["%kda_scan.1 = custom-call", 3.0, 0.25],
        ["%gdn_scan.1 = custom-call", 4.0, 0.5],
        ["%fusion.8 = fusion", 5.0, 2.0]])
    assert read("flood_kda_dev_pct", obs) == pytest.approx(100 * 1.5 / 4)
    assert read("flood_kda_dev_pct", slice_obs(
        [["%fusion.8 = fusion", 4.0, 2.5]])) is None


def test_the_shared_readers_read_this_family():
    """`flood_moe_glu_roofline` takes an expert's bytes and
    `flood_mla_decode_roofline` the one latent layer's 1,152 B a live token
    from the family, unedited."""
    obs = slice_obs(
        [["%moe_gmm_glu.3 = custom-call", 1.0, 1e-2],
         ["%paged_decode_mla.4 = custom-call", 1.2, 1e-2]],
        modules=[["jit__decode_burst(1)", 0.9, 0.5]],
        ticks=[{"t": 0.9, "dt": 0.5, "slots": 100, "live": 200_000}],
        expert_bursts=[[101.0, 8 * 500]],
        decode_bytes=family.decode_bytes(CFG))
    assert read("flood_moe_glu_roofline", obs) == pytest.approx(
        100 * 8 * 500 * 11_796_480 / 819e9 / 1e-2)
    assert 0 < read("flood_mla_decode_roofline", obs) < 100


# ------------------------------------------------------------- the driver
def test_the_sample_is_the_longest_one_chunked_and_one_in_one_chunk():
    class Done:
        def __init__(self, rid, n):
            self.rid, self.tokens = rid, [1] * n

    by_rid = {i: {"prompt": [0] * p} for i, p in enumerate(
        [3, 10, 20, 40, 70])}
    ok = [Done(i, 5) for i in by_rid]
    pick = driver.three_admissions(16)
    got = pick(ok, by_rid, 7, 3, [4, 8])
    prompts = [len(p) for p, _ in got]
    # of the chunked prompts, 20 ends 3 tokens after a boundary (< 16 // 4)
    assert prompts[0] == 70 and prompts[1] == 20 and prompts[2] <= 16
    # none that near: the nearest (40 ends 7 after, 30 ends 13 after)
    by_rid[2] = {"prompt": [0] * 30}
    assert [len(p) for p, _ in pick(ok, by_rid, 7, 3, [4, 8])][1] == 40
    assert len(pick(ok[:1], by_rid, 7, 3, [4, 8])) == 1
    assert pick([], by_rid, 7, 3, [4, 8]) == []


def test_the_drawn_dt_bias_is_shifted_and_nothing_else():
    abstract = {"mamba0": {"dt_bias": jax.ShapeDtypeStruct((64,), jnp.float32),
                           "A_log": jax.ShapeDtypeStruct((4,), jnp.float32)},
                "norm0": {"scale": jax.ShapeDtypeStruct((8,), jnp.float32)}}
    plain = weights_by_leaf.make_params(abstract, 5)
    moved = driver.shifted(weights_by_leaf.make_params,
                           {"dt_bias": -4.0})(abstract, 5)
    assert np.allclose(moved["mamba0"]["dt_bias"],
                       plain["mamba0"]["dt_bias"] - 4.0)
    assert (moved["mamba0"]["A_log"] == plain["mamba0"]["A_log"]).all()
    assert (moved["norm0"]["scale"] == plain["norm0"]["scale"]).all()


# ---------------------------------------------------------------- toy run
def toy_cell():
    cell = {"name": CELL, "config": "ling3_flash_ep4",
            "traffic": "reason_docs_s128", "chips": 1}
    traffic = copy.deepcopy(TRAFFIC)
    traffic["tenants"][0].update(rate_rps=30.0, prompt_len_median=12,
                                 prompt_len_cap=40, max_new_median=6,
                                 max_new_cap=12)
    traffic["engine"].update(max_slots=3, page=4, buckets=[4, 8], burst=4,
                             prefill_chunk=8, max_blocks_per_slot=14,
                             num_blocks=43)
    # one padded width for every request: one compile of the reference
    traffic["check"].update(pad_rows=64, served_rows=12)
    # the benchmark's own 0.02-normal weights leave a toy's logits within
    # 0.3 of each other: a sound run reads under 0.001, an altered token 0.1
    traffic["limits"] = {"served_token_gap": 0.02,
                         "served_token_gap_past_bf16": 0.02}
    # one period (K K T) is every kind of layer: fewer compiles
    return cell, ling3_toy.config(source=CFG["source"], layers_run=3), traffic


SOUND_SEED = 3_000_000_019


def toy_run(outroot, seed):
    cell, config, traffic = toy_cell()
    kept, real = {}, driver.run
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(driver, "run",
                      lambda ctx: kept.setdefault("result", real(ctx)))
        line = harness.measure(
            perf_toy.manifest(), cell, config, traffic, seed=seed,
            seconds=0.5, trace=False, devices=jax.devices()[:1],
            chip_peaks=perf_toy.PEAKS, outroot=str(outroot))
    series = json.load(open(os.path.join(
        outroot, cell["name"], f"seed{seed}_trace0", "series.json")))
    return line, kept["result"], series


@pytest.fixture(scope="module")
def sound_run(tmp_path_factory):
    return toy_run(tmp_path_factory.mktemp("sound"), SOUND_SEED)


@pytest.mark.parametrize("broken", [False, True])
def test_toy_run_is_correct_unless_a_token_is_altered(broken, tmp_path,
                                                      monkeypatch, request):
    swapped = (serve.build_engine, serve.reference_checks,
               serve.reference_gaps, serve.pick_sample,
               weights_by_leaf.make_params)
    if broken:
        from ddp_practice_tpu.serve import engine

        real = engine.PagedEngine.step_burst
        monkeypatch.setattr(engine.PagedEngine, "step_burst",
                            lambda self: (real(self) + 1) % 96)
        line, _, series = toy_run(tmp_path, SOUND_SEED)
    else:
        line, _, series = request.getfixturevalue("sound_run")
    assert line["correct"] is not broken and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"  # never a device number
    # the drivers put back what they swapped
    assert weights.make_params is not weights_by_leaf.make_params
    assert swapped == (serve.build_engine, serve.reference_checks,
                       serve.reference_gaps, serve.pick_sample,
                       weights_by_leaf.make_params)
    by_name = {c["name"]: c for c in series["checks"]}
    assert set(by_name) == {"served_token_logit_gap_max",
                            "served_token_logit_gap_past_bf16"}
    assert by_name["served_token_logit_gap_max"]["ok"] is not broken


def test_the_driver_notes_chunks_admissions_and_experts(sound_run):
    _, result, series = sound_run
    obs = result["obs"]
    # every prompt is chunk-admitted: bookkeeping in `admits`, the work here
    assert obs["admits"] and obs["chunks"] and obs["expert_bursts"]
    assert all(a <= b and first % 8 == 0 and 1 <= n <= 8
               for a, b, first, n in obs["chunks"])
    assert any(first > 0 for _, _, first, _ in obs["chunks"])
    assert series["experts_touched_a_burst"] > 0
    # the check is the window driver's, over the long driver's blocked gaps
    assert driver.serve_window_by_leaf is serve_window_by_leaf
    assert driver.serve_long_by_leaf is serve_long_by_leaf


def test_both_controls_read_over_a_sound_run(tmp_path):
    """What the calibration reads on the chip, at toy size, through the
    driver's blocked `reference_gaps`: over sequences the cell could have
    served, the reference's own best tokens read 0; the tokens that the
    reference computed in e4m3 puts first lie below them somewhere, and so
    do those of the reference whose carried state is zeroed every 8
    positions (a chunk of the toy cell)."""
    cell, config, traffic = toy_cell()
    ctx = harness.make_ctx(cell, config, traffic, seed=11, seconds=0.5,
                           trace=False, devices=jax.devices()[:1],
                           chip_peaks=perf_toy.PEAKS, outroot=str(tmp_path))
    _, params = ling3_toy.model_and_params(config, seed=11)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, 30).tolist() for _ in range(4)]
    with jax.default_matmul_precision("highest"):
        first = jax.jit(lambda t, reset: reference.forward(
            params, t, config, state_reset=reset)[0, 29:41].argmax(-1),
            static_argnums=1)
        padded = [jnp.asarray([p + [0] * 34]) for p in prompts]
        best = [np.asarray(first(t, None)) for t in padded]
        lost = [np.asarray(first(t, 8)) for t in padded]
        sample = [(p, [int(b[0])]) for p, b in zip(prompts, best)]
        sound = np.concatenate(
            serve_long_by_leaf.reference_gaps(ctx, params, sample))
        control = np.concatenate(serve_long_by_leaf.reference_gaps(
            ctx, params, [(p, [0] * 12) for p in prompts], quant="fp8"))
        reset = np.concatenate(serve_long_by_leaf.reference_gaps(
            ctx, params, [(p, [int(b[0])]) for p, b in zip(prompts, lost)]))
    assert sound.shape == (4,) and sound.max() == 0
    assert control.shape == (48,) and control.max() > 0
    assert reset.shape == (4,) and reset.max() > 0
