"""The DeepSeek-V3 (latent attention + gated experts) configuration's
benchmark files: the configuration against its source, the family's counts
against the issue's, the reference against the program through pages and
against its own control, the readers on hand-made observations, and one toy
run of the cell through the harness."""

import copy
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_toy
import perf_toy
from perf import run as harness
from perf.families import deepseek_v3 as family
from perf.lib import weights, weights_by_leaf
from perf.reference import deepseek_v3 as reference

CFG = perf_toy.load("perf/configs/kanana2_30b_pp8.json")
# the source's config.json, every number (the catalog's row)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256,
}


def read(metric, obs):
    return importlib.import_module(f"perf.layer_metrics.{metric}").read(obs)


# ----------------------------------------------------------- configuration
def test_published_widths_are_unchanged_and_the_cut_is_stated():
    assert {k: CFG[k] for k in PUBLISHED} == PUBLISHED
    entry = next(c for c in perf_toy.manifest()["configs"]
                 if c["name"] == "kanana2_30b_pp8")
    assert entry["reduced"] == ["layers_run"] == list(CFG["reduced"])
    assert CFG["layers_run"] == 6 and CFG["published"][
        "num_hidden_layers"] == 48
    assert family.counts(CFG) == {"attn": 6, "dense": 1, "moe": 5}
    assert "8 pipeline stages of 6 layers" in CFG["deployment"] \
        and "head" in CFG["deployment"]
    for key in ("weights", "latent_row", "cache_dtype"):
        assert CFG["assumed"][key]
    opts = family.model_options(CFG)
    assert (opts["num_experts"], opts["experts_held"], opts["top_k"],
            opts["expert_dim"], opts["shared_dim"], opts["vocab_size"],
            opts["num_layers"], opts["latent_dim"], opts["rope_dim"]) == (
        128, 128, 6, 768, 1536, 128256, 6, 512, 64)
    with pytest.raises(ValueError):
        family.model_options(dict(CFG, q_lora_rank=1536))


def test_family_counts_the_bytes_the_issue_counted():
    assert family.decode_bytes(CFG)[0] == 6912             # a cached token
    assert family.decode_bytes(CFG)[1] == 6 * 32 * (576 + 512) * 2
    assert family.expert_bytes(CFG) == 9_437_184
    assert family.param_count(CFG) == 3_789_584_000        # 7.58 GB in bf16
    assert round(2 * family.param_count(CFG) / 1e9, 2) == 7.58
    whole = family.param_count(dict(CFG, layers_run=48))
    assert 30.6e9 < whole < 30.8e9                          # "30B"
    # 69.6 kFLOP a cached token and layer against 1,152 B: 60 FLOP/B
    assert family.mla_decode_flops_per_token(CFG) == 6 * 2 * 32 * 1088
    toy = deepseek_toy.config()
    _, params = deepseek_toy.model_and_params(toy)
    assert family.param_count(toy) == sum(
        a.size for a in jax.tree.leaves(params))
    assert family.decode_flops_per_token(CFG) > 2 * 2048 * 128256


# --------------------------------------------------------------- reference
def test_reference_agrees_with_the_program_and_fp8_does_not():
    cfg = deepseek_toy.config()
    model, params = deepseek_toy.model_and_params(cfg, seed=11)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
    want = np.asarray(jax.jit(
        lambda p, t: reference.forward(p, t, cfg))(params, tokens))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale
    low = np.asarray(jax.jit(
        lambda p, t: reference.forward(p, t, cfg, "fp8"))(params, tokens))
    assert np.abs(low - want).max() > 100 * 1e-4 * scale
    loss = float(jax.jit(lambda p, t: reference.loss(
        p, {"tokens": t}, cfg))(params, tokens))
    assert np.isfinite(loss) and loss > 0


def test_sorted_windows_equal_every_expert_for_every_token():
    """The reference's own short cut (windows of picks sorted by expert)
    against the dense sum it stands for, windows smaller than an expert's
    share so that an expert takes several."""
    cfg = deepseek_toy.config()
    _, params = deepseek_toy.model_and_params(cfg, seed=2)
    p = params["moe1"]
    x = jax.random.normal(jax.random.PRNGKey(3), (50, 64))
    with jax.default_matmul_precision("highest"):
        picks, w = reference.route(x, p, cfg)
        want = np.zeros((50, 64), np.float32)
        for e in range(16):
            one = {"gate": {"kernel": p["expert_gate"][e]},
                   "up": {"kernel": p["expert_up"][e]},
                   "down": {"kernel": p["expert_down"][e]}}
            gate = np.asarray(jnp.sum(jnp.where(picks == e, w, 0.0), -1))
            want += gate[:, None] * np.asarray(reference.swiglu(x, one))
        for rows in (4, 64):
            got = np.asarray(reference.routed_experts(x, p, cfg, rows=rows))
            np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------- readers
def latent_obs(slots=128, ops=None, live=128 * 7000):
    """A 10 s slice: two decode bursts of 8 steps (1.0 s and 1.2 s of
    device time) with a suffix prefill between them; kernels by name."""
    ops = ops if ops is not None else [
        ["%while.1 = while(...)", 1.0, 1.0],                   # a parent
        ["%paged_decode_mla.3 = custom-call(...)", 1.0, 0.3],
        ["%moe_gmm_glu.2 = custom-call(...)", 1.3, 0.4],
        ["%fusion.9 = fusion(...)", 1.8, 0.2],
        ["%moe_gmm_glu.7 = custom-call(...)", 3.0, 0.5],       # the prefill's
        ["%fusion.11 = fusion(...)", 3.5, 0.5],
        ["%paged_decode_mla.3 = custom-call(...)", 5.0, 0.5],
        ["%moe_gmm_glu.2 = custom-call(...)", 5.5, 0.6],
        ["%copy-done.4 = copy-done(...)", 5.6, 0.1],   # overlaps the kernel
        ["%fusion.9 = fusion(...)", 6.1, 0.1]]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ["jit__decode_burst(1)", 1.0, 1.0],
                ["jit__prefix_prefill(2)", 3.0, 1.0],
                ["jit__decode_burst(1)", 5.0, 1.2]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["perf:traced", 0.0, 10.0]]}]}]}
    return {"kind": "serve", "trace": trace, "traced": (100.0, 110.0),
            "window": (95.0, 140.0), "spans": [], "burst": 8,
            "t_origin": 95.0, "chips": 1, "config": CFG,
            "decode_bytes": family.decode_bytes(CFG),
            "peaks": {"hbm_bytes_s": 819e9},
            "expert_bursts": [[101.0, 8 * 5 * 120], [105.0, 8 * 5 * 126],
                              [109.0, 8 * 5 * 128], [120.0, 8 * 5 * 30]],
            "ticks": [{"t": 5.5, "dt": 2.0, "slots": slots, "live": live,
                       "queue": 9},
                      {"t": 9.5, "dt": 2.0, "slots": slots, "live": live,
                       "queue": 9},
                      {"t": 30.0, "dt": 2.0, "slots": 1, "live": 10,
                       "queue": 0}]}


def test_readers_on_a_hand_made_latent_trace():
    obs = latent_obs()
    # the first burst's second is busy throughout (its `while`)
    busy = 1.0 + 0.5 + 0.5 + 0.5 + 0.6 + 0.1
    assert read("flood_mla_dev_pct", obs) == pytest.approx(100 * 0.8 / busy)
    assert read("flood_moe_glu_dev_pct", obs) == pytest.approx(
        100 * 1.5 / busy)
    # the experts that had a row (124.67 of 128 a layer and step, as the
    # program counted them), once each; the prefill's kernel left out
    least = 16 * 5 * (120 + 126 + 128) / 3 * 9437184 / 819e9
    assert read("flood_moe_glu_roofline", obs) == pytest.approx(
        100 * least / 1.0)
    # two ticks of 8 steps: the live rows once a step (a slot grows by one
    # a step), and q~ and out of every slot and step
    rows = 2 * (8 * 128 * 7000 + 128 * 36)
    least = (rows * 6912 + 2 * 8 * 128 * 6 * 32 * 1088 * 2) / 819e9
    assert read("flood_mla_decode_roofline", obs) == pytest.approx(
        100 * least / 0.8)
    # the accepted reader sees the same kernel (its name holds
    # "paged_decode") over the same bytes
    assert read("flood_paged_decode_roofline", obs) == pytest.approx(
        read("flood_mla_decode_roofline", obs))
    assert read("flood_moe_glu_roofline",
                dict(obs, expert_bursts=[])) is None


@pytest.mark.parametrize("metric", [
    "flood_mla_dev_pct", "flood_moe_glu_dev_pct", "flood_moe_glu_roofline",
    "flood_mla_decode_roofline"])
def test_a_program_without_the_kernels_gives_nothing_and_does_not_raise(
        metric):
    """The parent commit's trace (no such op) and another family's
    configuration: None, never an exception."""
    plain = [["%fusion.9 = fusion(...)", 1.0, 0.5],
             ["%paged_decode.1 = custom-call(...)", 1.5, 0.3],
             ["%moe_gmm.1 = custom-call(...)", 1.8, 0.2]]
    assert read(metric, latent_obs(ops=plain)) is None
    assert read(metric, dict(latent_obs(), trace=None)) is None
    for other in ("gpt2_small", "nemotron3_super_ep4"):
        cfg = perf_toy.load(f"perf/configs/{other}.json")
        assert read(metric, dict(latent_obs(ops=plain), config=cfg)) is None


# ------------------------------------------------------------------- a run
def toy_cell():
    cell = {"name": "kanana2_serve_docs", "config": "kanana2_30b_pp8",
            "traffic": "toy", "chips": 1}
    traffic = copy.deepcopy(perf_toy.load(
        "perf/traffic/docs_flood_s128.json"))
    traffic["tenants"][0].update(
        rate_rps=40.0, sessions=2, session_prefix_len=24,
        turns_per_session=3, prompt_len_median=5, prompt_len_cap=48,
        max_new_median=6, max_new_cap=12)
    traffic["engine"].update(max_slots=3, page=8, buckets=[8, 16], burst=4,
                             max_blocks_per_slot=8, num_blocks=40,
                             prefill_chunk=16)
    traffic["check"].update(pad_to=64, requests=4)
    traffic["limits"] = dict(perf_toy.SERVE_LIMITS,
                             served_token_gap_p99=0.05)
    return cell, deepseek_toy.config(source=CFG["source"]), traffic


@pytest.mark.parametrize("broken", [False, True])
def test_toy_run_is_correct_unless_a_token_is_altered(broken, tmp_path,
                                                      monkeypatch):
    cell, config, traffic = toy_cell()
    if broken:
        from ddp_practice_tpu.serve import engine

        real = engine.PagedEngine.step_burst
        monkeypatch.setattr(engine.PagedEngine, "step_burst",
                            lambda self: (real(self) + 1) % 96)
    line = harness.measure(
        perf_toy.manifest(), cell, config, traffic, seed=3_000_000_019,
        seconds=0.5, trace=False, devices=jax.devices()[:1],
        chip_peaks=perf_toy.PEAKS, outroot=str(tmp_path))
    assert line["correct"] is not broken and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"  # never a device number
    assert weights.make_params is not weights_by_leaf.make_params
    from perf.drivers import serve
    assert serve.reference_checks.__name__ == "reference_checks"  # put back
    series = json.load(open(os.path.join(
        tmp_path, cell["name"], "seed3000000019_trace0", "series.json")))
    # both numbers are compared, each beside its own limit
    by_name = {c["name"]: c for c in series["checks"]}
    assert set(by_name) == {"served_token_logit_gap_max",
                            "served_token_logit_gap_p99"}
    assert by_name["served_token_logit_gap_p99"]["ok"] is not broken
    assert by_name["served_token_logit_gap_p99"]["value"] \
        <= by_name["served_token_logit_gap_max"]["value"]
    # 4 steps x 2 expert layers x at most 9 picks (3 slots x top-3) a burst
    assert 0 < series["experts_touched_a_burst"] <= 4 * 2 * 9


def test_traffic_file_states_what_the_issue_asked_for():
    tr = perf_toy.load("perf/traffic/docs_flood_s128.json")
    t, e = tr["tenants"][0], tr["engine"]
    assert (t["sessions"], t["session_prefix_len"],
            t["turns_per_session"]) == (24, 6144, 8)
    assert (t["prompt_len_median"], t["prompt_len_sigma"],
            t["prompt_len_cap"]) == (128, 0.6, 8192)
    assert (t["max_new_median"], t["max_new_sigma"], t["max_new_cap"]) \
        == (384, 0.5, 768)
    assert t["arrivals"] == "poisson" and len(tr["tenants"]) == 1
    assert tr["rate_rule"].startswith("twice the knee")
    assert (e["max_slots"], e["burst"], e["prefix_cache"],
            e["prefill_chunk"]) == (128, 8, True, 1024)
    # a slot's table holds the longest context, the pool ~580k tokens
    assert e["page"] * e["max_blocks_per_slot"] == 8960 == 8192 + 768
    assert 575_000 <= e["page"] * (e["num_blocks"] - 1) <= 585_000
    assert max(e["buckets"]) == e["prefill_chunk"]
    # the by-leaf driver with the second number compared (its docstring)
    assert tr["drain_limit_s"] == 0 and tr["driver"] == "serve_by_leaf_p99"
    assert set(tr["limits"]) == {"served_token_gap", "served_token_gap_p99"}
    assert tr["limits"]["served_token_gap_p99"] < 1.0 < tr["limits"][
        "served_token_gap"]
    assert tr["check"] == dict(tr["check"], requests=4, pad_to=8960)
    cell = next(w for w in perf_toy.manifest()["workloads"]
                if w["name"] == "kanana2_serve_docs")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana2_30b_pp8", "docs_flood_s128", 1)
