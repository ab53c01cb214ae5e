"""The Nemotron-H configuration's benchmark files: the configuration against
its source, the readers on hand-made observations, the reference against the
program and against its own control, the weights a leaf at a time, and one
toy run of the cell through the harness."""

import copy
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nemotron_toy
import perf_toy
from perf import run as harness
from perf.families import nemotron_h as family
from perf.lib import weights, weights_by_leaf
from perf.reference import nemotron_h as reference

CFG = perf_toy.load("perf/configs/nemotron3_super_ep4.json")


def read(metric, obs):
    return importlib.import_module(f"perf.layer_metrics.{metric}").read(obs)


# ----------------------------------------------------------- configuration
def test_published_widths_are_unchanged_and_the_cuts_are_stated():
    want = {"hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64,
            "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
            "chunk_size": 128, "num_attention_heads": 32,
            "num_key_value_heads": 2, "head_dim": 128,
            "moe_intermediate_size": 2688, "moe_latent_size": 1024,
            "moe_shared_expert_intermediate_size": 5376,
            "n_routed_experts": 512, "num_experts_per_tok": 22,
            "routed_scaling_factor": 5, "num_hidden_layers": 88}
    assert {k: CFG[k] for k in want} == want
    assert set(CFG["reduced"]) == {"hybrid_override_pattern",
                                   "n_routed_experts_held", "vocab_size"}
    # a whole period, 128 >= 8 experts, 1/4 >= 1/8 of the vocabulary
    assert CFG["hybrid_override_pattern"] == \
        CFG["published"]["hybrid_override_pattern"][:11]
    assert family.counts(CFG) == {"M": 5, "E": 5, "*": 1}
    assert CFG["n_routed_experts_held"] == 128
    assert CFG["vocab_size"] * 4 == CFG["published"]["vocab_size"]
    assert "32 v5e chips" in CFG["deployment"]


def test_family_counts_the_bytes_the_issue_counted():
    assert family.decode_bytes(CFG) == (1024, 2 * 4096 * 2)
    assert family.expert_bytes(CFG) == 2 * 1024 * 2688 * 2      # 11.0 MB
    assert family.ssm_state_bytes(CFG) == 128 * 64 * 128 * 4    # 4.19 MB
    opts = family.model_options(CFG)
    assert opts["pattern"] == "MEMEMEM*EME" and opts["experts_held"] == 128
    # the model as run: 4.65 billion parameters, 9.3 GB in bf16
    from ddp_practice_tpu.models import create_model

    model = create_model("nemotron_h", **opts)
    abstract = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(abstract))
    assert 4.64e9 < n < 4.66e9
    assert 2.2e9 < family.decode_flops_per_token(CFG) < 2.4e9


# ----------------------------------------------------------------- weights
def test_weights_by_leaf_follow_the_same_rule_from_a_large_seed():
    abstract = {"norm": {"scale": jax.ShapeDtypeStruct((4096,), jnp.float32)},
                "moe": {"expert_w1": jax.ShapeDtypeStruct((4, 64, 96),
                                                          jnp.float32),
                        "A_log": jax.ShapeDtypeStruct((128,), jnp.float32)}}
    seed = 5_000_000_011
    a = weights_by_leaf.make_params(abstract, seed, dtype=jnp.bfloat16)
    b = weights_by_leaf.make_params(abstract, seed, dtype=jnp.bfloat16)
    c = weights_by_leaf.make_params(abstract, seed + 1, dtype=jnp.bfloat16)
    assert jax.tree.all(jax.tree.map(lambda x, y: bool((x == y).all()), a, b))
    assert not bool((a["moe"]["expert_w1"] == c["moe"]["expert_w1"]).all())
    assert a["norm"]["scale"].dtype == jnp.bfloat16
    f = jax.tree.map(lambda x: np.asarray(x, np.float32), a)
    assert abs(f["norm"]["scale"].mean() - 1.0) < 0.01
    assert abs(f["norm"]["scale"].std() - 0.1) < 0.01
    assert abs(f["moe"]["expert_w1"].std() - 0.02) < 0.001
    assert abs(f["moe"]["A_log"].mean()) < 0.01
    # a tree of arrays is consumed leaf by leaf (two sets do not fit)
    d = weights_by_leaf.make_params(a, seed + 2)
    assert a["norm"]["scale"].is_deleted() and d["norm"]["scale"].shape == \
        (4096,)


@pytest.mark.parametrize("what", ["weights", "bursts", "harness"])
def test_the_driver_swaps_in_one_place_and_puts_it_back(what):
    """`by_leaf()` is the one place that swaps attributes of files this PR
    may not edit; each is back when it closes, an error inside or not."""
    from perf.drivers import serve, serve_by_leaf

    before = (weights.make_params, serve.build_engine, harness.open_cell)
    kw = {"weights": {}, "bursts": {"bursts": []},
          "harness": {"harness": harness}}[what]
    with pytest.raises(RuntimeError), serve_by_leaf.by_leaf(**kw):
        assert weights.make_params is weights_by_leaf.make_params
        assert (serve.build_engine is before[1]) == (what != "bursts")
        assert (harness.open_cell is before[2]) == (what != "harness")
        raise RuntimeError("inside")
    assert (weights.make_params, serve.build_engine,
            harness.open_cell) == before


# --------------------------------------------------------------- reference
def test_reference_agrees_with_the_program_and_fp8_does_not():
    cfg = nemotron_toy.config()
    model, params = nemotron_toy.model_and_params(cfg, seed=11)
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


# ----------------------------------------------------------------- readers
def hybrid_obs(slots=128, ops=None):
    """A 10 s slice: two decode bursts of 8 steps (1.0 s and 1.2 s of
    device time) with a prefill between them; kernels by name inside."""
    ops = ops if ops is not None else [
        ["%while.1 = while(...)", 1.0, 1.0],                   # a parent
        ["%ssm_step.3 = custom-call(...)", 1.0, 0.3],
        ["%moe_gmm.2 = custom-call(...)", 1.3, 0.4],
        ["%paged_decode.1 = custom-call(...)", 1.7, 0.1],
        ["%fusion.9 = fusion(...)", 1.8, 0.2],
        ["%moe_gmm.7 = custom-call(...)", 3.0, 0.5],           # the prefill's
        ["%fusion.11 = fusion(...)", 3.5, 0.5],
        ["%ssm_step.3 = custom-call(...)", 5.0, 0.5],
        ["%moe_gmm.2 = custom-call(...)", 5.5, 0.6],
        ["%copy-done.4 = copy-done(...)", 5.6, 0.1],   # overlaps the kernel
        ["%fusion.9 = fusion(...)", 6.1, 0.1]]
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
            "peaks": {"hbm_bytes_s": 819e9},
            # three bursts in the slice (8 steps x 5 layers each), one after
            "expert_bursts": [[101.0, 8 * 5 * 100], [105.0, 8 * 5 * 110],
                              [109.0, 8 * 5 * 120], [120.0, 8 * 5 * 30]],
            "ticks": [{"t": 5.5, "dt": 2.0, "slots": slots, "live": 0,
                       "queue": 9},
                      {"t": 9.5, "dt": 2.0, "slots": slots, "live": 0,
                       "queue": 9},
                      {"t": 30.0, "dt": 2.0, "slots": 1, "live": 0,
                       "queue": 0}]}


def test_readers_on_a_hand_made_hybrid_trace():
    obs = hybrid_obs()
    busy = 0.3 + 0.4 + 0.1 + 0.2 + 0.5 + 0.5 + 0.5 + 0.6 + 0.1
    assert read("flood_moe_dev_pct", obs) == pytest.approx(
        100.0 * 1.5 / busy)
    assert read("flood_ssm_dev_pct", obs) == pytest.approx(
        100.0 * 0.8 / busy)
    # 16 decode steps, 128 slots, 5 Mamba layers, the state read and written
    least = 16 * 128 * 5 * 2 * 4194304 / 819e9
    assert read("flood_ssm_step_roofline", obs) == pytest.approx(
        100.0 * least / 0.8)
    # the experts that had a row (110 of 128 a layer and step, as the
    # program counted them), once each; the prefill's gmm left out
    least = 16 * 5 * 110 * 11010048 / 819e9
    assert read("flood_moe_gmm_roofline", obs) == pytest.approx(
        100.0 * least / 1.0)
    # without the program's count there is nothing to assume
    assert read("flood_moe_gmm_roofline",
                dict(obs, expert_bursts=[])) is None
    obs.pop("expert_bursts")
    assert read("flood_moe_gmm_roofline", obs) is None


@pytest.mark.parametrize("metric", ["flood_moe_dev_pct", "flood_ssm_dev_pct",
                                    "flood_ssm_step_roofline",
                                    "flood_moe_gmm_roofline"])
def test_a_program_without_the_kernels_gives_nothing_and_does_not_raise(
        metric):
    """The parent commit's trace (no such op) and another family's
    configuration: None, never an exception."""
    plain = [["%fusion.9 = fusion(...)", 1.0, 0.5],
             ["%paged_decode.1 = custom-call(...)", 1.5, 0.5]]
    assert read(metric, hybrid_obs(ops=plain)) is None
    assert read(metric, dict(hybrid_obs(), trace=None)) is None
    lm = dict(hybrid_obs(ops=plain),
              config=perf_toy.load("perf/configs/gpt2_small.json"))
    assert read(metric, lm) is None


def test_ssm_roofline_charges_the_decoding_slots_alone():
    assert read("flood_moe_gmm_roofline", hybrid_obs(slots=60)) \
        == pytest.approx(read("flood_moe_gmm_roofline", hybrid_obs()))
    assert read("flood_ssm_step_roofline", hybrid_obs(slots=60)) \
        == pytest.approx(read("flood_ssm_step_roofline", hybrid_obs())
                         * 60 / 128)


# ------------------------------------------------------------------- a run
def toy_cell():
    cell = {"name": "nemo3s_serve_flood", "config": "nemotron3_super_ep4",
            "traffic": "toy", "chips": 1}
    traffic = copy.deepcopy(perf_toy.load(
        "perf/traffic/reason_flood_s128.json"))
    traffic["tenants"][0].update(rate_rps=40.0, prompt_len_median=10,
                                 prompt_len_cap=24, max_new_median=6,
                                 max_new_cap=12)
    traffic["engine"].update(max_slots=3, buckets=[8, 24], burst=4,
                             max_blocks_per_slot=3, num_blocks=12)
    traffic["check"].update(pad_to=40, requests=6)
    traffic["limits"] = perf_toy.SERVE_LIMITS
    return cell, nemotron_toy.config(source=CFG["source"]), traffic


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
    series = json.load(open(os.path.join(
        tmp_path, cell["name"], "seed3000000019_trace0", "series.json")))
    # 4 steps x 3 expert layers x at most 4 held experts a burst
    assert 0 < series["experts_touched_a_burst"] <= 4 * 3 * 4


def test_traffic_files_state_what_the_issue_asked_for():
    tr = perf_toy.load("perf/traffic/reason_flood_s128.json")
    t, e = tr["tenants"][0], tr["engine"]
    assert (t["prompt_len_median"], t["prompt_len_sigma"],
            t["prompt_len_cap"]) == (192, 0.8, 768)
    assert (t["max_new_median"], t["max_new_sigma"], t["max_new_cap"]) \
        == (384, 0.6, 1024)
    # ISSUE names the rate: twice the knee the sweeps found, no other
    assert t["rate_rps"] == 18.0 and t["arrivals"] == "poisson"
    assert tr["rate_rule"].startswith("twice the knee, 9 req/s")
    assert (e["max_slots"], e["page"], e["burst"], e["max_blocks_per_slot"],
            e["num_blocks"]) == (128, 16, 8, 114, 1 + 128 * 114)
    assert tr["drain_limit_s"] == 0 and tr["driver"] == "serve_by_leaf"
    assert tr["check"] == dict(tr["check"], requests=16, pad_to=2048)
    one, four = (perf_toy.load(f"perf/traffic/{n}.json")
                 for n in ("lm_2k_b8", "lm_2k_b8_dp4"))
    same = set(four) - {"name", "why", "data_placement_picked", "limits"}
    assert {k: one[k] for k in same} == {k: four[k] for k in same}
    assert json.dumps(one["limits"])  # its own readings (PERF.md section 2)
