"""The Qwen3-Next configuration's benchmark files: the configuration against
its source (every key of the catalog row; depth, experts held and vocabulary
reduced and nothing else), the family's bytes and operations, the readers on
hand-made observations, the reference against the program and against its own
control, and one toy run of the cell through the harness."""

import copy
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perf_toy
import qwen3_next_toy
from perf import run as harness
from perf.drivers import serve
from perf.families import qwen3_next as family
from perf.lib import weights, weights_by_leaf
from perf.reference import qwen3_next as reference

CFG = perf_toy.load("perf/configs/qwen3next_80b_ep4.json")
TRAFFIC = perf_toy.load("perf/traffic/mixed_flood_s128.json")
CELL = "qwen3next_serve_mixed"
# the catalog row's `config` (model-configs guide, architectures.jsonl)
SOURCE = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
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
            CFG["vocab_size"]) == (8, 128, 0, 37984)
    assert CFG["published"] == {"vocab_size": SOURCE["vocab_size"]}
    # the guide's floors: a whole period and four layers more, eight
    # experts, an eighth of the vocabulary
    assert CFG["layers_run"] >= CFG["full_attention_interval"] + 4
    assert CFG["layers_run"] % CFG["full_attention_interval"] == 0
    assert CFG["num_experts_held"] >= 8
    assert 8 * CFG["vocab_size"] >= SOURCE["vocab_size"]
    entry = next(c for c in perf_toy.manifest()["configs"]
                 if c["name"] == "qwen3next_80b_ep4")
    assert entry["reduced"] == REDUCED and entry["source"] == CFG["source"] \
        == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/" \
           "main/config.json"
    assert {"layer_order", "norms", "gated_delta_net", "gated_attention",
            "experts", "ssm_state_dtype", "weights"} <= set(CFG["assumed"])
    assert {"multi_token_prediction", "absent_experts"} \
        == set(CFG["departures"])
    assert "24 v5e chips" in CFG["deployment"] \
        and "3,667,251,328" in CFG["deployment"]


def test_family_reads_the_layers_bytes_and_operations_from_the_keys():
    assert family.mixers(CFG) == "GGGAGGGA"
    assert family.counts(CFG) == {"G": 6, "A": 2, "Q": 8}
    opts = family.model_options(CFG)
    assert opts["pattern"] == "GQGQGQAQ" * 2
    assert (opts["gdn_key_heads"], opts["gdn_value_heads"],
            opts["gdn_key_dim"], opts["gdn_value_dim"]) == (16, 32, 128, 128)
    assert (opts["num_heads"], opts["kv_heads"], opts["head_dim"],
            opts["rope_dim"], opts["rope_theta"]) == (16, 2, 256, 64, 1e7)
    assert (opts["num_experts"], opts["top_k"], opts["expert_dim"],
            opts["shared_dim"], opts["experts_held"]) \
        == (512, 10, 512, 512, 128)
    # a cached token: 2 layers x (K + V) x 2 heads x 256 x 2 B
    assert family.decode_bytes(CFG) == (4096, 2 * 16 * 256 * 2 * 2)
    assert family.ssm_state_bytes(CFG) == 2_097_152
    assert family.conv_state_bytes(CFG) == 49_152
    assert family.expert_bytes(CFG) == 6_291_456
    # 32 heads x (3 x 128 + 2 x 128 + 64) float32 values a token: 90 KB
    assert family.scan_bytes_per_token(CFG) == 4 * 32 * 704 == 90_112
    # 32 heads x 2 x (3 x 128 x 128 + 64 x 128): 3.67 MFLOP a token
    assert family.scan_flops_per_token(CFG) == 3_670_016
    assert family.param_count(CFG) == 3_667_251_328
    # the mixers, the head and 2.5 of a token's 10 picks: 0.88 GFLOP a token
    assert 0.8e9 < family.decode_flops_per_token(CFG) < 1.0e9
    with pytest.raises(ValueError, match="an expert layer after every"):
        family.model_options(dict(CFG, mlp_only_layers=[3]))


def test_the_cell_and_its_metrics_are_appended_and_listed():
    m = perf_toy.manifest()
    cell = m["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "qwen3next_80b_ep4", "mixed_flood_s128", 1)
    assert m["configs"][-1]["name"] == "qwen3next_80b_ep4"
    listed = {e["name"] for e in m["per_layer"]
              if CELL in e.get("workloads", [])}
    assert listed == {
        "flood_attn_dev_pct", "flood_mlp_dev_pct", "flood_mixer_dev_pct",
        "flood_head_sample_dev_pct", "flood_unscoped_dev_pct",
        "flood_moe_glu_dev_pct", "flood_moe_glu_roofline",
        "flood_gdn_dev_pct", "flood_gdn_step_roofline",
        "flood_gdn_scan_roofline"}
    assert [e["name"] for e in m["per_layer"][-3:]] == [
        "flood_gdn_dev_pct", "flood_gdn_step_roofline",
        "flood_gdn_scan_roofline"]
    for e in m["per_layer"]:   # an append is the last entry of its list
        if CELL in e.get("workloads", []):
            assert e["workloads"][-1] == CELL
    serve_cells = next(e for e in m["end_to_end"]
                       if e["name"] == "serve_tok_s")["workloads"]
    assert serve_cells[-1] == CELL


def test_the_traffic_file_states_what_the_issue_asked_for():
    t, e = TRAFFIC["tenants"][0], TRAFFIC["engine"]
    assert len(TRAFFIC["tenants"]) == 1 and t["arrivals"] == "poisson"
    assert (t["prompt_len_median"], t["prompt_len_sigma"],
            t["prompt_len_cap"]) == (768, 1.0, 4096)
    assert (t["max_new_median"], t["max_new_sigma"], t["max_new_cap"]) \
        == (192, 0.6, 768)
    assert TRAFFIC["rate_rule"].startswith("three times the knee")
    assert (e["max_slots"], e["page"], e["burst"], e["buckets"]) \
        == (128, 64, 8, [256, 512, 1024, 2048, 4096])
    # a slot's table is 4,864 positions, every slot's backed
    assert e["page"] * e["max_blocks_per_slot"] == 4864 == 4096 + 768
    assert e["num_blocks"] == 1 + 128 * e["max_blocks_per_slot"]
    assert not {"prefix_cache", "prefill_chunk", "spec_decode"} & set(e)
    assert TRAFFIC["drain_limit_s"] == 0
    assert TRAFFIC["check"]["pad_to"] == 4864
    # both by-leaf drivers at once: the admissions' real lengths for
    # `flood_gdn_scan_roofline`, and the gaps' 99th percentile compared
    assert TRAFFIC["driver"] == "serve_by_leaf_admits_p99"
    assert set(TRAFFIC["limits"]) == {"served_token_gap",
                                      "served_token_gap_p99"}
    assert TRAFFIC["limits"]["served_token_gap_p99"] \
        < TRAFFIC["limits"]["served_token_gap"]


def test_the_prompts_are_short_and_long_in_one_queue():
    """About a quarter of the prompts under 400 tokens and a tenth over
    2,700, ids from the 37,984-row slice, as the schedule draws them."""
    from perf.lib import traffic as traffic_lib

    rows = traffic_lib.build_schedule(TRAFFIC, seed=5, duration_s=45.0,
                                      vocab=family.vocab(CFG))
    lens = np.asarray([len(r["prompt"]) for r in rows])
    assert 0.18 < (lens < 400).mean() < 0.32
    assert 0.06 < (lens > 2700).mean() < 0.16
    assert lens.max() <= 4096 and max(r["max_new"] for r in rows) <= 768
    assert max(max(r["prompt"]) for r in rows) < 37984


# ------------------------------------------------------- the one schedule
def dealt_rows(seed, seconds=45.0):
    from perf.lib import dealt, traffic as traffic_lib

    with dealt.one_order():
        return traffic_lib.build_schedule(TRAFFIC, seed=seed,
                                          duration_s=seconds,
                                          vocab=family.vocab(CFG))


@pytest.mark.parametrize("n", [1, 2, 5, 64, 100, 1536])
def test_the_net_is_two_permutations_and_every_aligned_run_is_spread(n):
    """Every aligned run of 2^k points of the first n holds at most one
    point in each of the 2^k equal boxes of any dyadic grid over both
    coordinates (the ranks among all 2^m stand in 2^k strata): a
    (0, m, 2)-net's property, which a digital shift keeps."""
    from perf.lib import dealt

    a, b = dealt.net_ranks(n, np.random.default_rng(n))
    assert sorted(a) == sorted(b) == list(range(n))
    m = max((n - 1).bit_length(), 1)
    full_a, full_b = dealt.net_ranks(1 << m, np.random.default_rng(n))
    for k in range(m + 1):
        for start in range(0, 1 << m, 1 << k):
            run = slice(start, start + (1 << k))
            for ka in range(k + 1):
                boxes = {(int(x) >> (m - ka), int(y) >> (m - (k - ka)))
                         for x, y in zip(full_a[run], full_b[run])}
                assert len(boxes) == 1 << k


def test_every_seed_is_dealt_the_same_requests_at_the_same_instants():
    """Lengths, counts of new tokens and arrival instants are the traffic
    file's alone; `--seed` draws the ids."""
    from perf.lib import traffic as traffic_lib

    one, other = dealt_rows(5), dealt_rows(3_800_000_011)
    shape = lambda rows: [(len(r["prompt"]), r["max_new"]) for r in rows]
    assert shape(one) == shape(other)
    np.testing.assert_allclose([r["due_s"] for r in one],
                               [r["due_s"] for r in other], atol=1e-9)
    assert [r["prompt"] for r in one] != [r["prompt"] for r in other]
    assert [r["rid"] for r in one] == list(range(len(one)))
    assert all(x["due_s"] <= y["due_s"] for x, y in zip(one, one[1:]))
    # what the generator drew, all of it and nothing else
    drawn = traffic_lib.build_schedule(TRAFFIC, seed=5, duration_s=45.0,
                                       vocab=family.vocab(CFG))
    assert sorted(r["prompt"] for r in one) \
        == sorted(r["prompt"] for r in drawn)
    assert sorted(r["max_new"] for r in one) \
        == sorted(r["max_new"] for r in drawn)
    assert one[-1]["due_s"] == pytest.approx(drawn[-1]["due_s"])
    gaps = lambda rows: np.sort(np.diff([0.0] + [r["due_s"] for r in rows]))
    np.testing.assert_allclose(gaps(one), gaps(drawn), atol=1e-9)
    # the generator is put back
    assert traffic_lib.build_schedule.__module__ == "perf.lib.traffic"


@pytest.mark.parametrize("served", [128, 384, 520, 700, 1100])
def test_whatever_part_of_the_order_is_served_is_the_stated_mix(served):
    """A window serves the order's first third or so, and a faster program
    more of it: the mean prompt and the mean answer of any such part lie
    within 1.5% of the whole offer's (a drawn order's first 520 lie 3% off
    as often as not), and its share of short and of long prompts too."""
    rows = dealt_rows(9)
    lens = np.asarray([len(r["prompt"]) for r in rows], float)
    new = np.asarray([r["max_new"] for r in rows], float)
    assert abs(lens[:served].mean() / lens.mean() - 1) < 0.015
    assert abs(new[:served].mean() / new.mean() - 1) < 0.015
    assert abs((lens[:served] < 400).mean() - (lens < 400).mean()) < 0.02
    assert abs((lens[:served] > 2700).mean() - (lens > 2700).mean()) < 0.02


def test_a_dealt_order_is_one_tenants():
    from perf.lib import dealt

    rows = [{"due_s": 0.1, "prompt": [1], "max_new": 2, "tenant": "a"},
            {"due_s": 0.2, "prompt": [1, 2], "max_new": 3, "tenant": "b"}]
    with pytest.raises(ValueError, match="one tenant"):
        dealt.deal(rows, 37)
    assert dealt.deal([], 37) == []


# --------------------------------------------------------------- reference
def test_reference_agrees_with_the_program_and_fp8_does_not():
    cfg = qwen3_next_toy.config()
    model, params = qwen3_next_toy.model_and_params(cfg, seed=11)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
    want = np.asarray(jax.jit(
        lambda p, t: reference.forward(p, t, cfg))(params, tokens))
    scale = np.abs(want).max()
    # the chunked scan's and the expert tiles' other order of sums, through
    # four layers of unit-scale weights
    assert np.abs(got - want).max() <= 1e-4 * scale
    low = np.asarray(jax.jit(
        lambda p, t: reference.forward(p, t, cfg, "fp8"))(params, tokens))
    assert np.abs(low - want).max() > 100 * 1e-4 * scale
    loss = float(jax.jit(lambda p, t: reference.loss(
        p, {"tokens": t}, cfg))(params, tokens))
    assert np.isfinite(loss) and loss > 0


def test_the_reference_holds_the_same_share_of_the_experts():
    """Experts outside [expert_offset, + num_experts_held) add nothing: with
    the held matrices zeroed the layer is the gated shared expert alone,
    whatever the router picked."""
    cfg = qwen3_next_toy.config(expert_offset=8)
    _, params = qwen3_next_toy.model_and_params(cfg, seed=2)
    p = dict(params["moe1"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, 128))
    with jax.default_matmul_precision("highest"):
        full = np.asarray(reference.experts(x, p, cfg))
        p["expert_down"] = p["expert_down"] * 0.0
        bare = np.asarray(reference.experts(x, p, cfg))
        xf = x.reshape(-1, 128)
        shared = np.asarray(
            reference.swiglu(xf, p["shared"]) * jax.nn.sigmoid(
                xf @ p["shared_expert_gate"]["kernel"])).reshape(1, 12, 128)
    np.testing.assert_allclose(bare, shared, atol=1e-6)
    assert np.abs(full - bare).max() > 0.05


# ----------------------------------------------------------------- readers
def qwen_obs(slots=128, ops=None, admits=None):
    """A 10 s slice: two decode bursts of 8 steps (1.0 s and 1.2 s of device
    time) with a prefill between them; kernels by name inside."""
    ops = ops if ops is not None else [
        ["%while.1 = while(...)", 1.0, 1.0],                   # a parent
        ["%gdn_step.3 = custom-call(...)", 1.0, 0.3],
        ["%moe_gmm_glu.5 = custom-call(...)", 1.3, 0.5],
        ["%paged_decode.1 = custom-call(...)", 1.8, 0.1],
        ["%gdn_scan.7 = custom-call(...)", 3.0, 0.4],          # the prefill's
        ["%fusion.11 = fusion(...)", 3.4, 0.6],
        ["%gdn_step.3 = custom-call(...)", 5.0, 0.5],
        ["%copy-done.4 = copy-done(...)", 5.1, 0.1],   # overlaps the kernel
        ["%moe_gmm_glu.5 = custom-call(...)", 5.5, 0.7]]
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
            "peaks": {"hbm_bytes_s": 819e9, "bf16_flops_s": 197e12},
            # [seconds, held experts touched a burst]: 8 steps of 8 layers
            "expert_bursts": [[101.5, 8 * 8 * 110], [105.5, 8 * 8 * 120]],
            # [start, end, real prompt tokens]: warm-up before the slice,
            # two prompts inside it, one too near its end, one after it
            "admits": admits if admits is not None else [
                [90.0, 90.1, 4096], [102.9, 103.0, 1300], [104.0, 104.1, 77],
                [109.7, 109.8, 500], [115.0, 115.1, 200]],
            "ticks": [{"t": 5.5, "dt": 2.0, "slots": slots, "live": 0,
                       "queue": 9},
                      {"t": 9.5, "dt": 2.0, "slots": slots, "live": 0,
                       "queue": 9},
                      {"t": 30.0, "dt": 2.0, "slots": 1, "live": 0,
                       "queue": 0}]}


def test_readers_on_a_hand_made_trace():
    obs = qwen_obs()
    busy = 0.3 + 0.5 + 0.1 + 0.4 + 0.6 + 0.5 + 0.1 + 0.7
    assert read("flood_gdn_dev_pct", obs) == pytest.approx(
        100.0 * 1.2 / busy)
    # 16 decode steps, 128 slots, 6 layers, the state read and written
    least = 16 * 128 * 6 * 2 * 2_097_152 / 819e9
    assert read("flood_gdn_step_roofline", obs) == pytest.approx(
        100.0 * least / 0.8)
    # the two prompts admitted inside the slice and 0.5 s before its end, at
    # their REAL lengths; a token's bytes (90,112 B: 110 ns) bound it, not
    # its operations (3.67 MFLOP: 18.6 ns); a state in and out a call
    a_token = max(90_112 / 819e9, 3_670_016 / 197e12)
    assert a_token == 90_112 / 819e9
    least = 6 * ((1300 + 77) * a_token + 2 * 2 * 2_097_152 / 819e9)
    assert read("flood_gdn_scan_roofline", obs) == pytest.approx(
        100.0 * least / 0.4)
    # the expert kernel's share through the accepted reader: 115 touched a
    # layer and step, 6.29 MB each
    least = 16 * 8 * 115 * 6_291_456 / 819e9
    assert read("flood_moe_glu_roofline", obs) == pytest.approx(
        100.0 * least / 1.2)
    # no `ssm_*` or `sel_*` op: the older mixers' readers stay silent
    assert read("flood_ssm_dev_pct", obs) is None
    assert read("flood_sel_dev_pct", obs) is None


def test_step_roofline_charges_the_decoding_slots_alone():
    assert read("flood_gdn_step_roofline", qwen_obs(slots=50)) \
        == pytest.approx(read("flood_gdn_step_roofline", qwen_obs())
                         * 50 / 128)


@pytest.mark.parametrize("metric", ["flood_gdn_dev_pct",
                                    "flood_gdn_step_roofline",
                                    "flood_gdn_scan_roofline"])
def test_a_program_without_the_kernels_gives_nothing_and_does_not_raise(
        metric):
    """The parent commit's trace (no such op), a run of another driver (no
    `admits`) and another family's configuration: None, never an
    exception."""
    plain = [["%fusion.9 = fusion(...)", 1.0, 0.5],
             ["%paged_decode.1 = custom-call(...)", 1.5, 0.5]]
    assert read(metric, qwen_obs(ops=plain)) is None
    assert read(metric, dict(qwen_obs(), trace=None)) is None
    for file in ("gpt2_small", "nemotron3_super_ep4", "jamba2_3b"):
        other = dict(qwen_obs(ops=plain),
                     config=perf_toy.load(f"perf/configs/{file}.json"))
        other.pop("admits")
        assert read(metric, other) is None
    if metric == "flood_gdn_scan_roofline":
        assert read(metric, qwen_obs(admits=[])) is None


# ------------------------------------------------------------------- a run
def toy_cell():
    cell = {"name": CELL, "config": "qwen3next_80b_ep4", "traffic": "toy",
            "chips": 1}
    traffic = copy.deepcopy(TRAFFIC)
    traffic["tenants"][0].update(rate_rps=40.0, prompt_len_median=10,
                                 prompt_len_cap=24, max_new_median=6,
                                 max_new_cap=12)
    traffic["engine"].update(max_slots=3, page=8, buckets=[8, 24], burst=4,
                             max_blocks_per_slot=5, num_blocks=16)
    traffic["check"].update(pad_to=40, requests=6)
    traffic["limits"] = dict(perf_toy.SERVE_LIMITS,
                             served_token_gap_p99=0.25)
    return cell, qwen3_next_toy.config(source=CFG["source"]), traffic


def test_a_seed_draws_its_own_toy_weights_and_the_same_each_time():
    """`perf/lib/weights_by_leaf.py` at the toy's shapes, in the serving
    type: the same `--seed` gives the same weights, another seed others, a
    norm's `scale` lies about 1 and every other leaf about 0. (The draw is
    one compiled program a shape, ~9 s for the toy's leaves the first time
    in a process: paid here, not inside the first run below.)"""
    from ddp_practice_tpu.models import create_model

    cfg = qwen3_next_toy.config()
    model = create_model(cfg["program_model"], **family.model_options(cfg))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    one, again, other = (
        jax.device_get(weights_by_leaf.make_params(
            shapes, seed, dtype=jnp.bfloat16))
        for seed in (3_000_000_019, 3_000_000_019, 41))
    paths = jax.tree_util.tree_leaves_with_path(one)
    for (path, x), y, z in zip(paths, jax.tree.leaves(again),
                               jax.tree.leaves(other)):
        assert x.dtype == jnp.bfloat16
        assert (x == y).all() and (x != z).any(), path
        want = 1.0 if str(path[-1].key) == "scale" else 0.0
        assert abs(float(x.astype(np.float32).mean()) - want) < 0.1, path


SOUND_SEED = 3_000_000_019


def toy_run(outroot, seed):
    """One run of the toy cell through `perf/run.py measure`, the normal
    path: (the result line, what the composed driver returned, the run's
    series)."""
    from perf.drivers import serve_by_leaf_admits_p99 as driver

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
    """The sound run, made once for the two tests that read it (a run is
    ~14 s of compiling the toy engine's programs)."""
    return toy_run(tmp_path_factory.mktemp("sound"), SOUND_SEED)


@pytest.mark.parametrize("broken", [False, True])
def test_toy_run_is_correct_unless_a_token_is_altered(broken, tmp_path,
                                                      monkeypatch, request):
    build, check = serve.build_engine, serve.reference_checks
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
    assert serve.build_engine is build and serve.reference_checks is check
    from perf.drivers import serve_by_leaf, serve_by_leaf_p99
    assert serve_by_leaf_p99.serve_by_leaf is serve_by_leaf
    # both numbers are compared, each beside its own limit
    by_name = {c["name"]: c for c in series["checks"]}
    assert set(by_name) == {"served_token_logit_gap_max",
                            "served_token_logit_gap_p99"}
    assert by_name["served_token_logit_gap_p99"]["ok"] is not broken
    # 4 steps x 4 expert layers x at most 12 picks (3 slots x top-4) a burst
    assert 0 < series["experts_touched_a_burst"] <= 4 * 4 * 12


def test_the_driver_notes_every_admission_and_compares_the_p99(sound_run):
    """`obs["admits"]` from the one part, the second check from the other:
    the composed driver hands both on."""
    _, result, _ = sound_run
    admits = result["obs"]["admits"]
    assert admits and all(a <= b and 1 <= n <= 24 for a, b, n in admits)
    assert [r["name"] for r in result["checks"].rows] == [
        "served_token_logit_gap_max", "served_token_logit_gap_p99"]
    assert result["obs"]["expert_bursts"]


def test_the_e4m3_control_reads_over_the_toy_limit(tmp_path):
    """What `perf/tools/calibrate.py` reads on the chip, at toy size: over a
    sequence the cell could have served, the tokens that the reference
    computed in e4m3 puts first lie further below the float32 reference's
    best than the limit a sound run meets (it reads 0: the toy run above)."""
    cell, config, traffic = toy_cell()
    ctx = harness.make_ctx(cell, config, traffic, seed=11, seconds=0.5,
                           trace=False, devices=jax.devices()[:1],
                           chip_peaks=perf_toy.PEAKS, outroot=str(tmp_path))
    _, params = qwen3_next_toy.model_and_params(config, seed=11)
    rng = np.random.default_rng(0)
    sample = [(rng.integers(0, 96, 12).tolist(),
               rng.integers(0, 96, 24).tolist())]
    with jax.default_matmul_precision("highest"):
        control = np.concatenate(
            serve.reference_gaps(ctx, params, sample, quant="fp8"))
    assert control.shape == (24,)
    assert control.max() > traffic["limits"]["served_token_gap"]
