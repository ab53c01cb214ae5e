"""The SmallThinker configuration's benchmark files: the configuration against
its source (every key of the catalog row; depth reduced and nothing else), the
family's bytes and operations, the readers on hand-made observations, the
reference against the program and against its own control, and one toy run of
the cell through the harness."""

import copy
import importlib
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perf_toy
import smallthinker_toy
from perf import run as harness
from perf.drivers import serve, serve_long_by_leaf, \
    serve_window_by_leaf as driver
from perf.families import smallthinker as family
from perf.lib import weights, weights_by_leaf
from perf.reference import smallthinker as reference

CFG = perf_toy.load("perf/configs/smallthinker_21b_pp7.json")
TRAFFIC = perf_toy.load("perf/traffic/short_long_s32.json")
CELL = "smallthinker_serve_shortlong"
LAYOUT = [0, 1, 1, 1] * 13
# the catalog row's `config` (model-configs guide, architectures.jsonl)
SOURCE = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936,
}


def read(metric, obs):
    return importlib.import_module(f"perf.layer_metrics.{metric}").read(obs)


# ----------------------------------------------------------- configuration
def test_every_key_of_the_source_is_kept_and_the_cut_is_stated():
    assert {k: CFG[k] for k in SOURCE} == SOURCE
    assert list(CFG["reduced"]) == ["layers_run"]
    assert CFG["layers_run"] == 8 and CFG["published"] == {}
    assert CFG["layers_published"] == list(range(8))
    assert family.mixers(CFG) == "*WWW*WWW"       # the published 1 : 3
    entry = next(c for c in perf_toy.manifest()["configs"]
                 if c["name"] == "smallthinker_21b_pp7")
    assert entry["reduced"] == ["layers_run"] \
        and entry["source"] == CFG["source"] \
        == "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct" \
           "/blob/main/config.json"
    assert {"router_input", "experts", "no_bias", "rotary", "window", "page",
            "weights"} == set(CFG["assumed"])
    assert "7 v5e chips" in CFG["deployment"] \
        and "3,966,937,600" in CFG["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):      # the row itself, where it is at hand
        row = next(json.loads(ln) for ln in open(catalog)
                   if '"SmallThinker-21BA3B-Instruct"' in ln)
        assert row["config"] == SOURCE and row["source_url"] == CFG["source"]


def test_family_reads_the_layers_bytes_and_operations_from_the_keys():
    assert family.counts(CFG) == {"*": 2, "W": 6, "R": 8}
    opts = family.model_options(CFG)
    assert opts["pattern"] == "*RWRWRWR" * 2
    assert (opts["num_heads"], opts["kv_heads"], opts["head_dim"],
            opts["hidden_dim"], opts["vocab_size"], opts["window"]) \
        == (28, 4, 128, 2560, 151936, 4096)
    assert (opts["num_experts"], opts["experts_held"], opts["top_k"],
            opts["expert_dim"]) == (64, 64, 6, 768)
    assert opts["rope_theta"] == 1.5e6 and opts["max_len"] == 16384
    # a cached token in the GLOBAL layers alone: 2 x (K + V) x 4 heads x
    # 128 x 2 B; every layer's would read `paged_decode` past its roofline
    assert family.decode_bytes(CFG) == (4096, 2 * 28 * 128 * 2 * 2)
    assert family.window_q_and_out_bytes(CFG) == 2 * 28 * 128 * 2 * 6
    assert family.walk_page_bytes(CFG, 64) == 32_768
    assert family.expert_bytes(CFG) == 11_796_480
    assert family.param_count(CFG) == 3_966_937_600
    # a row attends min(t + 1, 4096) keys in 6 layers and t + 1 in 2
    assert family.keys_attended(CFG, 0, 3) == 8 * (1 + 2 + 3)
    assert family.keys_attended(CFG, 4095, 2) \
        == 6 * (4096 + 4096) + 2 * (4096 + 4097)
    assert family.prefill_flops(CFG, 0, 1) == 4 * 28 * 128 * 8
    # q and out of 8 layers once, K and V: the context in 2, the window
    # behind the chunk and the chunk in 6
    assert family.prefill_bytes(CFG, 8192, 2048) == 2 * 128 * (
        2 * 2048 * 28 * 8 + 2 * 4 * (2 * 10240 + 6 * (4095 + 2048)))
    with pytest.raises(ValueError, match="this file asks for another"):
        family.model_options(dict(CFG, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="rotates in the window layers"):
        family.mixers(dict(CFG, rope_layout=[1] * 52))


def test_the_param_recount_is_the_programs_own_count():
    from ddp_practice_tpu.models import create_model

    model = create_model(CFG["program_model"], **family.model_options(CFG))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == family.param_count(CFG) == 3_966_937_600


def test_the_cell_and_its_metrics_are_appended_and_listed():
    m = perf_toy.manifest()
    names = [w["name"] for w in m["workloads"]]
    cell = m["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("smallthinker_21b_pp7", "short_long_s32", 1)
    assert names.index(CELL) > names.index("minicpm_sala_serve_long")
    assert len(names) == 10 and sum(w["chips"] == 4
                                    for w in m["workloads"]) == 1
    listed = {e["name"] for e in m["per_layer"]
              if CELL in e.get("workloads", [])}
    assert listed == {
        "flood_attn_dev_pct", "flood_mlp_dev_pct",
        "flood_head_sample_dev_pct", "flood_unscoped_dev_pct",
        "flood_moe_glu_dev_pct", "flood_moe_glu_roofline",
        "flood_window_dev_pct", "flood_window_walk_roofline",
        "flood_window_prefill_roofline"}
    # `flood_paged_decode_roofline` has the global layers' walk to read here
    # but does not list the cell: `test_perf_minicpm_sala.py` pins its list
    # to the five cells it had (PERF.md section 7)
    assert CELL not in next(e for e in m["per_layer"] if e["name"]
                            == "flood_paged_decode_roofline")["workloads"]
    # the three new readers list the new cell alone, at the lists' end
    assert [e["name"] for e in m["per_layer"][-3:]] == [
        "flood_window_dev_pct", "flood_window_walk_roofline",
        "flood_window_prefill_roofline"]
    assert all(e["workloads"] == [CELL] for e in m["per_layer"][-3:])
    for e in m["per_layer"] + m["end_to_end"]:
        if CELL in e.get("workloads", []):
            assert e["workloads"][-1] == CELL, e["name"]
    assert CELL in next(e for e in m["end_to_end"]
                        if e["name"] == "serve_tok_s")["workloads"]


def test_the_traffic_file_states_what_the_issue_asked_for():
    t, e = TRAFFIC["tenants"][0], TRAFFIC["engine"]
    assert len(TRAFFIC["tenants"]) == 1 and t["arrivals"] == "poisson"
    assert (t["prompt_len_median"], t["prompt_len_sigma"],
            t["prompt_len_cap"]) == (4096, 1.0, 14336)
    assert t["prompt_len_median"] == CFG["sliding_window_size"]
    assert (t["max_new_median"], t["max_new_sigma"], t["max_new_cap"]) \
        == (256, 0.6, 1024)
    assert (e["max_slots"], e["page"], e["burst"], e["buckets"],
            e["prefill_chunk"]) == (32, 64, 8, [256, 512, 1024, 2048], 2048)
    assert e["prefill_chunks_per_tick"] == 4 and "chunks_why" in TRAFFIC
    assert e["max_blocks_per_slot"] * e["page"] == 14336 + 1024 \
        <= CFG["max_position_embeddings"]
    # both groups all backed: 240 pages a slot, and 97 in the window group
    assert e["num_blocks"] == 1 + 32 * 240
    assert e["window_blocks"] == 1 + 32 * 97
    assert -(-(CFG["sliding_window_size"] + e["prefill_chunk"])
             // e["page"]) + 1 == 97
    assert TRAFFIC["drain_limit_s"] == 0 and TRAFFIC["driver"] \
        == "serve_window_by_leaf" and TRAFFIC["shape_seed"] == 44
    assert set(TRAFFIC["limits"]) == {"served_token_gap",
                                      "served_token_gap_past_bf16"}
    assert TRAFFIC["check"]["requests"] == 3 \
        and TRAFFIC["check"]["served_rows"] == t["max_new_cap"]


# ----------------------------------------------------------------- readers
def slice_obs(events, modules=(), **kw):
    """Observations of a traced slice [0, 10] with chip 0's ops `events`."""
    return dict({
        "kind": "serve", "traced": (100.0, 110.0), "t_origin": 100.0,
        "burst": 8, "page": 64, "config": CFG,
        "peaks": {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9},
        "trace": {"planes": [
            {"name": "/host:CPU", "lines": [{"name": "main", "events": [
                ["perf:traced", 0.0, 10.0]]}]},
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": events},
                {"name": "XLA Modules", "events": list(modules)}]}]},
    }, **kw)


def test_walk_roofline_charges_the_pages_the_program_counted():
    # two bursts in the slice walked 6,000 and 10,000 pages (the program's
    # sums over slots, window layers and steps; a page there is every KV
    # head's); 2 runs of 8 steps at 16 decoding slots; 1 ms a run
    obs = slice_obs(
        [["%window_walk.1 = custom-call", 1.0, 1e-3],
         ["%window_walk.1 = custom-call", 3.0, 1e-3],
         ["%window_walk.1 = custom-call", 11.0, 1e-3],    # past the slice
         ["%paged_decode.1 = custom-call", 1.1, 5e-3]],   # another op
        modules=[["jit__decode_burst(1)", 0.9, 0.5],
                 ["jit__decode_burst(1)", 2.9, 0.5]],
        ticks=[{"t": 0.9, "dt": 0.5, "slots": 16},
               {"t": 2.9, "dt": 0.5, "slots": 16}],
        window_bursts=[[101.0, 6000, 20000], [103.0, 10000, 30000],
                       [120.0, 1, 1]])
    least = (2 * 8000 * 4 * 32768 + 16 * 16 * 2 * 28 * 128 * 2 * 6) / 819e9
    assert read("flood_window_walk_roofline", obs) \
        == pytest.approx(100 * least / 2e-3)
    assert read("flood_window_walk_roofline",
                dict(obs, window_bursts=[])) is None
    # a program before the window group leaves no such record and no such op
    bare = dict(obs)
    del bare["window_bursts"]
    assert read("flood_window_walk_roofline", bare) is None
    assert read("flood_window_walk_roofline", dict(obs, trace=None)) is None


def test_prefill_roofline_charges_real_tokens_the_keys_of_the_rule():
    # one whole chunk at 0 and 100 real tokens at 12,288, both dispatched
    # in the slice; a third too late to have run inside it
    obs = slice_obs(
        [["%window_prefill.2 = custom-call", 2.0, 4e-3],
         ["%window_prefill.3 = custom-call", 2.1, 2e-3]],
        chunks=[[101.0, 101.2, 0, 2048], [104.0, 104.1, 12288, 100],
                [109.8, 109.9, 0, 2048]])
    flops = 4 * 28 * 128 * (8 * 2048 * 2049 // 2 + family.keys_attended(
        CFG, 12288, 100))
    assert family.keys_attended(CFG, 12288, 100) \
        == 6 * 100 * 4096 + 2 * sum(range(12289, 12389))
    assert read("flood_window_prefill_roofline", obs) \
        == pytest.approx(100 * flops / 197e12 / 6e-3)
    assert read("flood_window_prefill_roofline", dict(obs, chunks=[])) is None
    assert read("flood_window_prefill_roofline", slice_obs(
        [["%fusion.1 = fusion", 2.0, 1e-3]],
        chunks=[[101.0, 101.2, 0, 2048]])) is None


def test_window_share_counts_both_kernels_by_name():
    at = lambda path: f'f32[] fusion(), metadata={{op_name="{path}"}}'
    call = lambda name, path: f"%{name}.1 = custom-call(), metadata=" \
        f'{{op_name="jit(_decode_burst)/{path}/{name}"}}'
    obs = slice_obs([
        [call("window_walk", "attn2"), 1.0, 1.0],
        [call("window_prefill", "attn0"), 2.0, 0.5],
        [call("paged_decode", "attn0"), 3.0, 0.5],
        ["%fusion.8 = " + at("jit(_decode_burst)/moe1/moe_route/x"), 4.0,
         2.0]])
    assert read("flood_window_dev_pct", obs) == pytest.approx(100 * 1.5 / 4)
    assert read("flood_window_dev_pct", slice_obs(
        [["%fusion.8 = " + at("jit(x)/moe1/gate"), 4.0, 2.5]])) is None


def test_the_shared_readers_read_this_family():
    """`flood_moe_glu_roofline` takes an expert's bytes from the family
    (11,796,480 here); `flood_paged_decode_roofline` would read the global
    layers' 4,096 B a live token through `obs["decode_bytes"]` (the cell is
    not on its list: PERF.md section 7)."""
    obs = slice_obs(
        [["%moe_gmm_glu.3 = custom-call", 1.0, 1e-3],
         ["%paged_decode.4 = custom-call", 1.2, 1e-4]],
        modules=[["jit__decode_burst(1)", 0.9, 0.5]],
        ticks=[{"t": 0.9, "dt": 0.5, "slots": 20, "live": 100_000}],
        expert_bursts=[[101.0, 8 * 8 * 50]],
        decode_bytes=family.decode_bytes(CFG))
    assert read("flood_moe_glu_roofline", obs) == pytest.approx(
        100 * 8 * 8 * 50 * 11_796_480 / 819e9 / 1e-3)
    live = 8 * 100_000 + 20 * 8 * 9 / 2
    assert read("flood_paged_decode_roofline", obs) == pytest.approx(
        100 * (live * 4096 + 8 * 20 * 28672) / 819e9 / 1e-4)


# --------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def toy():
    return smallthinker_toy.model_and_params(smallthinker_toy.config())


def test_reference_and_program_agree_and_the_slice_is_the_whole(toy):
    """40 tokens: five windows of 8, so every window layer masks."""
    model, params = toy
    cfg = smallthinker_toy.config()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
        want = np.asarray(jax.jit(
            lambda p, t: reference.forward(p, t, cfg))(params, tokens))
        part = np.asarray(jax.jit(lambda p, t, at: reference.forward(
            p, t, cfg, at=(at, 9)))(params, tokens, 20))
        low = np.asarray(jax.jit(
            lambda p, t: reference.forward(p, t, cfg, "bf16"))(params, tokens))
    # float32 against float32 in another order of sums
    assert np.abs(got - want).max() < 1e-4
    assert np.abs(part - want[:, 20:29]).max() < 1e-6
    # bf16 for float32 is far outside that: a flipped pick moves a logit
    assert np.abs(low - want).max() > 1e-2


def test_blocked_pieces_equal_the_whole(toy, monkeypatch):
    """`by_rows` and the experts' rows at 16 positions a piece, 8 queries a
    step: the pieces a 15,360-token request is read in change no number."""
    _, params = toy
    cfg = smallthinker_toy.config()
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 64), 0, 96)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(reference.forward(params, tokens, cfg))
        monkeypatch.setattr(reference, "ROWS", 16)
        monkeypatch.setattr(reference, "QUERIES", 8)
        monkeypatch.setattr(
            reference, "by_rows",
            lambda fn, x, rows=16, real=reference.by_rows: real(fn, x, rows))
        pieces = np.asarray(reference.forward(params, tokens, cfg))
    assert np.abs(whole - pieces).max() < 1e-5


def test_the_references_experts_are_a_loop_over_a_tokens_picks(toy):
    """`experts` runs every expert on every token under the router's
    weights; a token at a time over its 2 picks alone is the same."""
    _, params = toy
    cfg = smallthinker_toy.config()
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.normal(size=(1, 6, 48)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(1, 6, 48)), jnp.float32)
    p = params["moe1"]
    with jax.default_matmul_precision("highest"):
        w = np.asarray(reference.route(a, p, cfg))
        got = np.asarray(reference.experts(m, jnp.asarray(w), p))
    assert ((w > 0).sum(-1) == 2).all() \
        and np.allclose(w.sum(-1), 1.0, atol=1e-6)
    logits = np.asarray(a)[0] @ np.asarray(p["router"]["kernel"])
    for t in range(6):
        picks = np.argsort(-logits[t])[:2]
        assert set(picks) == set(np.flatnonzero(w[0, t]))
        soft = np.exp(logits[t][picks] - logits[t][picks].max())
        want = np.zeros(48)
        for e, g in zip(picks, soft / soft.sum()):
            x = np.asarray(m)[0, t]
            hid = np.maximum(x @ np.asarray(p["expert_gate"][e]), 0) \
                * (x @ np.asarray(p["expert_up"][e]))
            want += g * (hid @ np.asarray(p["expert_down"][e]))
        assert np.abs(got[0, t] - want).max() < 1e-4


# ---------------------------------------------------------------- toy run
def toy_cell():
    cell = {"name": CELL, "config": "smallthinker_21b_pp7",
            "traffic": "short_long_s32", "chips": 1}
    traffic = copy.deepcopy(TRAFFIC)
    traffic["tenants"][0].update(rate_rps=30.0, prompt_len_median=12,
                                 prompt_len_cap=40, max_new_median=6,
                                 max_new_cap=12)
    traffic["engine"].update(max_slots=3, page=4, buckets=[4, 8], burst=4,
                             prefill_chunk=8, max_blocks_per_slot=14,
                             num_blocks=43, window_blocks=16)
    # one padded width for every request: one compile of the reference
    traffic["check"].update(pad_rows=64, served_rows=12)
    # the benchmark's own 0.02-normal weights leave a toy's logits within
    # 0.3 of each other: a sound run reads under 0.001, an altered token 0.1
    traffic["limits"] = {"served_token_gap": 0.02,
                         "served_token_gap_past_bf16": 0.02}
    # one period (G W W W) is every kind of layer: half the toy's compiles
    return cell, smallthinker_toy.config(
        source=CFG["source"], layers_run=4,
        layers_published=list(range(4))), traffic


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
               serve.reference_gaps, serve.pick_sample)
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
                       serve.reference_gaps, serve.pick_sample)
    by_name = {c["name"]: c for c in series["checks"]}
    assert set(by_name) == {"served_token_logit_gap_max",
                            "served_token_logit_gap_past_bf16"}
    assert by_name["served_token_logit_gap_max"]["ok"] is not broken


def test_the_driver_notes_chunks_experts_and_what_the_windows_read(
        sound_run):
    _, result, series = sound_run
    obs = result["obs"]
    # every prompt is chunk-admitted: bookkeeping in `admits`, the work here
    assert obs["admits"] and obs["chunks"] and obs["page"] == 4
    assert all(a <= b and first % 8 == 0 and 1 <= n <= 8
               for a, b, first, n in obs["chunks"])
    assert obs["window_bursts"] and obs["expert_bursts"]
    for _, near, whole in obs["window_bursts"]:
        assert 0 < near <= whole
    # contexts pass the toy's window of 8: some walks start mid-table
    assert 0 < series["window_pages_walked_share"] < 1
    # a slot's pages by group: the window group inside its bound, 5 here
    # (window 8 + chunk 8 over pages of 4, and one), with pages given back
    assert series["pages_a_slot_max"]["window"] <= 5 \
        < series["pages_a_slot_max"]["global"] <= 14
    assert series["window_pages_freed"] > 0
    assert series["experts_touched_a_burst"] > 0


def test_the_sample_is_the_longest_one_inside_and_one_that_crossed():
    class Done:
        def __init__(self, rid, n):
            self.rid, self.tokens = rid, [1] * n

    by_rid = {i: {"prompt": [0] * p} for i, p in enumerate(
        [3, 10, 20, 40, 70])}
    ok = [Done(i, 5) for i in by_rid]
    pick = driver.three_contexts(8, 16)
    sizes = [len(p) + len(s) for p, s in pick(ok, by_rid, 7, 3, [4, 8])]
    assert sizes[0] == 75 and sizes[1] == 8 and 16 < sizes[2] < 75
    assert len(pick(ok[1:3], by_rid, 7, 3, [4, 8])) == 1   # 25 alone


def test_the_check_is_the_maximum_and_the_mean_past_what_bf16_loses(
        monkeypatch):
    """The reference's gaps come from the long driver (read in blocks); the
    second number is their mean over the positions where the reference in
    bf16 picks the float32 reference's best: a near-tie that a loop of the
    answer repeats, and that bf16 arithmetic flips too, is left out."""
    assert driver.serve_long_by_leaf is serve_long_by_leaf
    served = np.array([0.0, 0.2, 0.2, 0.2, 0.0, 0.03, 0.0, 0.0], np.float32)
    in_bf16 = np.array([0.0, 0.2, 0.2, 0.2, 0.0, 0.0, 0.0, 0.01], np.float32)
    assert driver.mean_gap_past_bf16(served, in_bf16) \
        == pytest.approx(0.03 / 4)
    assert driver.mean_gap_past_bf16(served, served + 1) == float("inf")
    asked = []

    def gaps(ctx, params, sample, quant=None):
        asked.append(quant)
        return [in_bf16[:5], in_bf16[5:]] if quant else [served[:5],
                                                         served[5:]]

    monkeypatch.setattr(serve_long_by_leaf, "reference_gaps", gaps)
    ctx = types.SimpleNamespace(traffic={"limits": {
        "served_token_gap": 0.25, "served_token_gap_past_bf16": 0.007}})
    sample = [([1] * 5, [2] * 5), ([1] * 2, [2] * 3)]
    checks = driver.reference_checks(ctx, None, sample)
    assert asked == [None, "bf16"]
    rows = {r["name"]: r for r in checks.rows}
    assert rows["served_token_logit_gap_max"]["value"] \
        == pytest.approx(0.2) and rows["served_token_logit_gap_max"]["ok"]
    past = rows["served_token_logit_gap_past_bf16"]
    assert past["value"] == pytest.approx(0.0075) and not past["ok"]
    assert not checks.correct and "mean over 4 positions" in past["note"]
    assert not driver.reference_checks(ctx, None, []).correct


def test_the_e4m3_control_reads_over_a_sound_run(tmp_path):
    """What the calibration reads on the chip, at toy size, through the
    driver's blocked `reference_gaps`: over sequences the cell could have
    served, the reference's own best tokens read 0 and the tokens that the
    reference computed in e4m3 puts first lie below them somewhere."""
    cell, config, traffic = toy_cell()
    ctx = harness.make_ctx(cell, config, traffic, seed=11, seconds=0.5,
                           trace=False, devices=jax.devices()[:1],
                           chip_peaks=perf_toy.PEAKS, outroot=str(tmp_path))
    _, params = smallthinker_toy.model_and_params(config, seed=11)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, 30).tolist() for _ in range(4)]
    with jax.default_matmul_precision("highest"):
        first = jax.jit(lambda t: reference.forward(
            params, t, config)[0, 29:41].argmax(-1))
        best = [np.asarray(first(jnp.asarray([p + [0] * 10])))
                for p in prompts]
        sample = [(p, [int(b[0])]) for p, b in zip(prompts, best)]
        sound = np.concatenate(
            serve_long_by_leaf.reference_gaps(ctx, params, sample))
        control = np.concatenate(serve_long_by_leaf.reference_gaps(
            ctx, params, [(p, [0] * 12) for p in prompts], quant="fp8"))
    assert sound.shape == (4,) and sound.max() == 0
    assert control.shape == (48,) and control.max() > 0
