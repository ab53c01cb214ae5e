"""The MiniCPM-SALA configuration's benchmark files: the configuration against
its source (every key of the catalog row; depth reduced and nothing else), the
family's bytes and operations, the readers on hand-made observations, the
reference against the program and against its own control, and one toy run of
the cell through the harness."""

import copy
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import minicpm_sala_toy
import perf_toy
from perf import run as harness
from perf.drivers import serve, serve_long_by_leaf as driver
from perf.families import minicpm_sala as family
from perf.lib import weights, weights_by_leaf
from perf.reference import minicpm_sala as reference

CFG = perf_toy.load("perf/configs/minicpm_sala_9b_pp4.json")
TRAFFIC = perf_toy.load("perf/traffic/long_docs_s32.json")
CELL = "minicpm_sala_serve_long"
L, S = "lightning-attn", "minicpm4"
# the catalog row's `config` (model-configs guide, architectures.jsonl)
SOURCE = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "mixer_types": [S] + [L] * 8 + [S] + [L] * 6 + [S] * 2 + [L] * 4 + [S]
    + [L] * 6 + [S] * 3,
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True,
}


def read(metric, obs):
    return importlib.import_module(f"perf.layer_metrics.{metric}").read(obs)


# ----------------------------------------------------------- configuration
def test_every_key_of_the_source_is_kept_and_the_cut_is_stated():
    assert {k: CFG[k] for k in SOURCE} == SOURCE
    assert list(CFG["reduced"]) == ["layers_run"]
    assert CFG["layers_run"] == 8 and CFG["published"] == {}
    assert CFG["layers_published"] == list(range(9, 17))
    assert family.mixers(CFG) == "BLLLLLLB"       # the published 1 : 3
    entry = next(c for c in perf_toy.manifest()["configs"]
                 if c["name"] == "minicpm_sala_9b_pp4")
    assert entry["reduced"] == ["layers_run"] \
        and entry["source"] == CFG["source"] \
        == "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/" \
           "config.json"
    assert {"stream", "lightning_attn", "minicpm4", "ssm_state_dtype",
            "weights"} == set(CFG["assumed"])
    assert CFG["sparse"] == {
        "block": 64, "kernel": 32, "stride": 16, "init_blocks": 1,
        "window": 2048, "dense_len": 8192, "topk": 64}
    assert "4 v5e chips" in CFG["deployment"] \
        and "2,820,545,280" in CFG["deployment"]


def test_family_reads_the_layers_bytes_and_operations_from_the_keys():
    assert family.counts(CFG) == {"M": 6, "B": 2, "D": 8}
    opts = family.model_options(CFG)
    assert opts["pattern"] == "BD" + "LD" * 6 + "BD"
    assert opts["lightning_layers"] == (10, 11, 12, 13, 14, 15)
    assert opts["decay_layers"] == 32
    assert (opts["num_heads"], opts["kv_heads"], opts["head_dim"],
            opts["mlp_dim"], opts["vocab_size"]) == (32, 2, 128, 16384, 73448)
    assert opts["embed_scale"] == 12.0 and opts["head_scale"] == 1 / 16
    assert opts["residual_scale"] == pytest.approx(1.4 / 32 ** 0.5)
    assert tuple(opts["sparse"]) == (64, 32, 16, 1, 2048, 8192, 64)
    # a cached token: 2 layers x (K + V) x 2 heads x 128 x 2 B, and its
    # share of the compressed keys: 2 layers x 2 heads x 256 B / 16
    assert family.decode_bytes(CFG) == (2048, 2 * 32 * 128 * 2 * 2)
    assert family.index_bytes_per_token(CFG) == 64
    assert family.ssm_state_bytes(CFG) == 2_097_152
    assert family.walk_page_bytes(CFG) == 32_768
    # the recount from shapes: 2 x 253.8 M + 6 x 285.2 M + 8 MLPs' share
    # inside them + embedding and head 2 x 300.8 M
    assert family.param_count(CFG) == 2_820_545_280
    assert 4.9e9 < family.decode_flops_per_token(CFG) < 5.2e9
    # dense rows attend all they see, sparse rows 63 whole blocks and their
    # own page's head
    assert family.keys_attended(CFG, 0, 4) == 1 + 2 + 3 + 4
    assert family.keys_attended(CFG, 8191, 2) == 8192 + 63 * 64 + 1
    assert family.prefill_flops(CFG, 0, 1) == 4 * 32 * 128
    with pytest.raises(ValueError, match="this file asks for another"):
        family.model_options(dict(CFG, attn_use_rope=True))


def test_the_param_recount_is_the_programs_own_count():
    from ddp_practice_tpu.models import create_model

    model = create_model(CFG["program_model"], **family.model_options(CFG))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == family.param_count(CFG)


def test_the_cell_and_its_metrics_are_appended_and_listed():
    m = perf_toy.manifest()
    names = [w["name"] for w in m["workloads"]]
    cell = m["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("minicpm_sala_9b_pp4", "long_docs_s32", 1)
    assert names.index(CELL) > names.index("qwen3next_serve_mixed")
    listed = {e["name"] for e in m["per_layer"]
              if CELL in e.get("workloads", [])}
    assert listed == {
        "flood_attn_dev_pct", "flood_mlp_dev_pct", "flood_mixer_dev_pct",
        "flood_head_sample_dev_pct", "flood_unscoped_dev_pct",
        "flood_ssm_dev_pct", "flood_ssm_step_roofline",
        "flood_sparse_dev_pct", "flood_sparse_walk_roofline",
        "flood_sparse_prefill_roofline"}
    # no op of this model's programs is named `paged_decode`: that share
    # lists the five cells that run the kernel and nothing else of it moved
    paged = next(e for e in m["per_layer"]
                 if e["name"] == "flood_paged_decode_roofline")
    assert paged["workloads"] == [
        "gpt2s_serve_flood", "nemo3s_serve_flood", "kanana2_serve_docs",
        "jamba2_serve_batch", "qwen3next_serve_mixed"]
    assert CELL in next(e for e in m["end_to_end"]
                        if e["name"] == "serve_tok_s")["workloads"]


def test_the_traffic_file_states_what_the_issue_asked_for():
    t, e = TRAFFIC["tenants"][0], TRAFFIC["engine"]
    assert len(TRAFFIC["tenants"]) == 1 and t["arrivals"] == "poisson"
    assert (t["prompt_len_median"], t["prompt_len_sigma"],
            t["prompt_len_cap"]) == (12288, 0.6, 32768)
    assert (t["max_new_median"], t["max_new_sigma"], t["max_new_cap"]) \
        == (384, 0.6, 1536)
    assert (e["max_slots"], e["page"], e["burst"], e["buckets"],
            e["prefill_chunk"]) == (32, 64, 8, [256, 512, 1024, 2048], 2048)
    assert e["prefill_chunks_per_tick"] == 4 and "chunks_why" in TRAFFIC
    assert e["max_blocks_per_slot"] * e["page"] == 32768 + 1536
    assert e["num_blocks"] == 1 + 32 * e["max_blocks_per_slot"]
    assert e["page"] == CFG["sparse"]["block"]
    assert TRAFFIC["drain_limit_s"] == 0 and TRAFFIC["driver"] \
        == "serve_long_by_leaf"
    assert TRAFFIC["slo"]["ttft_ms"] == 6000 \
        and TRAFFIC["slo"]["tpot_ms"] == 80
    assert set(TRAFFIC["limits"]) == {"served_token_gap",
                                      "served_token_gap_p99"}


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


def test_walk_roofline_charges_the_pages_the_program_counted():
    # two bursts in the slice walked 4,096 and 8,192 pages (the program's
    # sums over slots, KV heads, layers and steps); 2 runs of 8 steps at 16
    # decoding slots; the kernel took 1 ms in each run
    obs = slice_obs(
        [["%sparse_walk.1 = custom-call", 1.0, 1e-3],
         ["%sparse_walk.1 = custom-call", 3.0, 1e-3],
         ["%sparse_walk.1 = custom-call", 11.0, 1e-3]],   # past the slice
        modules=[["jit__decode_burst(1)", 0.9, 0.5],
                 ["jit__decode_burst(1)", 2.9, 0.5]],
        ticks=[{"t": 0.9, "dt": 0.5, "slots": 16},
               {"t": 2.9, "dt": 0.5, "slots": 16}],
        sparse_bursts=[[101.0, 4096, 20000, 9], [103.0, 8192, 30000, 12],
                       [120.0, 1, 1, 1]])
    least = (2 * 6144 * 32768 + 16 * 16 * 32768) / 819e9
    assert read("flood_sparse_walk_roofline", obs) \
        == pytest.approx(100 * least / 2e-3)
    assert read("flood_sparse_walk_roofline",
                dict(obs, sparse_bursts=[])) is None
    assert read("flood_sparse_walk_roofline", dict(obs, trace=None)) is None


def test_prefill_roofline_charges_real_tokens_their_picked_keys():
    # one whole dense chunk at 0 and 100 real tokens past dense_len, both
    # dispatched in the slice; a third too late to have run inside it
    obs = slice_obs(
        [["%sparse_prefill.2 = custom-call", 2.0, 4e-3]],
        chunks=[[101.0, 101.2, 0, 2048], [104.0, 104.1, 12288, 100],
                [109.8, 109.9, 0, 2048]])
    flops = 4 * 32 * 128 * (2048 * 2049 // 2 + family.keys_attended(
        CFG, 12288, 100))
    assert read("flood_sparse_prefill_roofline", obs) \
        == pytest.approx(100 * 2 * flops / 197e12 / 4e-3)
    assert read("flood_sparse_prefill_roofline", dict(obs, chunks=[])) is None


def test_sparse_share_counts_kernels_by_name_and_the_selection_by_scope():
    at = lambda path: f'f32[] fusion(), metadata={{op_name="{path}"}}'
    walk = "%sparse_walk.1 = custom-call(), metadata={op_name=" \
        '"jit(_decode_burst)/attn0/sparse_walk"}'
    obs = slice_obs([
        [walk, 1.0, 1.0],
        ["%fusion.7 = " + at("jit(_decode_burst)/attn0/sparse_select/top_k"),
         3.0, 0.5],
        ["%fusion.8 = " + at("jit(_decode_burst)/mlp1/gate"), 4.0, 2.5]])
    assert read("flood_sparse_dev_pct", obs) == pytest.approx(100 * 1.5 / 4)
    assert read("flood_sparse_dev_pct", slice_obs(
        [["%fusion.8 = " + at("jit(x)/mlp1/gate"), 4.0, 2.5]])) is None


def test_ssm_step_roofline_reads_the_lightning_layers_from_the_family():
    obs = slice_obs(
        [["%ssm_step.3 = custom-call", 1.0, 1e-3]],
        modules=[["jit__decode_burst(1)", 0.9, 0.5]],
        ticks=[{"t": 0.9, "dt": 0.5, "slots": 20}])
    least = 8 * 20 * 6 * 2 * 2_097_152 / 819e9
    assert read("flood_ssm_step_roofline", obs) \
        == pytest.approx(100 * least / 1e-3)


# --------------------------------------------------------------- reference
@pytest.fixture(scope="module")
def toy():
    return minicpm_sala_toy.model_and_params(minicpm_sala_toy.config())


def test_reference_and_program_agree_and_the_slice_is_the_whole(toy):
    """72 tokens: past the toy's dense_len 32, so the selection is live."""
    model, params = toy
    cfg = minicpm_sala_toy.config()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 72), 0, 96)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)({"params": params}, tokens))
        want = np.asarray(jax.jit(
            lambda p, t: reference.forward(p, t, cfg))(params, tokens))
        part = np.asarray(jax.jit(lambda p, t, at: reference.forward(
            p, t, cfg, at=(at, 9)))(params, tokens, 50))
        low = np.asarray(jax.jit(
            lambda p, t: reference.forward(p, t, cfg, "bf16"))(params, tokens))
    # float32 against float32 in another order of sums
    assert np.abs(got - want).max() < 2e-5
    assert np.abs(part - want[:, 50:59]).max() < 1e-6
    # bf16 for float32 is far outside that: a flipped pick moves a logit
    assert np.abs(low - want).max() > 1e-2


def test_blocked_pieces_equal_the_whole(toy, monkeypatch):
    """`by_rows` at 16 positions a piece and 8 queries a step: the pieces
    a 34,304-token request is read in change no number."""
    _, params = toy
    cfg = minicpm_sala_toy.config()
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


# ---------------------------------------------------------------- toy run
def toy_cell():
    cell = {"name": CELL, "config": "minicpm_sala_9b_pp4",
            "traffic": "long_docs_s32", "chips": 1}
    traffic = copy.deepcopy(TRAFFIC)
    traffic["tenants"][0].update(rate_rps=30.0, prompt_len_median=40,
                                 prompt_len_cap=72, max_new_median=6,
                                 max_new_cap=12)
    traffic["engine"].update(max_slots=3, page=8, buckets=[8, 16], burst=4,
                             prefill_chunk=16, max_blocks_per_slot=12,
                             num_blocks=40)
    traffic["check"].update(pad_rows=16, served_rows=12)
    # the benchmark's own 0.02-normal weights leave a toy's logits within
    # 0.3 of each other: a sound run reads 0, an altered token 0.18
    traffic["limits"] = {"served_token_gap": 0.02,
                         "served_token_gap_p99": 0.02}
    return cell, minicpm_sala_toy.config(source=CFG["source"]), traffic


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
                            "served_token_logit_gap_p99"}
    assert by_name["served_token_logit_gap_max"]["ok"] is not broken


def test_the_driver_notes_chunks_and_what_the_sparse_layers_read(sound_run):
    _, result, series = sound_run
    obs = result["obs"]
    # every prompt is chunk-admitted: bookkeeping in `admits`, the work here
    assert obs["admits"] and obs["chunks"]
    assert all(a <= b and first % 16 == 0 and 1 <= n <= 16
               for a, b, first, n in obs["chunks"])
    assert sum(n for *_, n in obs["chunks"]) \
        >= sum(n for _, _, n in obs["admits"][:1])
    assert obs["sparse_bursts"]
    for _, walked, held, slots in obs["sparse_bursts"]:
        assert 0 < walked <= held and 0 <= slots <= 3
    # contexts pass the toy's dense_len 32: some walks read fewer pages
    assert 0 < series["sparse_pages_walked_share"] < 1


def test_the_sample_is_the_longest_one_dense_and_one_between():
    class Done:
        def __init__(self, rid, n):
            self.rid, self.tokens = rid, [1] * n

    by_rid = {i: {"prompt": [0] * p} for i, p in enumerate(
        [10, 20, 40, 60, 70])}
    ok = [Done(i, 5) for i in by_rid]
    picked = serve_long_pick(ok, by_rid)
    sizes = [len(p) + len(s) for p, s in picked]
    assert sizes[0] == 75 and sizes[1] <= 32 < sizes[2] < 75
    assert len(serve_long_pick(ok[:2], by_rid)) == 2   # nothing between


def serve_long_pick(ok, by_rid):
    return driver.three_contexts(32)(ok, by_rid, 7, 3, [8, 16])


def test_the_e4m3_control_reads_over_a_sound_run(tmp_path):
    """What the calibration reads on the chip, at toy size, through the
    driver's own blocked `reference_gaps`: over sequences the cell could have
    served, the reference's own best tokens read 0 and the tokens that the
    reference computed in e4m3 puts first lie below them somewhere."""
    cell, config, traffic = toy_cell()
    ctx = harness.make_ctx(cell, config, traffic, seed=11, seconds=0.5,
                           trace=False, devices=jax.devices()[:1],
                           chip_peaks=perf_toy.PEAKS, outroot=str(tmp_path))
    _, params = minicpm_sala_toy.model_and_params(config, seed=11)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, 50).tolist() for _ in range(4)]
    with jax.default_matmul_precision("highest"):
        best = [np.asarray(reference.forward(
            params, jnp.asarray([p + [0] * 14]), config)[0, 49:61].argmax(-1))
            for p in prompts]
        # teacher-forced on its own first token alone: 12 rows are read,
        # the first is the prompt's
        sample = [(p, [int(b[0])]) for p, b in zip(prompts, best)]
        sound = np.concatenate(driver.reference_gaps(ctx, params, sample))
        control = np.concatenate(
            driver.reference_gaps(ctx, params, [(p, [0] * 12)
                                                for p in prompts],
                                  quant="fp8"))
    assert sound.shape == (4,) and sound.max() == 0
    assert control.shape == (48,) and control.max() > 0
