"""Toy sizes for the benchmark's tests: the real cells' files with every
size cut until a CPU run takes seconds. The program's registry is steered
from here (test side), not through an option of the program."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAKS = {"bf16_flops_s": 1e12, "hbm_bytes_s": 1e11, "hbm_bytes": 1e9}
# a model of width 32 rounds coarser than one of width 768: the toy cells
# carry limits of their own, the real cells' come from readings on the chip
TRAIN_LIMITS = {"loss_rel_gap": 0.01, "grad_norm_gap": 0.05,
                "param_change_gap": 0.6}
SERVE_LIMITS = {"served_token_gap": 0.25}


def load(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def manifest() -> dict:
    return load("BENCHMARK.json")


def manifest_with_the_tail_cell() -> dict:
    """The manifest plus the serving cell below the knee, which waits under
    PERF.md's Open questions: its traffic file, driver path and readers are
    kept and tested, so that a later PR adds it back as entries alone."""
    m = manifest()
    m["end_to_end"].append({
        "name": "ttft_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
        "source": "host_clock", "workloads": ["gpt2s_serve_chat"]})
    return m


def shrink_registry(monkeypatch) -> None:
    """lm_tiny and vit_tiny become one layer of width 32."""
    from ddp_practice_tpu import models
    from ddp_practice_tpu.models.lm import TransformerLM
    from ddp_practice_tpu.models.vit import ViT

    def lm(*, num_classes, policy, axis_name, **kw):
        return TransformerLM(hidden_dim=32, depth=1, num_heads=2, mlp_dim=64,
                             dtype=policy.compute_dtype,
                             param_dtype=policy.param_dtype, **kw)

    def vit(*, num_classes, policy, axis_name, **kw):
        return ViT(num_classes=num_classes, hidden_dim=32, depth=1,
                   num_heads=2, mlp_dim=64, fused=False,
                   dtype=policy.compute_dtype,
                   param_dtype=policy.param_dtype, **kw)

    monkeypatch.setitem(models._REGISTRY, "lm_tiny", lm)
    monkeypatch.setitem(models._REGISTRY, "vit_tiny", vit)


def lm_config(file: str = "gpt2_small_bytes", **kw) -> dict:
    """Either LM configuration at toy widths; the vocabulary is the byte
    one in both (the toy limits were read there)."""
    return dict(load(f"perf/configs/{file}.json"),
                program_model="lm_tiny", n_embd=32, n_layer=1, n_head=2,
                n_inner=64, n_positions=64, vocab_size=256, **kw)


def vit_config(**kw) -> dict:
    return dict(load("perf/configs/vit_b16.json"),
                program_model="vit_tiny_p2", program_model_base="vit_tiny",
                program_model_kwargs={"patch_size": 2},
                hidden_size=32, num_hidden_layers=1, num_attention_heads=2,
                intermediate_size=64, image_size=8, patch_size=2,
                num_labels=10, **kw)


def train_cell(family: str) -> tuple:
    if family == "vit":
        cell = {"name": "vitb16_train_224", "config": "vit_b16",
                "traffic": "toy", "chips": 1}
        traffic = dict(load("perf/traffic/vit_224_b128.json"),
                       batch_per_chip=4, segment_steps=2,
                       reference_block_rows=2, limits=TRAIN_LIMITS,
                       warmup={"agree_pct": 50.0, "max_segments": 2})
        return cell, vit_config(), traffic
    cell = {"name": "gpt2s_train_2k_dp4", "config": "gpt2_small_bytes",
            "traffic": "toy", "chips": 2}
    traffic = dict(load("perf/traffic/lm_2k_b8_dp4.json"), batch_per_chip=2,
                   seq_len=16, segment_steps=2, reference_block_rows=2,
                   limits=TRAIN_LIMITS,
                   warmup={"agree_pct": 50.0, "max_segments": 2})
    traffic["trainer"] = dict(traffic["trainer"], attn_impl="xla")
    return cell, lm_config("gpt2_small_bytes"), traffic


def serve_cell(mode: str) -> tuple:
    name = {"tail": "gpt2s_serve_chat", "flood": "gpt2s_serve_flood"}[mode]
    file = {"tail": "chat_poisson", "flood": "flood_poisson"}[mode]
    cell = {"name": name, "config": "gpt2_small", "traffic": "toy",
            "chips": 1}
    traffic = copy.deepcopy(load(f"perf/traffic/{file}.json"))
    traffic["tenants"][0].update(
        rate_rps=6.0 if mode == "tail" else 40.0, prompt_len_median=10,
        prompt_len_cap=24, max_new_median=6, max_new_cap=12)
    traffic["engine"].update(max_slots=3, buckets=[8, 24], burst=4,
                             max_blocks_per_slot=3, num_blocks=12)
    traffic["check"].update(pad_to=40, requests=6)
    traffic["limits"] = SERVE_LIMITS
    return cell, lm_config("gpt2_small"), traffic
