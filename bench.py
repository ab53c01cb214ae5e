"""Benchmark: steady-state training throughput and MFU, one JSON line.

Headline: ViT-Base (the MXU-bound flagship transformer) training
images/sec/chip with computed MFU against the chip's bf16 peak. The final
stdout line is a COMPACT driver-parseable record (metric/value/unit/
vs_baseline + headline MFU only); the full per-model suite is written to
BENCHMARKS.json next to this file. Companion entries there: ViT-Tiny
(HBM-bound at d=192 — see BENCHMARKS.md), the ConvNet/MNIST parity model
(the BASELINE.json north-star metric, with `vs_baseline` = ratio to the
reference's ~7,923 images/sec implied by README.md:201), ResNet-18,
ResNet-50 at ImageNet shape, and the LM train/decode entries.

Methodology — device-resident uint8 data pool, on-device gather+normalize,
K steps per dispatch, timing fenced by a scalar host readback — is
documented in BENCHMARKS.md. End-to-end wall-clock numbers with the real
input pipeline live in PARITY.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


REFERENCE_IMAGES_PER_SEC = 60000 * 3 / 22.72  # README.md:201 (incl. eval)

# (name, kwargs) — per-model saturating configs for one chip
_SUITE = {
    # the DEFAULT vit_tiny path: since round 5 fused="auto" selects the
    # Pallas encoder-layer kernels (ops/fused_encoder.py) on a single
    # TPU chip without flags — this entry records what a user gets
    "vit_tiny": dict(
        image_shape=(32, 32, 3), batch_size=1024, steps_per_call=32, calls=8,
    ),
    # the per-op XLA pipeline, kept as the documented companion number
    # (BENCHMARKS.md "Why ViT-Tiny sat at ~17%" — the HBM-bound small-d
    # regime the fused kernels fix)
    "vit_tiny_unfused": dict(
        model="vit_tiny", image_shape=(32, 32, 3), batch_size=1024,
        steps_per_call=32, calls=8, model_kwargs={"fused": False},
    ),
    # FORCED fused=True (fails loudly if the kernel can't run — also on a
    # multichip host, where the kernel has no shard_map island and
    # "vit_tiny" above falls back to per-op): on a single chip identical
    # to "vit_tiny".
    "vit_tiny_fused": dict(
        model="vit_tiny", image_shape=(32, 32, 3), batch_size=1024,
        steps_per_call=32, calls=8, model_kwargs={"fused": True},
    ),
    "vit_base": dict(
        # bs swept 96..512 on v5e (2026-07-30): 192 is the plateau top —
        # 54.9% MFU vs 48.0% at the earlier 256 default; throughput falls
        # ~19% by bs 512 (activation traffic, not MXU, sets the ceiling).
        # calls=24: the chip clocks up under SUSTAINED load (the ramp
        # the ConvNet entry quantifies) — at 6 calls the 1.4 s
        # half-windows read ~5% low
        image_shape=(32, 32, 3), batch_size=192, steps_per_call=8,
        calls=24,
    ),
    # the vs_baseline denominator — measured over LONG windows: at
    # ~0.4 ms/step the old 32-step calls were dispatch-amortization-bound
    # and the recorded rate swung 62-91k img/s run to run (round-3
    # verdict item 7). 512 steps/call fixed that; the round-4 second
    # pass then found the rate RAMPS with sustained load (half-window
    # rates: 219k at 8 calls -> 253k at 16 -> 284k at 32, where the two
    # fenced half-windows finally agree within ~1% — short windows
    # measure a cold-clock chip). 32 calls x 512 steps = ~2.4 s per
    # half-window; repeats land 278-284k img/s.
    "convnet": dict(
        image_shape=(28, 28, 1), batch_size=32, steps_per_call=512,
        calls=32, warmup_calls=4, pool_size=4096,
    ),
    # resnet windows lengthened for the same clock-ramp reason as
    # vit_base/convnet (short windows read a cold chip ~5-8% low)
    "resnet18": dict(
        image_shape=(32, 32, 3), batch_size=512, steps_per_call=16,
        calls=16,
    ),
    "resnet50": dict(
        image_shape=(224, 224, 3), num_classes=1000, batch_size=128,
        steps_per_call=8, calls=12, pool_size=512,
    ),
    # long-context LM entries (kind="lm" -> bench_lm_train: tokens/sec +
    # MFU; causal flash attention). lm_long runs in the default list; the
    # longer lengths are opt-in: `--models lm_8k` / `--models lm_16k`.
    "lm_long": dict(
        # K=8 steps/dispatch amortizes the per-call dispatch + readback
        # (K=4 vs K=8 and bs 8/16/32 were swept 2026-07 on a set-up that
        # no longer exists; not re-measured — bs 8 was the best of the
        # three there, activation HBM traffic favoring the small batch)
        kind="lm", seq_len=2048, batch_size=8, steps_per_call=8, calls=6,
    ),
    # MoE LM at lm_base dims, experts every other block (GShard layout),
    # under EXPERT-CHOICE routing (ops/moe.py expert_choice_gating) —
    # the TPU-first router: experts pick tokens, so every buffer slot
    # fills — zero drops and zero capacity padding BY CONSTRUCTION
    # (cf 1.0: executed expert FLOPs == active FLOPs, vs the 1.5x a
    # token-choice capacity factor executes). Measured round 5:
    # 44.3% MFU vs 37.7% token-choice — the padding was the whole
    # remaining MoE-dense gap (the round-5 BENCHMARKS.md MoE section
    # records the full dispatch-glue shootout that led here). Groups
    # of 256 strided tokens bound both the dispatch einsum cost and
    # the EC routing-competition scope (group 128/512 measured 42.0/
    # 41.4%).
    "lm_moe": dict(
        kind="lm", model="lm_moe", seq_len=2048, batch_size=8,
        steps_per_call=4, calls=4, warmup_calls=10, data="corpus",
        model_kwargs={
            "hidden_dim": 768, "depth": 12, "num_heads": 12,
            "mlp_dim": 3072, "moe_every": 2, "num_experts": 8,
            "moe_group_size": 256, "capacity_factor": 1.0,
            "moe_router": "expert_choice",
        },
    ),
    # the token-choice (GShard/Switch top-k) record: tokens/sec + MFU
    # (active-FLOPs accounting) + router drop rate. warmup 10 calls
    # (40 steps) + the synthetic Markov corpus so the recorded router
    # health is the WARM equilibrium of the balancing machinery (fixed
    # Switch aux + DeepSeek-style selection bias), not init-state
    # garbage — the round-3 entry recorded an untrained router's
    # drop=0.30 on uniform-random tokens (round-3 verdict item 3).
    # Routing groups of 256 strided-interleaved tokens at capacity 1.5
    # (round-4 sweep): the dispatch/combine einsums are O(group_size)
    # per token, so 2048 -> 256 cuts them ~8x, and the interleave
    # decorrelates per-group demand enough that cf 1.5 drops LESS
    # (1.1%) than whole-sequence cf 2.0 did (1.4%). Kept in the suite:
    # token-choice is the strictly-causal training scheme (see the EC
    # caveat in ops/moe.py) and the multichip expert-parallel path's
    # semantics.
    "lm_moe_tc": dict(
        kind="lm", model="lm_moe", seq_len=2048, batch_size=8,
        steps_per_call=4, calls=4, warmup_calls=10, data="corpus",
        model_kwargs={
            "hidden_dim": 768, "depth": 12, "num_heads": 12,
            "mlp_dim": 3072, "moe_every": 2, "num_experts": 8,
            "moe_group_size": 256, "capacity_factor": 1.5,
        },
    ),
    # short-seq decoder LM through the fused Pallas encoder-layer kernels
    # (round 4: ops/fused_encoder.py grew causal masking) — the d=256
    # HBM-bound regime's fix applied to the LM family. heads=4 keeps
    # head_dim 64 (the kernel's 64-aligned column-slice contract);
    # attn_impl stays xla (the whole layer IS the kernel). Companion
    # unfused number in BENCHMARKS.md: 1.70x.
    "lm_tiny_fused": dict(
        kind="lm", model="lm_tiny", seq_len=256, batch_size=256,
        steps_per_call=16, calls=12, warmup_calls=4, attn_impl="xla",
        data="corpus",
        model_kwargs={"num_heads": 4, "fused": True},
    ),
    "lm_8k": dict(
        kind="lm", seq_len=8192, batch_size=2, steps_per_call=2, calls=3,
    ),
    "lm_16k": dict(
        kind="lm", seq_len=16384, batch_size=1, steps_per_call=2, calls=3,
    ),
    "lm_32k": dict(
        kind="lm", seq_len=32768, batch_size=1, steps_per_call=1, calls=2,
        model_kwargs={"remat": True},
    ),
    # autoregressive generation (KV-cache decode, inference.py): tokens/sec
    # + model-bandwidth utilization — decode re-reads all params per token,
    # so the roofline is HBM, not the MXU. bs=1 is the single-stream MBU
    # flagship (params-streaming bound); bs=8 trades MBU for batch rate.
    # Params stream as bf16 (inference needs no fp32 masters).
    "lm_decode": dict(
        kind="decode", prompt_len=128, max_new_tokens=512, batch_size=8,
        calls=3,
    ),
    "lm_decode_bs1": dict(
        kind="decode", prompt_len=128, max_new_tokens=512, batch_size=1,
        calls=3,
    ),
    # longer-context batched decode with the INT8 KV cache
    # (models/vit.py kv_cache_dtype="int8" + the quantized packed
    # kernel): at L=1024 the bf16 cache read is ~1.8x the param stream,
    # and int8 measured +17.5% tokens/s over bf16 (0.544 vs 0.663
    # ms/step; the crossover is L~768 — below it the scale-buffer
    # traffic eats the saving, so the short entries stay bf16).
    "lm_decode_1k": dict(
        kind="decode", prompt_len=256, max_new_tokens=768, batch_size=8,
        calls=3, kv_cache="int8",
    ),
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser("bench")
    p.add_argument("--models",
                   default="vit_base,vit_tiny,vit_tiny_unfused,"
                           "vit_tiny_fused,convnet,"
                           "resnet18,resnet50,lm_long,lm_moe,lm_moe_tc,"
                           "lm_tiny_fused,lm_decode,lm_decode_bs1,"
                           "lm_decode_1k",
                   help="comma-separated; first successful is the headline")
    p.add_argument("--precision", default="bf16", choices=["fp32", "bf16"])
    p.add_argument("--batch_size", type=int, default=0, help="override")
    p.add_argument("--steps_per_call", type=int, default=0, help="override")
    p.add_argument("--calls", type=int, default=0, help="override")
    args = p.parse_args(argv)

    import jax

    from ddp_practice_tpu.benchmarks import (
        bench_lm_decode,
        bench_lm_train,
        bench_train,
    )
    from ddp_practice_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a rate measured on the CPU backend or the Pallas interpreter is
        # not a number about this system; refuse before any model runs
        print(f"[bench] no TPU: jax reports platform {dev.platform!r} "
              f"({dev.device_kind}) — this benchmark measures the chip only",
              file=sys.stderr)
        return 2

    results = []
    errors = []
    names = [m.strip() for m in args.models.split(",") if m.strip()]
    unknown = [n for n in names if n not in _SUITE]
    if unknown:
        p.error(f"no bench config for {unknown}; known: {sorted(_SUITE)}")
    for name in names:
        kw = dict(_SUITE[name])
        kind = kw.pop("kind", "image")
        kw["precision"] = args.precision
        if args.batch_size:
            if name.endswith("_bs1"):
                # the entry's identity pins its batch size; an override
                # would record a wrong number under the bs1 name
                print(f"[bench] --batch_size ignored for {name}",
                      file=sys.stderr)
            else:
                kw["batch_size"] = args.batch_size
        if args.steps_per_call:
            kw["steps_per_call"] = args.steps_per_call
        if args.calls:
            kw["calls"] = args.calls
        try:
            if kind == "lm":
                r = bench_lm_train(kw.pop("model", "lm_base"), **kw)
                r["model"] = name
                results.append(r)
            elif kind == "decode":
                r = bench_lm_decode("lm_base", **kw)
                r["model"] = name
                results.append(r)
            else:
                r = bench_train(kw.pop("model", name), **kw)
                r["model"] = name
                results.append(r)
        except Exception:  # noqa: BLE001 — the other models still run; exit code 1 below
            errors.append({"model": name, "error": traceback.format_exc(limit=3)})

    if not results:
        # deliberately do NOT touch BENCHMARKS.json here: a transient
        # all-models failure must not clobber the last good recorded suite
        for e in errors:
            print(f"[bench] {e['model']} failed:\n{e['error']}",
                  file=sys.stderr)
        print(json.dumps({
            "metric": "bench failed", "value": 0.0, "unit": "images/sec/chip",
            "vs_baseline": 0.0, "n_errors": len(errors),
        }))
        return 1

    head = results[0]
    head_rate = head.get(
        "images_per_sec_per_chip", head.get("tokens_per_sec_per_chip", 0.0)
    )
    head_unit = (
        "images/sec/chip" if "images_per_sec_per_chip" in head
        else "tokens/sec/chip"
    )
    convnet = next((r for r in results if r["model"] == "convnet"), None)
    if convnet:
        vs_baseline = round(
            convnet["images_per_sec_per_chip"] / REFERENCE_IMAGES_PER_SEC, 3
        )
        vs_note = (
            "ratio of the ConvNet/MNIST companion entry (results) to the "
            "reference's ~7,923 img/s (README.md:201); the reference "
            "publishes no transformer numbers"
        )
    else:
        vs_baseline = round(head_rate / REFERENCE_IMAGES_PER_SEC, 3)
        vs_note = (
            f"CROSS-MODEL ratio: {head['model']} {head_unit} over the "
            "reference's ConvNet/MNIST ~7,923 img/s (README.md:201) — no "
            "convnet entry ran in this invocation; rerun with "
            "--models convnet,... for the like-for-like number"
        )
    head_mode = "decode" if head.get("mode") == "decode" else "train"
    line = {
        "metric": (
            f"{head['model']} {head_mode} throughput (bs={head['batch_size']}, "
            f"{head['precision']}, {head['n_chips']} chip(s), "
            f"{head['device_kind']})"
        ),
        "value": head_rate,
        "unit": head_unit,
        "vs_baseline": vs_baseline,
    }
    if "mfu_pct" in head:
        line["mfu_pct"] = head["mfu_pct"]
        line["tflops_per_chip"] = head["tflops_per_chip"]
    if "mbu_pct" in head:
        line["mbu_pct"] = head["mbu_pct"]
    if errors:
        line["n_errors"] = len(errors)
        for e in errors:
            print(f"[bench] {e['model']} failed:\n{e['error']}",
                  file=sys.stderr)

    # Full suite (every model record, the vs_baseline provenance note, and
    # any tracebacks) goes to a file; the driver's tail capture only needs
    # the compact line above: a several-KB stdout line was once truncated
    # mid-record by the capture and parsed as null.
    _write_suite({
        "headline": head,
        "results": results,
        "vs_baseline": vs_baseline,
        "vs_baseline_note": vs_note,
        "errors": errors,
    }, partial=(
        args.models != p.get_default("models")
        or args.precision != p.get_default("precision")
        or bool(args.batch_size or args.steps_per_call or args.calls)
        or bool(errors)
    ))
    print(json.dumps(line))
    # the line and the suite are written either way, but a requested
    # model that failed fails the run
    return 1 if errors else 0


def _write_suite(suite: dict, *, partial: bool = False) -> None:
    """Dump the full suite next to this file; never kill the stdout line.

    Partial invocations (a custom --models subset) write to
    BENCHMARKS.partial.json so they cannot clobber the recorded
    default-suite results that BENCHMARKS.md cites.
    """
    name = "BENCHMARKS.partial.json" if partial else "BENCHMARKS.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    try:
        with open(path, "w") as f:
            json.dump(suite, f, indent=1)
        print(f"full suite -> {path}", file=sys.stderr)
    except OSError as e:  # read-only checkout / full disk: line still prints
        print(f"could not write {path}: {e}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
