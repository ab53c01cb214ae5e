"""CLI launcher.

One command replaces both reference launch styles (SURVEY §2.4): the
mp.spawn parent (ddp_main.py:173-178) and torchrun (README launch cmd) —
on TPU there is one process per host, so "launching" is just running this
module; multi-host runs add --coordinator (no hardcoded port — the
reference pins 19198, ddp_main.py:62).

Parity flags kept: -e/--epochs (default 3), -b/--batch_size (default 32,
per data-parallel replica) — origin_main.py:34-54. `--gpu` has no TPU
meaning; `--devices N` limits visible local devices instead.

Examples:
  python -m ddp_practice_tpu.cli                      # ConvNet/MNIST parity run
  python -m ddp_practice_tpu.cli --precision bf16     # the "AMP" variant
  python -m ddp_practice_tpu.cli --model vit_tiny --dataset cifar10 \\
      --tensor 2 --optimizer adamw --lr 1e-3
  python -m ddp_practice_tpu.cli serve --ckpt_dir ck --prompt "ab"
                                       # serve prompts from a trained LM
                                       # checkpoint through the
                                       # continuous-batching engine
                                       # (serve/cli.py owns the flags)

Measuring is not this module's job: `python3 perf/run.py --workload
<cell>` is the benchmark (BENCHMARK.json) and PERF.md holds its results.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

from ddp_practice_tpu.config import MeshConfig, TrainConfig


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("ddp_practice_tpu")
    p.add_argument("-e", "--epochs", type=int, default=3)
    p.add_argument("-b", "--batch_size", type=int, default=32,
                   help="per data-parallel replica, like the reference")
    p.add_argument("--model", default="convnet",
                   choices=["convnet", "resnet18", "resnet50", "vit_tiny",
                            "vit_base", "vit_tiny_moe", "vit_tiny_pipe",
                            "lm_tiny", "lm_base", "lm_moe", "lm_pipe"])
    p.add_argument("--num_heads", type=int, default=0,
                   help="override attention head count (transformer models; "
                        "0 = model default — note tensor parallelism needs "
                        "heads divisible by the tensor degree)")
    p.add_argument("--dataset", default="mnist",
                   help="image models: mnist|cifar10|imagenet|synthetic; "
                        "lm models: text (bytes from --data_dir) or "
                        "anything else for the synthetic Markov corpus")
    p.add_argument("--seq_len", type=int, default=256,
                   help="LM sequence length (lm_* models)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize LM block activations in backward "
                        "(longer sequences for ~1/3 more FLOPs)")
    p.add_argument("--pos_emb", default="learned", choices=["learned", "rope"],
                   help="LM position encoding: learned absolute table or "
                        "rotary Q/K (relative; long-context default)")
    p.add_argument("--tied", action="store_true",
                   help="tie the LM output projection to the token "
                        "embedding (GPT-2 weight tying)")
    p.add_argument("--data_dir", default="./data")
    p.add_argument("--synthetic_size", type=int, default=0,
                   help="synthetic-fallback corpus size (train split; "
                        "0 = per-dataset default)")
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--optimizer", default="sgd", choices=["sgd", "adam", "adamw"])
    p.add_argument("--momentum", type=float, default=0.0)
    p.add_argument("--clip_norm", type=float, default=0.0,
                   help="clip gradients to this global L2 norm (0 = off)")
    p.add_argument("--dropout", type=float, default=0.0,
                   help="dropout rate for the ViT/LM transformer blocks "
                        "(residual branches + LM embedding; 0 = off)")
    p.add_argument("--weight_decay", type=float, default=0.0)
    p.add_argument("--lr_schedule", default="constant",
                   choices=["constant", "cosine", "warmup_cosine"])
    p.add_argument("--accum_steps", type=int, default=1,
                   help="gradient accumulation: average grads over k "
                        "micro-steps before each optimizer apply")
    p.add_argument("--scale_lr", action="store_true",
                   help="scale lr by replica count (the reference deliberately "
                        "does not; README.md:506)")
    p.add_argument("--seed", type=int, default=3407)
    p.add_argument("--precision", default="fp32", choices=["fp32", "bf16"])
    p.add_argument("--data_axis", type=int, default=-1)
    p.add_argument("--seq", type=int, default=1, help="sequence-parallel degree")
    p.add_argument("--tensor", type=int, default=1, help="tensor-parallel degree")
    p.add_argument("--pipe", type=int, default=1, help="pipeline-parallel stages")
    p.add_argument("--expert", type=int, default=1, help="expert-parallel degree")
    p.add_argument("--sp_impl", default="ring", choices=["ring", "ulysses"],
                   help="sequence-parallel attention scheme")
    p.add_argument("--attn_impl", default="auto",
                   choices=["auto", "xla", "flash"],
                   help="local attention kernel: xla = compiler-fused, "
                        "flash = the Pallas streaming kernels (long "
                        "sequences), auto = from the shape: the Pallas "
                        "whole-sequence kernels for short sequences on a "
                        "TPU, xla otherwise")
    p.add_argument("--microbatches", type=int, default=4,
                   help="GPipe microbatches per step (pipe > 1)")
    p.add_argument("--pipe_schedule", default="gpipe",
                   choices=["gpipe", "1f1b", "interleaved"],
                   help="pipeline schedule (pipe > 1): gpipe = autodiff "
                        "scan, activation memory O(M+P); 1f1b = one-F-one-B "
                        "backward, O(P) memory; interleaved = virtual "
                        "pipeline chunks (Megatron), ~V-fold smaller "
                        "bubble (LM models)")
    p.add_argument("--num_virtual", type=int, default=2,
                   help="virtual pipeline chunks per device (interleaved "
                        "schedule only; depth must divide pipe*V)")
    p.add_argument("--num_experts", type=int, default=0,
                   help="MoE expert count (0 = auto from --expert axis)")
    p.add_argument("--moe_router", default="topk",
                   choices=["topk", "expert_choice"],
                   help="MoE routing scheme: topk = tokens choose experts "
                        "(GShard/Switch; aux loss + balance bias + capacity "
                        "drops); expert_choice = experts choose tokens "
                        "(perfect balance, zero drops/padding — ops/moe.py)")
    p.add_argument("--fsdp", action="store_true",
                   help="ZeRO-3: shard params + optimizer state over 'data'")
    p.add_argument("--devices", type=int, default=0,
                   help="use only the first N local devices (0 = all)")
    p.add_argument("--cpu", type=int, default=0, metavar="N",
                   help="force the CPU platform with N virtual devices "
                        "(sharding dev-runs without TPU hardware)")
    p.add_argument("--coordinator", default=None,
                   help="host:port for multi-host rendezvous")
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--process_id", type=int, default=None)
    p.add_argument("--ckpt_dir", default=None)
    p.add_argument("--ckpt_every", type=int, default=0, metavar="STEPS",
                   help="also checkpoint every N optimizer steps "
                        "(async write; 0 = only per-epoch/end)")
    p.add_argument("--ckpt_sync", action="store_true",
                   help="force synchronous periodic checkpoint writes")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="checkpoint-based restarts on training failure")
    p.add_argument("--watchdog", type=float, default=0.0, metavar="SECS",
                   help="fail-fast if no step completes within SECS")
    p.add_argument("--sync_check", type=int, default=0, metavar="STEPS",
                   help="assert cross-host driver sync every STEPS steps")
    p.add_argument("--eval_every", type=int, default=0)
    p.add_argument("--max_steps", type=int, default=0,
                   help="cap steps per epoch (smoke runs; 0 = full epoch)")
    p.add_argument("--log_every", type=int, default=100)
    p.add_argument("--profile_dir", default=None)
    p.add_argument("--trace-out", "--trace_out", dest="trace_out",
                   default=None, metavar="PATH",
                   help="write a Chrome trace-event JSON of host-side "
                        "step phases (data/dispatch/block/checkpoint "
                        "spans) at fit end — open in Perfetto; "
                        "validate with tools/check_traces.py")
    p.add_argument("--metrics_file", default=None, metavar="PATH",
                   help="append one JSON record per logged step / eval / "
                        "summary (training curves; process 0 only)")
    p.add_argument("--metrics-port", "--metrics_port", dest="metrics_port",
                   type=int, default=None, metavar="PORT",
                   help="serve /metrics (Prometheus), /healthz and "
                        "/flight (rolling step-time percentiles) over "
                        "HTTP during the fit (0 = ephemeral port, "
                        "logged at startup; process 0 only)")
    p.add_argument("--telemetry-out", "--telemetry_out",
                   dest="telemetry_out", default=None, metavar="PATH",
                   help="stream step spans + step records + metrics "
                        "snapshots as line-delimited JSONL while "
                        "training (survives a killed run; validate "
                        "with tools/check_traces.py)")
    p.add_argument("--slo", default=None, metavar="JSON|PATH",
                   help="SLO config (serve/slo.py) — arms a burn-rate "
                        "watchdog over the step-time straggler "
                        "detector; alerts land in the telemetry "
                        "stream and the metrics registry")
    p.add_argument("--alert-sink", "--alert_sink", dest="alert_sink",
                   action="append", default=None, metavar="KIND:TARGET",
                   help="repeatable; PUSH SLO alert edges to an "
                        "operator sink (command:..., webhook:http://..., "
                        "jsonl:path) with retry backoff and a dead-sink "
                        "breaker (serve/slo.py AlertSinks); needs --slo")
    p.add_argument("--loader", default="auto", choices=["auto", "native", "python"])
    p.add_argument("--steps_per_call", type=int, default=1,
                   help="K optimizer steps per jitted call (amortizes host "
                        "dispatch + H2D for small models); -1 = the whole "
                        "epoch per call (device-resident data only)")
    p.add_argument("--data_placement", default="auto",
                   choices=["auto", "host", "device"],
                   help="corpus home: device = upload once to HBM, epochs "
                        "driven by index grids alone; host = stream batches; "
                        "auto = device when single-process and it fits")
    p.add_argument("--compile_cache", default="auto",
                   choices=["auto", "off"],
                   help="persistent XLA compilation cache (repeat runs skip "
                        "compile): auto = $JAX_COMPILATION_CACHE_DIR when "
                        "set, else .jax_compile_cache/ in the checkout; "
                        "off = disable")
    p.add_argument("--fused", nargs="?", const="on", default="auto",
                   choices=["auto", "on", "off"],
                   help="fused Pallas encoder-layer kernels "
                        "(ops/fused_encoder.py — the small-d HBM-bound "
                        "fix). auto (default): selected whenever the "
                        "model/shape supports them (vit_tiny, dense LMs "
                        "with head_dim a multiple of 64 via --num_heads), "
                        "silent per-op fallback otherwise; on (or bare "
                        "--fused): force, raising on unsupported configs "
                        "(exception: an MoE-interleaved LM fuses its DENSE "
                        "blocks only — routed blocks have no fused kernel); "
                        "off: always per-op")
    p.add_argument("--augment", action="store_true",
                   help="on-device augmentation inside the jitted train "
                        "step (image models; deterministic per seed/step — "
                        "ops/augment.py)")
    p.add_argument("--augment_kind", default="crop_flip",
                   choices=["crop_flip", "rrc"],
                   help="crop_flip: pad-crop + flip (CIFAR/MNIST rung); "
                        "rrc: random resized crop (the ImageNet rung)")
    p.add_argument("--json", action="store_true", help="print summary as JSON")
    return p


def _alert_sinks_from(args):
    if not args.alert_sink:
        return None
    if not args.slo:
        # the sinks only ever carry the watchdog's edges — accepting
        # them without --slo would arm a pager that can never fire
        raise SystemExit("--alert-sink needs --slo (the sinks carry "
                         "the watchdog's trip/resolve edges)")
    return tuple(args.alert_sink)


def config_from_args(args) -> TrainConfig:
    if args.augment_kind != "crop_flip" and not args.augment:
        raise SystemExit(
            "--augment_kind has no effect without --augment — pass both "
            "(the run would otherwise train UNAUGMENTED while its flags "
            "suggest otherwise)"
        )
    return TrainConfig(
        model=args.model,
        dataset=args.dataset,
        data_dir=args.data_dir,
        synthetic_size=args.synthetic_size,
        seq_len=args.seq_len,
        remat=args.remat,
        pos_emb=args.pos_emb,
        tied_embeddings=args.tied,
        epochs=args.epochs,
        batch_size=args.batch_size,
        learning_rate=args.lr,
        optimizer=args.optimizer,
        momentum=args.momentum,
        clip_norm=args.clip_norm,
        dropout=args.dropout,
        weight_decay=args.weight_decay,
        lr_schedule=args.lr_schedule,
        scale_lr_by_replicas=args.scale_lr,
        accum_steps=args.accum_steps,
        seed=args.seed,
        precision=args.precision,
        mesh=MeshConfig(
            data=args.data_axis, seq=args.seq, tensor=args.tensor,
            pipe=args.pipe, expert=args.expert,
        ),
        fsdp=args.fsdp,
        sp_impl=args.sp_impl,
        attn_impl=args.attn_impl,
        num_microbatches=args.microbatches,
        pipe_schedule=args.pipe_schedule,
        num_virtual=args.num_virtual,
        augment=args.augment,
        augment_kind=args.augment_kind,
        fused_encoder=args.fused,
        num_experts=args.num_experts,
        moe_router=args.moe_router,
        num_heads=args.num_heads,
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every_steps=args.ckpt_every,
        checkpoint_async=not args.ckpt_sync,
        resume=args.resume,
        max_restarts=args.max_restarts,
        watchdog_timeout_s=args.watchdog,
        sync_check_every_steps=args.sync_check,
        eval_every_epochs=args.eval_every,
        max_steps_per_epoch=args.max_steps,
        log_every_steps=args.log_every,
        profile_dir=args.profile_dir,
        trace_out=args.trace_out,
        metrics_file=args.metrics_file,
        metrics_port=args.metrics_port,
        telemetry_out=args.telemetry_out,
        slo=args.slo,
        alert_sinks=_alert_sinks_from(args),
        loader_backend=args.loader,
        steps_per_call=args.steps_per_call,
        data_placement=args.data_placement,
        compilation_cache=args.compile_cache,
    )


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "serve":
        # inference subcommand: the training flags below don't apply, so
        # dispatch before the trainer parser sees the argv (serve/cli.py
        # owns the serve flag surface)
        from ddp_practice_tpu.serve.cli import main as serve_main

        return serve_main(argv[1:])
    args = build_parser().parse_args(argv)
    if args.devices:
        import os

        os.environ.setdefault("JAX_NUM_CPU_DEVICES", str(args.devices))
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    from ddp_practice_tpu.train.loop import fit  # deferred: jax import cost

    t0 = time.time()
    summary = fit(config_from_args(args))
    summary["wall_seconds"] = time.time() - t0
    if args.json:
        print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
