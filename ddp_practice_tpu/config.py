"""Configuration for training runs.

The reference exposes exactly three CLI flags — `--gpu`, `-e/--epochs`,
`-b/--batch_size` (origin_main.py:34-54) — with everything else hardcoded:
lr 1e-4 (ddp_main.py:125), seed 3407 (ddp_main.py:76), AMP on/off by script
choice. Here the same knobs live in one dataclass, with distribution described
by a device-mesh shape instead of a GPU list.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Mixed-precision policy replacing autocast + GradScaler.

    On TPU, bf16 has the same exponent range as fp32, so the dynamic
    loss-scaling machinery the reference needs for fp16 (GradScaler,
    ddp_main.py:10,126,91-93) is unnecessary: we simply run compute in
    ``compute_dtype`` while keeping parameters and optimizer state in
    ``param_dtype``.
    """

    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    output_dtype: jnp.dtype = jnp.float32

    @staticmethod
    def fp32() -> "PrecisionPolicy":
        return PrecisionPolicy()

    @staticmethod
    def bf16() -> "PrecisionPolicy":
        return PrecisionPolicy(
            param_dtype=jnp.float32,
            compute_dtype=jnp.bfloat16,
            output_dtype=jnp.float32,
        )

    @staticmethod
    def from_name(name: str) -> "PrecisionPolicy":
        name = name.lower()
        if name in ("fp32", "float32", "f32"):
            return PrecisionPolicy.fp32()
        if name in ("bf16", "bfloat16", "mixed"):
            return PrecisionPolicy.bf16()
        raise ValueError(f"unknown precision policy {name!r}")

    @property
    def name(self) -> str:
        return "bf16" if self.compute_dtype == jnp.bfloat16 else "fp32"


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Shape of the device mesh.

    Replaces the reference's rank/world bookkeeping (`--gpu 0,1`,
    WORLD_SIZE env, ddp_main.py:60-66): the mesh *is* the distributed-backend
    configuration. Axes:

    - ``data``: data parallelism (batch sharding + gradient pmean)
    - ``seq``: sequence/context parallelism (ring/ulysses attention)
    - ``tensor``: tensor parallelism (head/feature sharding)
    - ``pipe``: pipeline parallelism (stage-sharded block stacks, GPipe
      microbatch schedule over ppermute)
    - ``expert``: expert parallelism (MoE expert sharding, all-to-all
      token dispatch)

    A size of -1 on the data axis means "all remaining devices".
    """

    data: int = -1
    seq: int = 1
    tensor: int = 1
    pipe: int = 1
    expert: int = 1

    AXIS_DATA = "data"
    AXIS_SEQ = "seq"
    AXIS_TENSOR = "tensor"
    AXIS_PIPE = "pipe"
    AXIS_EXPERT = "expert"

    @property
    def axis_names(self) -> tuple:
        return (
            self.AXIS_DATA,
            self.AXIS_SEQ,
            self.AXIS_TENSOR,
            self.AXIS_PIPE,
            self.AXIS_EXPERT,
        )

    def resolve(self, n_devices: int) -> tuple:
        """Return concrete (data, seq, tensor, pipe, expert) sizes."""
        rest = self.seq * self.tensor * self.pipe * self.expert
        data = self.data
        if data == -1:
            if n_devices % rest != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by "
                    f"seq*tensor*pipe*expert={rest}"
                )
            data = n_devices // rest
        if data * rest != n_devices:
            raise ValueError(
                f"mesh {data}x{self.seq}x{self.tensor}x{self.pipe}"
                f"x{self.expert} != {n_devices} devices"
            )
        return (data, self.seq, self.tensor, self.pipe, self.expert)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Full training-run configuration.

    Defaults reproduce the reference contract: 3 epochs, per-replica batch 32,
    SGD lr 1e-4 (NOT scaled by world size — parity with ddp_main.py:125 and
    the acknowledged accuracy gap in the reference README), seed 3407.
    """

    # model / data
    model: str = "convnet"
    dataset: str = "mnist"
    data_dir: str = "./data"
    num_classes: int = 10
    # override the synthetic-fallback corpus size (train split; eval gets
    # ~1/6, the MNIST train:test ratio; for LM tasks this is the token
    # count). 0 = per-dataset default.
    synthetic_size: int = 0
    # sequence length for LM models (lm_*): batches are (seq_len + 1)
    # token windows, position t predicting t + 1
    seq_len: int = 256
    # rematerialize LM block activations in backward (jax.checkpoint):
    # ~1/3 more FLOPs for O(depth) less activation memory; with the
    # streaming flash kernels this is what takes lm_base from seq 16k to
    # 32k on one v5e chip (BENCHMARKS.md)
    remat: bool = False
    # LM position encoding: "learned" absolute table (GPT-2 style) or
    # "rope" rotary Q/K (relative positions; ops/rope.py)
    pos_emb: str = "learned"
    # share the token embedding with the output projection (GPT-2 weight
    # tying): removes the (d, vocab) lm_head parameter
    tied_embeddings: bool = False

    # optimization (reference defaults: origin_main.py:37-52, ddp_main.py:125)
    epochs: int = 3
    batch_size: int = 32          # per data-parallel replica, like the reference
    learning_rate: float = 1e-4
    optimizer: str = "sgd"
    momentum: float = 0.0
    # clip gradients to this global L2 norm before the optimizer update
    # (0 = off) — the standard transformer-training stabilizer
    clip_norm: float = 0.0
    # residual-branch + embedding dropout for the transformer families
    # (ViT, LM); 0 = off. Masks are keyed on the global step (train/steps.py
    # _step_rngs): deterministic across resume and driver variants.
    dropout: float = 0.0
    weight_decay: float = 0.0
    lr_schedule: str = "constant"     # constant | cosine | warmup_cosine
    warmup_steps: int = 0
    scale_lr_by_replicas: bool = False  # parity default: False (README.md:506)
    label_smoothing: float = 0.0
    # gradient accumulation: average grads over k micro-steps before the
    # optimizer applies (optax.MultiSteps) — large effective batches
    # without the memory; 1 = off. Decaying lr schedules advance once per
    # optimizer APPLY; make_optimizer divides their horizons (total and
    # warmup) by k so decay still completes over the run
    accum_steps: int = 1

    # rng (reference: 3407 + rank, ddp_main.py:76-80)
    seed: int = 3407

    # precision
    precision: str = "fp32"

    # distribution
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    # ZeRO-3: shard params + optimizer state over the 'data' axis (the
    # reference replicates both on every process — SURVEY §2.3)
    fsdp: bool = False
    # sequence-parallel attention scheme when mesh.seq > 1
    sp_impl: str = "ring"              # ring | ulysses
    # local attention kernel: "xla" (compiler-fused) | "flash" (the Pallas
    # streaming kernels, ops/flash_attention.py) — both compose with
    # ring/ulysses — | "auto": each attention layer picks from its shape
    # at trace time (models/vit.py SelfAttention.resolve_attn_impl): the
    # whole-sequence Pallas kernels for short unsharded sequences on a
    # TPU (ViT's 196 patches), "xla" everywhere else
    attn_impl: str = "auto"
    # GPipe microbatches per step when mesh.pipe > 1
    num_microbatches: int = 4
    # pipeline schedule: "gpipe" (autodiff-of-scan; activation memory grows
    # O(M + P)) | "1f1b" (LM only; explicit interleaved backward with an
    # O(P) input stash — parallel/pipeline_1f1b.py)
    pipe_schedule: str = "gpipe"
    # virtual pipeline chunks per device (interleaved schedule only)
    num_virtual: int = 2
    # on-device input augmentation (random crop + horizontal flip inside
    # the jitted train step, ops/augment.py); image models only
    augment: bool = False
    # which augmentation when --augment is set: "crop_flip" (pad-crop +
    # flip, the CIFAR/MNIST rung) or "rrc" (random resized crop, the
    # ImageNet rung — ResNet-50/224)
    augment_kind: str = "crop_flip"

    # encoder layers as fused Pallas kernels (ops/fused_encoder.py):
    # "auto" (default) = the model picks them whenever its constraints
    # hold (models/vit.py EncoderBlock._auto_fuse); "on"/True = force,
    # raising on unsupported configs; "off"/False = per-op pipeline
    fused_encoder: object = "auto"  # "auto" | "on"/True | "off"/False
    # MoE expert count when mesh.expert > 1 (0 = auto: 8 rounded up to a
    # multiple of the expert axis)
    num_experts: int = 0
    # MoE routing scheme: "topk" (tokens choose experts) |
    # "expert_choice" (experts choose tokens; ops/moe.py)
    moe_router: str = "topk"
    # attention head count override for transformer models (0 = model
    # default); tensor parallelism shards heads, so heads % tensor == 0
    num_heads: int = 0
    # multi-host rendezvous (replaces MASTER_ADDR/MASTER_PORT, ddp_main.py:61-62)
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None

    # checkpointing (reference saves once at end, no resume: origin_main.py:113)
    checkpoint_dir: Optional[str] = None
    checkpoint_every_epochs: int = 0   # 0 = only at end
    checkpoint_every_steps: int = 0    # 0 = off (periodic mid-epoch saves)
    # periodic saves write on a background thread (gather fences the
    # device, serialization overlaps the next steps); the end-of-fit save
    # is always synchronous, and multi-host saves are always synchronous
    # (collective ordering)
    checkpoint_async: bool = True
    resume: bool = False

    # failure detection / elastic recovery (absent in reference, SURVEY §5.3)
    max_restarts: int = 0              # checkpoint-based restarts on failure
    watchdog_timeout_s: float = 0.0    # 0 = no step watchdog
    # force a device-progress probe every N steps — the watchdog beats only
    # on CONFIRMED device progress, never on dispatch (async dispatch
    # outruns a hung collective). A probe fetches the OLDEST unconfirmed
    # step's metrics scalar (one rung past the last confirmed point), so it
    # blocks for at most ~one step of device time even when the host has
    # dispatched far ahead — the watchdog fires only when NO step completes
    # within the timeout, not when the host merely outruns a healthy
    # device. Independent of N, a probe also fires whenever half the
    # watchdog timeout passes without one, so slow steps can't starve the
    # watchdog into a spurious firing. 0 = time-based probing only.
    watchdog_probe_every_steps: int = 50
    sync_check_every_steps: int = 0    # 0 = no cross-host driver sync checks

    # eval / logging
    max_steps_per_epoch: int = 0       # 0 = full epoch; >0 caps steps (smoke runs)
    eval_every_epochs: int = 0         # 0 = only at end (reference behavior)
    log_every_steps: int = 100
    profile_dir: Optional[str] = None
    # write a Chrome trace-event JSON of the host-side step phases
    # (data / dispatch / block / checkpoint spans, utils/trace.py) at
    # fit end — open in Perfetto; process 0 only. Complements
    # profile_dir: that traces the DEVICE, this traces the driver.
    trace_out: Optional[str] = None
    # append one JSON record per logged train step / eval / run summary
    # (process 0 only) — machine-readable training curves next to the
    # human stdout logs; records carry the global step, so resumed runs
    # append seamlessly
    metrics_file: Optional[str] = None
    # ---- live telemetry plane (utils/telemetry.py; process 0 only)
    # bind /metrics (Prometheus exposition of step-time/MFU/anomaly
    # metrics), /healthz, /flight (rolling step-time percentiles) on
    # this port for the whole fit (0 = ephemeral, logged at startup)
    metrics_port: Optional[int] = None
    # stream trace events + flight/step records + periodic metrics
    # snapshots as line-delimited JSONL WHILE training — a killed run
    # still leaves a parseable file (the exit-time trace_out dump
    # leaves nothing)
    telemetry_out: Optional[str] = None
    # SLO config (serve/slo.py SLOConfig JSON or path): a burn-rate
    # watchdog over the straggler detector's verdicts — sustained
    # anomalous step times trip an alert into the telemetry stream
    slo: Optional[str] = None
    # push-alert sinks ("kind:target" specs, serve/slo.py AlertSinkSpec:
    # command:... / webhook:http://... / jsonl:path): SLO trip/resolve
    # edges are PUSHED to an operator, with per-sink retry backoff and a
    # dead-sink breaker — a burning SLO that only lands in a scrape
    # endpoint pages nobody
    alert_sinks: Optional[tuple] = None

    # input pipeline
    loader_backend: str = "auto"       # auto | native | python
    prefetch: int = 2
    # K optimizer steps per jitted call (lax.scan over stacked batches);
    # amortizes host dispatch + H2D latency for small models. 1 = off.
    # -1 = the whole epoch per call (device-resident data only: the scan
    # gathers batches from HBM, so no per-chunk feeding is needed).
    steps_per_call: int = 1
    # where the corpus lives during training: "host" streams batches (the
    # DataLoader/prefetch path), "device" uploads the whole uint8 corpus to
    # HBM once and sends only per-epoch index grids (single-process only),
    # "auto" picks device when single-process and the corpus fits
    # resident_max_bytes. Same batches and math either way; agreement is
    # to float noise (different XLA programs associate reductions
    # differently — tests/test_resident.py pins the bound).
    data_placement: str = "auto"       # auto | host | device
    resident_max_bytes: int = 256 * 1024 * 1024
    # persistent XLA compilation cache (utils/backend.py
    # enable_compile_cache): "auto" = $JAX_COMPILATION_CACHE_DIR when set,
    # else a fixed git-ignored directory inside the checkout; "off"
    # disables (tests that count compiles).
    compilation_cache: str = "auto"
    shuffle_eval: bool = False  # the reference baseline shuffles eval; don't (SURVEY §2.5)

    def __post_init__(self):
        if self.steps_per_call == -1 or self.steps_per_call >= 1:
            return
        raise ValueError(
            f"steps_per_call={self.steps_per_call}: must be >= 1 (K steps "
            "per dispatch) or exactly -1 (whole epoch per dispatch, "
            "device-resident data only)"
        )

    def precision_policy(self) -> PrecisionPolicy:
        return PrecisionPolicy.from_name(self.precision)

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)
