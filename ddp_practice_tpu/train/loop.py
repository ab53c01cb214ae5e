"""Training driver: the main() of the framework.

Mirrors the reference's driver contract (ddp_main.py:115-170): epoch loop
with per-epoch reshuffle (set_epoch, ddp_main.py:160), eval participated in
by every process with globally reduced counts (ddp_main.py:108-109), side
effects (prints, checkpoint) on process 0 only (ddp_main.py:158-169), and
the three parity-visible outputs: epoch banners, "Accuracy is XX.XX%", and
final elapsed seconds (origin_main.py:109,81,121).

TPU-first differences: one process per host; a Mesh instead of ranks; the
step is one compiled XLA program; throughput is reported as images/sec/chip
(the BASELINE.json north-star metric).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ddp_practice_tpu import checkpoint as ckpt
from ddp_practice_tpu.config import MeshConfig, TrainConfig
from ddp_practice_tpu.data import DataLoader, ShardSpec, load_dataset
from ddp_practice_tpu.data.loader import prefetch_to_device
from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.parallel import dist
from ddp_practice_tpu.parallel.mesh import batch_sharding, build_mesh, shard_state
from ddp_practice_tpu.parallel.ring import set_current_mesh
from ddp_practice_tpu.parallel.sharding_rules import param_sharding_rules
from ddp_practice_tpu.train.state import create_state, make_optimizer
from ddp_practice_tpu.utils import backend
from ddp_practice_tpu.utils.logging import get_logger, main_process_only
from ddp_practice_tpu.utils.profiling import profile_region, step_annotation
from ddp_practice_tpu.utils.timing import Timer
from ddp_practice_tpu.utils.trace import NULL_SPAN as _NULL_SPAN

log = get_logger()


def _future_ready(x) -> bool:
    """Best-effort completion check for a device scalar (False when the
    runtime can't say — the probe then just confirms this older rung)."""
    try:
        return bool(x.is_ready())
    except (AttributeError, RuntimeError):
        return False

# side effects on process 0 only (ddp_main.py:158-169); collectives and
# device work above these gates still run on every process
info0 = main_process_only(log.info)
warn0 = main_process_only(log.warning)


class Trainer:
    def __init__(self, config: TrainConfig, tracer=None):
        """`tracer`: a utils/trace.py TraceRecorder to record the
        step-phase spans into (a caller that drives `train_epoch()`
        itself hands one in and reads it back through `self.tracer`);
        None leaves it to `trace_out` / `telemetry_out`."""
        self.config = config
        backend.enable_compile_cache(config.compilation_cache)
        dist.initialize(
            config.coordinator_address, config.num_processes, config.process_id
        )
        policy = config.precision_policy()
        self.mesh = build_mesh(config.mesh)
        set_current_mesh(self.mesh)
        mesh_shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        self.dp = mesh_shape.get(MeshConfig.AXIS_DATA, 1)
        self.sp = mesh_shape.get(MeshConfig.AXIS_SEQ, 1)
        self.pp = mesh_shape.get(MeshConfig.AXIS_PIPE, 1)

        # data — per-replica batch size x data-parallel degree = global batch
        # (the reference's "batch 32 per process" contract, README.md:506)
        self.global_batch = config.batch_size * self.dp
        shard = ShardSpec(dist.process_index(), dist.process_count())
        # lm_* models train on token streams (data/lm_corpus.py), the image
        # families on the dataset registry; both honor the same sampler
        # contract (seed/epoch permutation, per-process shards)
        self.task = "lm" if config.model.lower().startswith("lm") else "image"
        if self.task == "lm":
            self.train_ds = self.eval_ds = None
            (self.train_loader, self.eval_loader,
             self._vocab_size) = self._build_lm_data(shard)
        else:
            self.train_ds = load_dataset(
                config.dataset, config.data_dir, "train", seed=config.seed,
                synthetic_size=config.synthetic_size or None,
            )
            self.eval_ds = load_dataset(
                config.dataset, config.data_dir, "test", seed=config.seed,
                synthetic_size=(max(config.synthetic_size // 6, 1)
                                if config.synthetic_size else None),
            )
            self.train_loader = DataLoader(
                self.train_ds,
                global_batch_size=self.global_batch,
                shard=shard,
                seed=config.seed,
                shuffle=True,
                backend=config.loader_backend,
            )
            self.eval_loader = DataLoader(
                self.eval_ds,
                global_batch_size=self.global_batch,
                shard=shard,
                seed=config.seed,
                shuffle=config.shuffle_eval,
                backend=config.loader_backend,
            )

        # model
        model_kwargs = {}
        if config.num_heads:
            if not config.model.startswith(("vit", "lm")):
                raise ValueError(
                    f"--num_heads applies to transformer models, not "
                    f"{config.model!r}"
                )
            model_kwargs["num_heads"] = config.num_heads
        if config.dropout:
            if not 0.0 < config.dropout < 1.0:
                # rate >= 1 would silently zero every residual branch;
                # negative rates silently rescale activations
                raise ValueError(
                    f"--dropout must be in [0, 1), got {config.dropout}"
                )
            if config.model not in ("vit_tiny", "vit_base") and not (
                config.model.startswith("lm") and config.model != "lm_pipe"
            ):
                raise ValueError(
                    "--dropout is wired for the dense transformer families "
                    f"(vit_tiny, vit_base, lm_tiny/lm_base), not "
                    f"{config.model!r}"
                )
            model_kwargs["dropout_rate"] = config.dropout
        if self.sp > 1:
            model_kwargs["seq_axis"] = MeshConfig.AXIS_SEQ
            model_kwargs["sp_impl"] = config.sp_impl
        if config.attn_impl != "auto":
            # only attention models accept this; a conv model raises loudly
            # rather than silently ignoring the requested kernel ("auto"
            # asks for none: the attention models resolve it themselves)
            model_kwargs["attn_impl"] = config.attn_impl
        fused_req = config.fused_encoder
        from ddp_practice_tpu.models import accepts_fused

        if fused_req in (True, "on"):
            if not accepts_fused(config.model):
                raise ValueError(
                    "--fused on is the small-d fused encoder-layer kernel "
                    "(ops/fused_encoder.py) for the dense transformer "
                    f"families, not {config.model!r} (conv/pipelined/"
                    "ViT-MoE keep their paths). Note wide models "
                    "(vit_base, lm_base) will then fail the kernel's VMEM "
                    "weight-residency check loudly, and lm_tiny needs "
                    "--num_heads 4 (head_dim must be a multiple of 64)"
                )
            model_kwargs["fused"] = True
        elif fused_req in (False, "off"):
            # the dense transformer families default to fused="auto";
            # an explicit off must override that, but only models that
            # take the kwarg can receive it (declared at registration —
            # models/__init__.py accepts_fused)
            if accepts_fused(config.model):
                model_kwargs["fused"] = False
        elif fused_req != "auto":
            raise ValueError(
                f"fused_encoder={fused_req!r} (want 'auto'|'on'|'off')"
            )
        # "auto": nothing to pass — the models default to fused="auto"
        # and resolve per block (models/vit.py EncoderBlock._auto_fuse)
        if config.pipe_schedule != "gpipe":
            # same fail-loudly convention as the other pipeline flags: a
            # schedule request on a pipe-less mesh, or for a model family
            # that only implements GPipe, must not train something else
            if self.pp <= 1:
                raise ValueError(
                    f"--pipe_schedule {config.pipe_schedule} needs a "
                    "pipeline mesh axis (--pipe > 1)"
                )
            if not config.model.startswith("lm_"):
                raise ValueError(
                    f"--pipe_schedule {config.pipe_schedule} is an LM "
                    "pipeline feature (models/pipeline_lm.py); "
                    f"{config.model} schedules with GPipe only"
                )
        if self.pp > 1:
            # pipeline-capable models take the stage count from the mesh; a
            # non-pipeline model with mesh.pipe > 1 fails loudly here rather
            # than silently training unpipelined
            model_kwargs["num_stages"] = self.pp
            model_kwargs["num_microbatches"] = config.num_microbatches
            if config.pipe_schedule != "gpipe":
                model_kwargs["schedule"] = config.pipe_schedule
            if config.pipe_schedule == "interleaved":
                model_kwargs["num_virtual"] = config.num_virtual
            # tensor parallelism composes: the pipeline shard_map is manual
            # over 'pipe'/'data' only, so the _vit_pipe_rule tensor specs
            # ride GSPMD inside each stage (parallel/pipeline.py)
        self.ep = mesh_shape.get(MeshConfig.AXIS_EXPERT, 1)
        if self.ep > 1 or config.num_experts:
            # expert count must divide evenly over the 'expert' axis; default
            # rounds the model's 8 up to the nearest multiple of the axis
            n_exp = config.num_experts or ((8 + self.ep - 1) // self.ep) * self.ep
            if n_exp % self.ep != 0:
                raise ValueError(
                    f"num_experts={n_exp} not divisible by expert axis {self.ep}"
                )
            model_kwargs["num_experts"] = n_exp
        if config.moe_router != "topk":
            if config.model not in ("vit_tiny_moe", "lm_moe"):
                raise ValueError(
                    "--moe_router applies to the MoE model families "
                    f"(vit_tiny_moe, lm_moe), not {config.model!r}"
                )
            model_kwargs["moe_router"] = config.moe_router
            # expert choice fills buffers by construction: cf 1.0 IS
            # "executed == active FLOPs". The registries' token-choice
            # headroom defaults (lm_moe 2.0) would silently double the
            # expert compute here.
            model_kwargs.setdefault("capacity_factor", 1.0)
        if self.task == "lm":
            model_kwargs["vocab_size"] = self._vocab_size
            model_kwargs["max_len"] = config.seq_len
            if config.remat:
                model_kwargs["remat"] = True
            if config.pos_emb != "learned":
                model_kwargs["pos_emb"] = config.pos_emb
            if config.tied_embeddings:
                if config.model == "lm_pipe":
                    raise ValueError(
                        "--tied is not wired for the pipelined LM — use "
                        "lm_tiny/lm_base for weight tying"
                    )
                model_kwargs["tied_embeddings"] = True
            if self.pp > 1 and config.model != "lm_pipe":
                raise ValueError(
                    "pipeline parallelism for language models uses the "
                    "stage-sharded variant: --model lm_pipe"
                )
            self.model = create_model(
                config.model, policy=policy, **model_kwargs
            )
        elif config.pos_emb != "learned":
            raise ValueError(
                "--pos_emb applies to the LM family (lm_*); "
                f"{config.model!r} keeps its own position scheme"
            )
        elif config.tied_embeddings:
            raise ValueError(
                "--tied (embedding/output weight tying) applies to the LM "
                f"family (lm_*), not {config.model!r}"
            )
        elif config.remat:
            raise ValueError(
                "remat is only wired for the LM family (lm_*) — the image "
                "models at these sizes gain nothing from rematerialization"
            )
        else:
            self.model = create_model(
                config.model,
                num_classes=self.train_ds.num_classes,
                policy=policy,
                axis_name=None,  # GSPMD: batch-axis stats are global by sharding
                **model_kwargs,
            )
        tp = mesh_shape.get(MeshConfig.AXIS_TENSOR, 1)
        if tp > 1:
            # fail with the fix named, not a pjit divisibility traceback:
            # the Megatron rules shard the head dim of qkv/out kernels
            heads = getattr(self.model, "num_heads", None) or getattr(
                getattr(self.model, "block", None), "num_heads", None
            )
            if heads is not None and heads % tp:
                raise ValueError(
                    f"tensor parallelism shards attention heads: "
                    f"{config.model} has {heads} heads, not divisible by "
                    f"--tensor {tp} — pass --num_heads (e.g. "
                    f"{((heads // tp) + 1) * tp}) or a different degree"
                )
        self.tx = make_optimizer(config, self.train_loader.steps_per_epoch)

        # state, sharded at init (params materialize directly on the mesh)
        rng = jax.random.PRNGKey(config.seed)
        # init with the global batch shape: sequence-parallel models open a
        # shard_map island whose dims must divide the mesh even during init
        if self.task == "lm":
            sample = jnp.zeros((self.global_batch, config.seq_len), jnp.int32)
        else:
            sample = jnp.zeros(
                (self.global_batch,) + self.train_ds.image_shape, jnp.float32
            )

        def init_fn(r):
            return create_state(self.model, self.tx, rng=r, sample_input=sample)

        from ddp_practice_tpu.models.vit import resolved_attn_impls

        with resolved_attn_impls() as resolved:
            abstract = jax.eval_shape(init_fn, rng)
        # which attention core the step programs run, as SelfAttention
        # resolved it for this shape and mesh (None: a model without one);
        # an attribute of every train_epoch span
        self.attn_impl = "+".join(sorted(resolved)) or None
        rules = param_sharding_rules(config.model)
        if config.fsdp:
            from ddp_practice_tpu.parallel.fsdp import fsdp_rules

            rules = fsdp_rules(self.dp, rules)
        self.state_shardings = shard_state(abstract, self.mesh, rules)
        self.state = jax.jit(init_fn, out_shardings=self.state_shardings)(rng)

        self.batch_shardings = batch_sharding(self.mesh)
        # one construction block for both tasks: only the factories differ
        # (the step signatures are deliberately uniform, train/steps.py)
        if self.task == "lm":
            from ddp_practice_tpu.train.steps import (
                make_chunked_lm_train_step as chunk_factory,
                make_lm_eval_step as eval_factory,
                make_lm_train_step as train_factory,
            )
        else:
            from ddp_practice_tpu.train.steps import (
                make_chunked_train_step as chunk_factory,
                make_eval_step as eval_factory,
                make_train_step as train_factory,
            )
        common = dict(
            mesh=self.mesh,
            state_shardings=self.state_shardings,
            batch_shardings=self.batch_shardings,
        )
        step_kwargs = dict(
            label_smoothing=config.label_smoothing, seed=config.seed,
        )
        if config.augment:
            if self.task == "lm":
                raise ValueError(
                    "--augment is image-input augmentation (random crop + "
                    "flip, ops/augment.py); it does not apply to token "
                    "streams"
                )
            step_kwargs["augment"] = config.augment_kind
        self.train_step = train_factory(
            self.model, self.tx, **step_kwargs, **common,
        )
        self.chunk_step = None
        if config.steps_per_call > 1:
            from ddp_practice_tpu.train.steps import stack_shardings

            self.stacked_shardings = stack_shardings(self.batch_shardings)
            self.chunk_step = chunk_factory(
                self.model, self.tx,
                num_steps=config.steps_per_call,
                **step_kwargs, **common,
            )
        self.eval_step = eval_factory(self.model, **common)
        # device-resident data: corpus uploaded to HBM once, epochs driven
        # by index grids alone (no per-batch H2D) — see _train_epoch_resident
        self.resident_train_step = None
        self.resident_eval_step = None
        if self._use_resident_data():
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ddp_practice_tpu.parallel.mesh import replicated
            from ddp_practice_tpu.train.steps import (
                make_resident_eval_step,
                make_resident_train_step,
            )

            rep = replicated(self.mesh)
            self._grid_sharding = NamedSharding(
                self.mesh, P(None, MeshConfig.AXIS_DATA)
            )
            if self.task == "lm":
                from ddp_practice_tpu.train.steps import (
                    make_resident_lm_eval_step,
                    make_resident_lm_train_step,
                )

                self._train_data = {
                    "tokens": jax.device_put(
                        np.asarray(
                            self.train_loader.corpus.tokens, np.int32
                        ),
                        rep,
                    ),
                }
                self._eval_data = {
                    "tokens": jax.device_put(
                        np.asarray(self.eval_loader.corpus.tokens, np.int32),
                        rep,
                    ),
                }
                window = config.seq_len + 1
                self.resident_train_step = make_resident_lm_train_step(
                    self.model,
                    self.tx,
                    window=window,
                    label_smoothing=config.label_smoothing,
                    seed=config.seed,
                    mesh=self.mesh,
                    state_shardings=self.state_shardings,
                )
                self.resident_eval_step = make_resident_lm_eval_step(
                    self.model,
                    window=window,
                    mesh=self.mesh,
                    state_shardings=self.state_shardings,
                )
            else:
                self._train_data = {
                    "image": jax.device_put(
                        np.asarray(self.train_ds.images), rep
                    ),
                    "label": jax.device_put(
                        np.asarray(self.train_ds.labels), rep
                    ),
                }
                self._eval_data = {
                    "image": jax.device_put(
                        np.asarray(self.eval_ds.images), rep
                    ),
                    "label": jax.device_put(
                        np.asarray(self.eval_ds.labels), rep
                    ),
                }
                self.resident_train_step = make_resident_train_step(
                    self.model,
                    self.tx,
                    label_smoothing=config.label_smoothing,
                    seed=config.seed,
                    augment=(config.augment_kind if config.augment
                             else False),
                    mesh=self.mesh,
                    state_shardings=self.state_shardings,
                )
                self.resident_eval_step = make_resident_eval_step(
                    self.model,
                    mesh=self.mesh,
                    state_shardings=self.state_shardings,
                )
        elif config.steps_per_call == -1:
            raise ValueError(
                "steps_per_call=-1 (whole epoch per dispatch) needs "
                "device-resident data; got data_placement="
                f"{config.data_placement!r}"
                + (" in a multi-process run" if dist.process_count() > 1 else "")
                + " — use data_placement='device' (single process) or a "
                "positive steps_per_call"
            )
        self.chunk_eval_step = None
        if config.steps_per_call > 1 and self.task == "image":
            from ddp_practice_tpu.train.steps import make_chunked_eval_step

            self.chunk_eval_step = make_chunked_eval_step(
                self.model,
                num_steps=config.steps_per_call,
                mesh=self.mesh,
                state_shardings=self.state_shardings,
                batch_shardings=self.batch_shardings,
            )

        if config.resume and config.checkpoint_dir and ckpt.exists(config.checkpoint_dir):
            self.state = ckpt.restore(
                config.checkpoint_dir, self.state, shardings=self.state_shardings
            )
            info0("resumed from %s at step %d",
                  config.checkpoint_dir, int(self.state.step))

        self._train_images = 0
        self._train_seconds = 0.0
        self.eval_perplexity = None  # set by _evaluate_lm
        # host-side step-phase tracing (utils/trace.py): one
        # `train_epoch` span per epoch whose children are epoch_open /
        # data / dispatch / after_group / block (and checkpoint), into
        # the same recorder family the serving stack uses; `save_trace`
        # writes it as Chrome trace JSON (fit() does at its end). The
        # recorder mirrors every span into the profiler as
        # `train:<name>` (set_annotate), so a device profile carries
        # them on its own clock. None = zero overhead. `self.tracer` is
        # the public name; the attribute stays, the benchmark's train
        # driver reads it.
        self._tracer = tracer
        # without one handed in, a recorder exists for EITHER consumer:
        # --trace_out wants the exit-time dump, --telemetry_out wants
        # the live stream (the exporter attaches as sink below);
        # process 0 only
        if tracer is None and (config.trace_out or config.telemetry_out) \
                and dist.process_index() == 0:
            from ddp_practice_tpu.utils.trace import TraceRecorder

            self._tracer = TraceRecorder()
        if self._tracer is not None:
            self._tracer.set_process_name(0, "train")
            self._tracer.set_thread_name(0, 0, "steps")
            self._tracer.set_annotate(jax.profiler.TraceAnnotation, "train")
        # XLA:CPU's in-process collective rendezvous can deadlock when more
        # than one execution of a collective-bearing program is in flight
        # (device threads join different run_ids). On the CPU dev platform,
        # serialize step dispatch; on TPU, keep async dispatch (collectives
        # ride ICI and overlap is the point).
        self._serialize_steps = not backend.on_tpu()
        self._watchdog = None
        self._pending_save = None  # in-flight async checkpoint write
        self._metrics_fh = None
        if config.metrics_file and dist.process_index() == 0:
            import os

            d = os.path.dirname(config.metrics_file)
            if d:
                os.makedirs(d, exist_ok=True)
            # append: records carry the global step, so a resumed run's
            # curve continues the same file
            self._metrics_fh = open(config.metrics_file, "a")
        # ladder of per-step scalar futures (see _probe_if_due)
        from collections import deque

        self._pending = deque()

        # ---- live telemetry plane (utils/telemetry.py; process 0 only):
        # step-time histogram, per-step MFU gauge (utils/flops.py
        # analytic count / measured step time / chip peak), rolling-MAD
        # straggler detector, optionally exported as streaming JSONL
        # (--telemetry_out) and scraped over HTTP (--metrics_port), with
        # an SLO burn-rate watchdog (--slo) over the detector's verdicts
        # — the same plane the serving stack exposes (serve/slo.py).
        self._telemetry = None
        self._tele_server = None
        self._train_registry = None
        self._anomaly = None
        self._slo = None
        self._last_group_t = None
        plane_on = (config.metrics_port is not None
                    or config.telemetry_out or config.slo)
        if plane_on and dist.process_index() == 0:
            from ddp_practice_tpu.utils.flops import chip_peak_flops
            from ddp_practice_tpu.utils.metrics import MetricsRegistry
            from ddp_practice_tpu.utils.telemetry import (
                StepAnomalyDetector,
                TelemetryExporter,
                TelemetryServer,
            )

            reg = MetricsRegistry()
            self._train_registry = reg
            self._step_time = reg.histogram("train_step_time_s")
            self._mfu_gauge = reg.gauge("train_mfu")
            self._anomaly_ctr = reg.counter("train_step_anomalies_total")
            self._anomaly = StepAnomalyDetector()
            self._flops_per_step = self._estimate_flops_per_step()
            self._peak_flops = chip_peak_flops(
                jax.devices()[0].device_kind
            )
            if config.telemetry_out:
                self._telemetry = TelemetryExporter(
                    config.telemetry_out, registry=reg
                )
                if self._tracer is not None:
                    self._telemetry.attach(self._tracer)
            if config.metrics_port is not None:
                self._tele_server = TelemetryServer(
                    registry=reg,
                    # one lane; DEGRADED while the step-time SLO burns
                    health_fn=lambda: {0: (
                        "degraded"
                        if self._slo is not None and self._slo.active
                        else "healthy"
                    )},
                    flight_fn=lambda: {
                        "step_time_s": self._step_time.summary(),
                    },
                    port=config.metrics_port,
                )
                info0("telemetry: /metrics /healthz /flight on port %d",
                      self._tele_server.port)
            if config.slo:
                from ddp_practice_tpu.serve.slo import (
                    AlertSinks,
                    SLOConfig,
                    SLOWatchdog,
                )

                sinks = (AlertSinks(config.alert_sinks, registry=reg)
                         if config.alert_sinks else None)
                self._slo = SLOWatchdog(
                    SLOConfig.from_json(config.slo), registry=reg,
                    tracer=self._tracer, telemetry=self._telemetry,
                    sinks=sinks, pid=0,
                )

    def _estimate_flops_per_step(self) -> Optional[float]:
        """Analytic train FLOPs per optimizer step (utils/flops.py) for
        the MFU gauge — best-effort: None (gauge stays 0) when the
        architecture has no analytic model here."""
        cfg = self.config
        try:
            if self.task == "lm":
                from ddp_practice_tpu.utils.flops import (
                    lm_train_flops_per_token,
                )

                m = self.model
                per_tok = lm_train_flops_per_token(
                    hidden_dim=m.hidden_dim, depth=m.depth,
                    mlp_dim=m.mlp_dim, vocab_size=m.vocab_size,
                    seq_len=cfg.seq_len,
                )
                return per_tok * cfg.seq_len * self.global_batch
            from ddp_practice_tpu.utils.flops import train_flops_per_image

            kw = {}
            if cfg.model.startswith("vit"):
                m = self.model
                kw = dict(patch_size=m.patch_size, hidden_dim=m.hidden_dim,
                          depth=m.depth, mlp_dim=m.mlp_dim)
            f = train_flops_per_image(
                cfg.model, tuple(self.train_ds.image_shape),
                self.train_ds.num_classes, **kw,
            )
            return f * self.global_batch if f else None
        except (AttributeError, TypeError, ValueError):
            return None

    def _observe_group(self, k: int) -> None:
        """Telemetry per dispatch group: step-time histogram, rolling-
        MAD straggler verdict (counted, traced, streamed), per-step MFU
        gauge, SLO feed. Host wall time between group boundaries — a
        straggler is a straggler whether the time went to the device,
        the data pipeline, or dispatch."""
        import time as _time

        now = _time.monotonic()
        last, self._last_group_t = self._last_group_t, now
        if last is None or k <= 0:
            return
        step_s = (now - last) / k
        self._step_time.observe(step_s)
        anomalous = self._anomaly.observe(step_s)
        if anomalous:
            self._anomaly_ctr.inc()
            warn0("step-time anomaly: %.3fs/step vs rolling median "
                  "(straggler?)", step_s)
            if self._tracer is not None and self._tracer.enabled:
                self._tracer.instant("step_anomaly", pid=0, tid=0,
                                     step_s=round(step_s, 6))
            if self._telemetry is not None:
                self._telemetry.emit("anomaly", step_s=step_s)
        if self._flops_per_step and self._peak_flops:
            self._mfu_gauge.set(
                self._flops_per_step / step_s
                / (self._peak_flops * jax.device_count())
            )
        if self._slo is not None:
            # the straggler SLO: an anomalous step is the bad event
            self._slo.observe_event(
                t=now, status="error" if anomalous else "eos"
            )
            self._slo.evaluate(now)

    @property
    def tracer(self):
        """The TraceRecorder the step-phase spans go to, or None."""
        return self._tracer

    def _tspan(self, name: str, **attrs):
        """A step-phase span on the train lane, or a no-op without a
        tracer (one attribute test on the hot path)."""
        if self._tracer is None:
            return _NULL_SPAN
        return self._tracer.span(name, pid=0, tid=0, **attrs)

    def _traced_batches(self, items):
        """Wrap the prefetch stream so the time spent WAITING for the
        next batch (host data stall) shows up as "data" spans."""
        it = iter(items)
        while True:
            with self._tspan("data"):
                try:
                    item = next(it)
                except StopIteration:
                    return
            yield item

    def save_trace(self) -> None:
        """Write the recorder to `config.trace_out` (Chrome trace JSON).
        fit() calls it at its end; a caller of `train_epoch()` alone
        calls it itself."""
        if self._tracer is None or not self.config.trace_out:
            return  # stream-only runs (--telemetry_out) have no dump
        try:
            self._tracer.save(self.config.trace_out)
            info0("wrote host trace to %s (%d events)",
                  self.config.trace_out, len(self._tracer))
        except OSError:
            log.exception("could not write --trace_out")

    def _track(self, scalar) -> None:
        """Record one step's scalar metric future on the progress ladder."""
        if self._watchdog is not None:
            self._pending.append(scalar)

    def _probe_if_due(self, prev: int, cur: int) -> None:
        """Watchdog probe on CONFIRMED device progress, when due: either the
        starvation rule (half the timeout without a beat) or a step-count
        boundary of watchdog_probe_every_steps crossed between prev and cur
        (boundary crossing, not modulo: chunked steps advance by K).

        The probe fetches the OLDEST unconfirmed step's scalar, never the
        newest: under async dispatch the host runs arbitrarily far ahead of
        the device, and fetching the newest step's metrics would block on
        the entire in-flight backlog — a healthy-but-behind device would
        then look hung and be killed. Fetching one rung past the last
        confirmed point blocks for at most one step of device time, so the
        watchdog fires exactly when NO step completes within the timeout.
        Already-completed rungs are skipped via is_ready() (if is_ready
        under-reports, probes just re-confirm older rungs — detection
        stays monotone, only delayed)."""
        n = self.config.watchdog_probe_every_steps
        if self._watchdog is None or not self._pending:
            return
        if self._watchdog.probe_due() or (n and prev // n != cur // n):
            while len(self._pending) > 1 and _future_ready(self._pending[0]):
                self._pending.popleft()
            self._watchdog.probe(self._pending.popleft())

    def _drain_pending(self) -> None:
        """Confirm every remaining ladder rung (beating on each) before an
        end-of-phase fence: the monolithic block_until_ready/device_get at
        epoch or eval end waits on the whole in-flight backlog, and without
        intermediate beats a healthy-but-behind device would look hung."""
        if self._watchdog is None:
            self._pending.clear()
            return
        while self._pending:
            self._watchdog.probe(self._pending.popleft())

    # ------------------------------------------------------------------ #

    def _build_lm_data(self, shard):
        """Token loaders for the LM task: dataset='text' reads bytes from
        data_dir (file or directory), anything else (or missing files)
        falls back to the deterministic synthetic Markov corpus. The last
        10% of the token stream is the held-out eval split."""
        from ddp_practice_tpu.data.lm_corpus import (
            LMDataLoader,
            TokenCorpus,
            load_text_corpus,
            synthetic_token_corpus,
        )

        cfg = self.config
        window = cfg.seq_len + 1
        batch_tokens = self.global_batch * window
        corpus = None
        if cfg.dataset == "text":
            try:
                corpus = load_text_corpus(cfg.data_dir)
            except FileNotFoundError:
                warn0(
                    "no readable files under %s — using the synthetic "
                    "Markov corpus", cfg.data_dir,
                )
        if corpus is None:
            # the synthetic default scales with the global batch so both
            # splits always hold >= one batch of windows on any mesh size
            corpus = synthetic_token_corpus(
                cfg.synthetic_size or max(262144, 16 * batch_tokens),
                seed=cfg.seed,
            )
        # eval = 10% of the stream, but never less than one global batch
        n_eval = max(len(corpus) - int(len(corpus) * 0.9), batch_tokens)
        n_train = len(corpus) - n_eval
        if n_train < batch_tokens:
            raise ValueError(
                f"corpus {corpus.name} has {len(corpus)} tokens — too few "
                f"for one train + one eval batch of {batch_tokens} tokens "
                f"each (global_batch {self.global_batch} x window {window}); "
                "grow the corpus or shrink batch_size/seq_len"
            )
        train_c = TokenCorpus(
            corpus.tokens[:n_train], corpus.vocab_size, f"{corpus.name}-train"
        )
        eval_c = TokenCorpus(
            corpus.tokens[n_train:], corpus.vocab_size, f"{corpus.name}-eval"
        )

        def make(c, shuffle):
            return LMDataLoader(
                c, seq_len=cfg.seq_len, global_batch_size=self.global_batch,
                shard=shard, seed=cfg.seed, shuffle=shuffle,
            )

        return make(train_c, True), make(eval_c, cfg.shuffle_eval), corpus.vocab_size

    def _use_resident_data(self) -> bool:
        """Decide the corpus's home. 'device' demands it (and single-process
        addressability); 'auto' takes it when it fits; 'host' never."""
        cfg = self.config
        if cfg.data_placement == "host":
            return False
        multi = dist.process_count() > 1
        if self.task == "lm":
            if cfg.data_placement == "device":
                if multi:
                    raise ValueError(
                        "data_placement='device' requires a single process"
                    )
                return True
            # auto: token streams are tiny (bytes per token; uploaded as
            # int32) — resident whenever they fit the same budget
            nbytes = 4 * (
                len(self.train_loader.corpus) + len(self.eval_loader.corpus)
            )
            return not multi and nbytes <= cfg.resident_max_bytes
        if cfg.data_placement == "device":
            if multi:
                raise ValueError(
                    "data_placement='device' requires a single process: the "
                    "whole corpus must be addressable to upload it; "
                    "multi-host runs stream with data_placement='host'"
                )
            return True
        if cfg.data_placement != "auto":
            raise ValueError(
                f"unknown data_placement {cfg.data_placement!r} "
                "(auto | host | device)"
            )
        nbytes = sum(
            ds.images.nbytes + ds.labels.nbytes
            for ds in (self.train_ds, self.eval_ds)
        )
        return not multi and nbytes <= cfg.resident_max_bytes

    def _resident_group(self, total_steps: int) -> int:
        """Steps per dispatch in resident mode: the whole epoch at
        steps_per_call=-1, else the configured chunk (min 1).

        With a watchdog enabled, the group is capped at
        watchdog_probe_every_steps: the watchdog's contract is that a
        probe blocks for at most ~one dispatch group of device time
        (_probe_if_due), so a whole-epoch group would turn every probe
        into an epoch-long blocking wait with no beats — a timeout
        shorter than compile+epoch would then kill a healthy run.
        Bounded groups keep hang detection and dispatch amortization
        both honest."""
        k = self.config.steps_per_call
        g = max(total_steps, 1) if k == -1 else max(k, 1)
        if self.config.watchdog_timeout_s:
            g = min(g, max(self.config.watchdog_probe_every_steps, 1))
        return g

    def _after_train_group(self, epoch: int, prev: int, steps_done: int,
                           metrics) -> None:
        """Post-dispatch bookkeeping shared by the host and resident train
        loops: progress ladder + watchdog probe, cross-host driver sync
        check, and the log-every readback (which doubles as a confirmed-
        progress beat). Boundary-crossing tests, not modulo: groups
        advance by K."""
        cfg = self.config
        if self._train_registry is not None:
            self._observe_group(steps_done - prev)
        self._track(metrics["loss"])
        self._probe_if_due(prev, steps_done)
        if cfg.sync_check_every_steps and (
            prev // cfg.sync_check_every_steps
            != steps_done // cfg.sync_check_every_steps
        ):
            from ddp_practice_tpu.train.elastic import assert_in_sync

            # host-side counter, NOT device state: detects driver-loop
            # drift (skewed data exhaustion, missed batches) — SURVEY §5.2
            assert_in_sync(
                epoch * self.train_loader.steps_per_epoch + steps_done,
                what="driver step",
            )
        bookkeeping = False  # log readback / checkpoint this boundary?
        if cfg.log_every_steps and (
            prev // cfg.log_every_steps != steps_done // cfg.log_every_steps
        ):
            bookkeeping = True
            with self._tspan("block", step=steps_done):
                m = jax.device_get(metrics)
            if self._watchdog is not None:
                self._watchdog.beat()  # the device_get confirmed progress
            info0(
                "epoch %d step %d loss %.4f acc %.3f",
                epoch, steps_done, float(m["loss"]), float(m["accuracy"]),
            )
            self._write_metrics({
                "kind": "train",
                "epoch": epoch,
                "step": int(self.state.step),
                **{k: float(v) for k, v in m.items()},
            })
        if (
            cfg.checkpoint_dir
            and cfg.checkpoint_every_steps
            and prev // cfg.checkpoint_every_steps
            != steps_done // cfg.checkpoint_every_steps
        ):
            bookkeeping = True
            self.save(periodic=True)
        if bookkeeping and self._train_registry is not None:
            # the readback/checkpoint above is boundary bookkeeping, not
            # a step: restart the step-time window AFTER it, or the next
            # group's sample absorbs it and the straggler detector / SLO
            # flags a healthy run (same reason _close_train_epoch resets)
            import time as _time

            self._last_group_t = _time.monotonic()

    def _write_metrics(self, record: dict) -> None:
        """Append one JSON line to the metrics file (process 0; no-op
        otherwise). Flushed per record so a crashed run's curve survives."""
        if self._metrics_fh is None:
            return
        import json
        import time as _time

        record.setdefault("time", _time.time())
        self._metrics_fh.write(json.dumps(record) + "\n")
        self._metrics_fh.flush()

    def _write_eval_record(self, epoch: int, accuracy: float) -> None:
        """One {kind: "eval"} metrics record — shared by the in-loop
        (--eval_every) and end-of-run eval sites."""
        self._write_metrics({
            "kind": "eval", "epoch": epoch,
            "step": int(self.state.step), "accuracy": accuracy,
            **({"perplexity": self.eval_perplexity}
               if self.eval_perplexity is not None else {}),
        })

    def _close_train_epoch(self, final_metrics) -> None:
        """End-of-epoch fence shared by both train loops: drain the probe
        ladder rung by rung (beats during the wait), then close timing on
        a scalar readback — the only progress signal that fences on every
        transport (block_until_ready may not — BENCHMARKS.md)."""
        with self._tspan("block", at="epoch_end"):
            self._drain_pending()
            jax.block_until_ready(self.state.params)
            if final_metrics is not None:
                jax.device_get(final_metrics["loss"])
                if self._watchdog is not None:
                    self._watchdog.beat()
        # an epoch boundary's eval/checkpoint gap is not a step — don't
        # let the straggler detector judge it as one
        self._last_group_t = None

    def _train_epoch_resident(self, epoch: int) -> dict:
        """One epoch against the HBM-resident corpus: the only H2D traffic
        is the (steps, batch) int32 index grid (~4·S·B bytes — for MNIST at
        bs 32, ~240 KB/epoch vs ~47 MB of pixels), sliced into groups of
        `_resident_group` rows per dispatch. With steps_per_call=-1 the
        epoch is ONE XLA call. Numerically equivalent to the host path:
        same (seed, epoch) plan (DataLoader.epoch_plan), same batches, same
        math — agreement is to float noise (the two compile as different
        XLA programs, so reductions associate differently; <= 2 ulps
        measured, tests/test_resident.py).

        With profile_dir, the trace covers the whole first epoch (the first
        group includes compile; perf/run.py --trace 1 for steady state)."""
        cfg = self.config
        with self._tspan("epoch_open"):
            self.train_loader.set_epoch(epoch)
            idx, _ = self.train_loader.epoch_plan()
            if cfg.max_steps_per_epoch:
                idx = idx[: cfg.max_steps_per_epoch]
            total = len(idx)
            g = self._resident_group(total)
            self._pending.clear()
            timer = Timer()
            # host-side global step base for trace labels (resume-aware);
            # the state is quiescent at epoch start so this readback is
            # free
            step_base = int(self.state.step)
        final_metrics = None
        steps_done = 0
        profiling = False
        if cfg.profile_dir and epoch == 0:
            jax.profiler.start_trace(cfg.profile_dir)
            profiling = True
        try:
            for g0 in range(0, total, g):
                with self._tspan("data", step=step_base + steps_done):
                    rows = jax.device_put(
                        idx[g0 : g0 + g], self._grid_sharding
                    )
                with step_annotation(step_base + steps_done), \
                        self._tspan("dispatch", step=step_base + steps_done):
                    self.state, metrics = self.resident_train_step(
                        self.state, self._train_data, rows
                    )
                if self._serialize_steps:
                    jax.block_until_ready(metrics)
                inc = min(g, total - g0)
                prev = steps_done
                steps_done += inc
                final_metrics = metrics
                with self._tspan("after_group", step=step_base + steps_done):
                    self._after_train_group(epoch, prev, steps_done,
                                            metrics)
            self._close_train_epoch(final_metrics)
        finally:
            if profiling:
                jax.profiler.stop_trace()
        dt = timer.elapsed()
        images = self.global_batch * steps_done
        self._train_images += images
        self._train_seconds += dt
        return {"epoch_seconds": dt, "images": images}

    def _evaluate_resident(self) -> float:
        """Exact global accuracy from the HBM-resident eval corpus; the
        padded tail carries zero weights in the plan grid, so the weighted
        counts match the host path bit for bit."""
        idx, w = self.eval_loader.epoch_plan()
        total_rows = len(idx)
        g = self._resident_group(total_rows)
        correct = jnp.zeros((), jnp.float32)
        total = jnp.zeros((), jnp.float32)
        self._pending.clear()
        with profile_region("eval"):
            n_eval = 0
            for g0 in range(0, total_rows, g):
                di = jax.device_put(idx[g0 : g0 + g], self._grid_sharding)
                dw = jax.device_put(w[g0 : g0 + g], self._grid_sharding)
                c, t = self.resident_eval_step(
                    self.state, self._eval_data, di, dw
                )
                if self._serialize_steps:
                    jax.block_until_ready(c)
                correct = correct + c
                total = total + t
                prev = n_eval
                n_eval += min(g, total_rows - g0)
                self._track(c)
                self._probe_if_due(prev, n_eval)
        self._drain_pending()
        acc = float(correct) / max(float(total), 1.0)
        if self._watchdog is not None:
            self._watchdog.beat()
        return acc

    def _tagged_batches(self, loader, k: int):
        """Prefetched ("chunk"|"single", device_batch) stream: K-stacked
        chunks when k > 1, per-batch otherwise — one selection point for
        both the train and eval loops."""
        if k > 1:
            from ddp_practice_tpu.data.loader import prefetch_chunked

            return prefetch_chunked(
                iter(loader), k,
                self.batch_shardings, self.stacked_shardings,
                size=self.config.prefetch,
            )
        return (
            ("single", b) for b in prefetch_to_device(
                iter(loader), self.batch_shardings,
                size=self.config.prefetch,
            )
        )

    def train_epoch(self, epoch: int) -> dict:
        """One epoch. With a tracer it is one `train_epoch` span whose
        children are `epoch_open` (loader plan, step readback), then per
        dispatch group `data`, `dispatch` and `after_group` (telemetry,
        watchdog, log readback — its `block` child is the readback),
        then the closing `block` fence: host time outside every child
        is the span's self time."""
        attrs = {"attn_impl": self.attn_impl} if self.attn_impl else {}
        with self._tspan("train_epoch", epoch=epoch, **attrs):
            if self.resident_train_step is not None:
                return self._train_epoch_resident(epoch)
            return self._train_epoch_host(epoch)

    def _train_epoch_host(self, epoch: int) -> dict:
        """One epoch fed from the host loader through the prefetcher."""
        cfg = self.config
        with self._tspan("epoch_open"):
            self.train_loader.set_epoch(epoch)  # ≡ sampler.set_epoch (ddp_main.py:160)
            k = max(1, cfg.steps_per_call
                    if self.chunk_step is not None else 1)
            items = self._tagged_batches(self.train_loader, k)
            batches = self._traced_batches(items)
            self._pending.clear()
            timer = Timer()
            # host-side global step base for trace labels (resume-aware);
            # the state is quiescent at epoch start, and a host counter —
            # unlike int(self.state.step) per group — never blocks on
            # in-flight steps
            step_base = int(self.state.step)
        final_metrics = None
        images_this_epoch = 0
        # profile a steady-state window (post-compile) of the first epoch,
        # shrunk to fit short (smoke) epochs
        profile_window = None
        if cfg.profile_dir and epoch == 0:
            n = self.train_loader.steps_per_epoch
            if cfg.max_steps_per_epoch:
                n = min(n, cfg.max_steps_per_epoch)
            start = min(10, max(0, n - 10))
            stop = min(start + 10, n)
            if stop > start:
                profile_window = (start, stop)
            else:
                warn0("profile_dir set but epoch has %d steps — skipping trace", n)
        profiling = False
        steps_done = 0
        try:
            for tag, batch in batches:
                if cfg.max_steps_per_epoch and steps_done >= cfg.max_steps_per_epoch:
                    break
                if profiling and steps_done >= profile_window[1]:
                    jax.block_until_ready(self.state.params)
                    jax.profiler.stop_trace()
                    profiling = False
                    profile_window = None
                # start once anywhere past the window start (chunked runs
                # only visit multiples of k, which may skip the window)
                if profile_window and not profiling and (
                    steps_done >= profile_window[0]
                ):
                    jax.profiler.start_trace(cfg.profile_dir)
                    profiling = True
                with step_annotation(step_base + steps_done), \
                        self._tspan("dispatch", step=step_base + steps_done):
                    remaining = (
                        cfg.max_steps_per_epoch - steps_done
                        if cfg.max_steps_per_epoch else None
                    )
                    if tag == "chunk" and (remaining is None or remaining >= k):
                        self.state, metrics = self.chunk_step(self.state, batch)
                        inc = k
                    elif tag == "chunk":
                        # step cap mid-chunk: run the tail as single steps so
                        # the cap (and the resume-epoch math) stays exact
                        for j in range(remaining):
                            sub = jax.tree.map(lambda v: v[j], batch)
                            self.state, metrics = self.train_step(self.state, sub)
                        inc = remaining
                    else:
                        self.state, metrics = self.train_step(self.state, batch)
                        inc = 1
                if self._serialize_steps:
                    jax.block_until_ready(metrics)
                prev = steps_done
                steps_done += inc
                images_this_epoch += self.global_batch * inc
                final_metrics = metrics
                with self._tspan("after_group", step=step_base + steps_done):
                    self._after_train_group(epoch, prev, steps_done,
                                            metrics)
            self._close_train_epoch(final_metrics)
        finally:
            items.close()  # stop the prefetch producer thread promptly
            if profiling:  # short epoch or mid-window failure: close trace
                jax.profiler.stop_trace()
        dt = timer.elapsed()
        self._train_images += images_this_epoch
        self._train_seconds += dt
        return {"epoch_seconds": dt, "images": images_this_epoch}

    def evaluate(self) -> float:
        """Global exact accuracy; all processes participate in the reduction
        (the all-ranks-call-the-collective contract, ddp_main.py:164,108-109).

        With steps_per_call > 1, K eval batches run per dispatch (scan),
        mirroring the chunked train path; the padded-tail weights keep the
        result exact either way."""
        if self.task == "lm":
            return self._evaluate_lm()
        if self.resident_eval_step is not None:
            return self._evaluate_resident()
        k = max(1, self.config.steps_per_call if self.chunk_eval_step else 1)
        it = self._tagged_batches(self.eval_loader, k)
        correct = jnp.zeros((), jnp.float32)
        total = jnp.zeros((), jnp.float32)
        self._pending.clear()
        try:
            # trace annotation: eval separates from train on device timelines
            with profile_region("eval"):
                n_eval = 0
                for tag, batch in it:
                    if tag == "chunk":
                        c, t = self.chunk_eval_step(self.state, batch)
                        inc = k
                    else:
                        c, t = self.eval_step(self.state, batch)
                        inc = 1
                    if self._serialize_steps:
                        jax.block_until_ready(c)
                    correct = correct + c
                    total = total + t
                    prev = n_eval
                    n_eval += inc
                    self._track(c)
                    self._probe_if_due(prev, n_eval)
        finally:
            it.close()  # stop the prefetch producer thread promptly
        self._drain_pending()  # rung-by-rung: beats during the wait
        acc = float(correct) / max(float(total), 1.0)  # readback = confirmed
        if self._watchdog is not None:
            self._watchdog.beat()
        return acc

    def _evaluate_lm(self) -> float:
        """Held-out next-token accuracy (the parity-visible number) plus
        perplexity (exp of mean token NLL, stored on self.eval_perplexity
        and in the fit summary) — all processes participate, like the
        image eval."""
        if self.resident_eval_step is not None:
            return self._evaluate_lm_resident()
        it = prefetch_to_device(
            iter(self.eval_loader), self.batch_shardings,
            size=self.config.prefetch,
        )
        correct = jnp.zeros((), jnp.float32)
        total = jnp.zeros((), jnp.float32)
        nll = jnp.zeros((), jnp.float32)
        self._pending.clear()
        try:
            with profile_region("eval"):
                n_eval = 0
                for batch in it:
                    c, t, s = self.eval_step(self.state, batch)
                    if self._serialize_steps:
                        jax.block_until_ready(c)
                    correct = correct + c
                    total = total + t
                    nll = nll + s
                    prev = n_eval
                    n_eval += 1
                    self._track(c)
                    self._probe_if_due(prev, n_eval)
        finally:
            it.close()
        return self._finish_lm_eval(correct, total, nll)

    def _evaluate_lm_resident(self) -> float:
        """LM eval against the HBM-resident token stream: grouped grids of
        window starts, (correct, total, nll) summed in-graph."""
        starts, _ = self.eval_loader.epoch_plan()
        total_rows = len(starts)
        g = self._resident_group(total_rows)
        correct = jnp.zeros((), jnp.float32)
        total = jnp.zeros((), jnp.float32)
        nll = jnp.zeros((), jnp.float32)
        self._pending.clear()
        with profile_region("eval"):
            n_eval = 0
            for g0 in range(0, total_rows, g):
                rows = jax.device_put(
                    starts[g0 : g0 + g], self._grid_sharding
                )
                c, t, s = self.resident_eval_step(
                    self.state, self._eval_data, rows
                )
                if self._serialize_steps:
                    jax.block_until_ready(c)
                correct = correct + c
                total = total + t
                nll = nll + s
                prev = n_eval
                n_eval += min(g, total_rows - g0)  # steps, not dispatches
                self._track(c)
                self._probe_if_due(prev, n_eval)
        return self._finish_lm_eval(correct, total, nll)

    def _finish_lm_eval(self, correct, total, nll) -> float:
        """Shared LM-eval epilogue (host + resident paths): drain the probe
        ladder, derive accuracy/perplexity, confirm progress."""
        import math

        self._drain_pending()
        t_f = max(float(total), 1.0)
        acc = float(correct) / t_f
        self.eval_perplexity = math.exp(min(float(nll) / t_f, 30.0))
        if self._watchdog is not None:
            self._watchdog.beat()
        return acc

    def save(self, *, periodic: bool = False) -> None:
        """Checkpoint the current state.

        periodic=True (the every-N-steps saves) uses the async writer in
        single-process runs: the leaf gather fences the device, the
        serialization + rename overlap the next steps. The previous write
        is always waited on first (overlapping saves to one directory are
        forbidden — checkpoint.save_async). End-of-fit and multi-host
        saves are synchronous."""
        if self._watchdog is not None:
            self._watchdog.beat()  # checkpoint IO is progress, not a hang
        with self._tspan("checkpoint", periodic=periodic):
            self._save_impl(periodic=periodic)

    def _save_impl(self, *, periodic: bool) -> None:
        if self._pending_save is not None:
            self._pending_save.wait()  # surfaces write errors too
            self._pending_save = None
        if self.config.checkpoint_dir:
            cfg = self.config
            # everything needed to rebuild the state TREE (not just values)
            # offline: generate.py restores a checkpoint with no knowledge
            # of the training invocation, so the knobs that change the
            # optimizer-state structure ride along in the manifest
            extra = {
                "step": int(self.state.step),
                "precision_policy": cfg.precision_policy().name,
                "model": cfg.model,
                "optimizer": cfg.optimizer,
                "momentum": cfg.momentum,
                "clip_norm": cfg.clip_norm,
                "weight_decay": cfg.weight_decay,
                "accum_steps": cfg.accum_steps,
            }
            if self.task == "lm":
                extra["seq_len"] = cfg.seq_len
                extra["vocab_size"] = self._vocab_size
                extra["remat"] = bool(cfg.remat)
                extra["pos_emb"] = cfg.pos_emb
                extra["tied_embeddings"] = bool(cfg.tied_embeddings)
            if periodic and cfg.checkpoint_async and dist.process_count() == 1:
                self._pending_save = ckpt.save_async(
                    cfg.checkpoint_dir, self.state, extra=extra
                )
            else:
                ckpt.save(cfg.checkpoint_dir, self.state, extra=extra)

    def fit(self) -> dict:
        cfg = self.config
        if cfg.watchdog_timeout_s:
            from ddp_practice_tpu.train.elastic import StepWatchdog

            self._watchdog = StepWatchdog(cfg.watchdog_timeout_s).start()
        try:
            return self._fit_inner()
        finally:
            if self._watchdog is not None:
                self._watchdog.stop()
                self._watchdog = None
            if self._pending_save is not None:
                # an exception mid-epoch must not leave an orphan writer
                # racing a restarted Trainer's restore/save in the same
                # directory (run_with_restarts reconstructs immediately);
                # swallow the write error — the original exception wins
                try:
                    self._pending_save.wait()
                except Exception:
                    log.exception("async checkpoint write failed")
                self._pending_save = None
            if self._metrics_fh is not None:
                # crash path: a restarted Trainer reopens the same file in
                # append mode; don't leak this fd until GC
                self._metrics_fh.close()
                self._metrics_fh = None
            # written in the finally so a crashed run still leaves its
            # partial timeline — a flight recorder's whole point
            self.save_trace()
            if self._tele_server is not None:
                self._tele_server.close()
                self._tele_server = None
            if self._telemetry is not None:
                # drain + final snapshot; the streamed lines were
                # flushed as they happened, so even skipping this
                # (SIGKILL) leaves a valid line-by-line file
                self._telemetry.close()
                self._telemetry = None

    def _fit_inner(self) -> dict:
        cfg = self.config
        timer = Timer()
        accuracy: Optional[float] = None
        # after a checkpoint restore, continue from the epoch the restored
        # step count falls in — lost work is bounded by one checkpoint
        # interval, not replayed from epoch 0
        steps_per_epoch = self.train_loader.steps_per_epoch
        if cfg.max_steps_per_epoch:
            steps_per_epoch = min(steps_per_epoch, cfg.max_steps_per_epoch)
        start_epoch = min(int(self.state.step) // max(steps_per_epoch, 1),
                          cfg.epochs)
        if start_epoch:
            info0("resuming at epoch %d (step %d)",
                  start_epoch, int(self.state.step))
        for epoch in range(start_epoch, cfg.epochs):
            info0("=== epoch %d / %d ===", epoch + 1, cfg.epochs)
            self.train_epoch(epoch)
            if cfg.eval_every_epochs and (epoch + 1) % cfg.eval_every_epochs == 0:
                accuracy = self.evaluate()
                info0("Accuracy is %.2f%%", accuracy * 100.0)
                self._write_eval_record(epoch, accuracy)
            if cfg.checkpoint_every_epochs and (epoch + 1) % cfg.checkpoint_every_epochs == 0:
                self.save()
        if accuracy is None or not cfg.eval_every_epochs:
            accuracy = self.evaluate()
            self._write_eval_record(cfg.epochs - 1, accuracy)
        self.save()
        elapsed = timer.elapsed()
        ips = self._train_images / max(self._train_seconds, 1e-9)
        summary = {
            "accuracy": accuracy,
            "elapsed_seconds": elapsed,
            "train_seconds": self._train_seconds,
            "images_per_sec": ips,
            "images_per_sec_per_chip": ips / jax.device_count(),
            "steps": int(self.state.step),
            "global_batch": self.global_batch,
            "devices": jax.device_count(),
        }
        if self.task == "lm" and self.eval_perplexity is not None:
            summary["perplexity"] = self.eval_perplexity
            summary["tokens_per_sec_per_chip"] = (
                ips * cfg.seq_len / jax.device_count()
            )
            info0("perplexity: %.3f", self.eval_perplexity)
        # the reference's three parity-visible lines (SURVEY §5.5)
        info0("Accuracy is %.2f%%", accuracy * 100.0)
        info0("time elapsed: %.2fs", elapsed)
        info0("throughput: %.1f images/sec (%.1f /chip)",
              ips, ips / jax.device_count())
        self._write_metrics({"kind": "summary", **summary})
        return summary


def fit(config: TrainConfig) -> dict:
    """Train once, or with checkpoint-based elastic restarts when
    max_restarts > 0 (recovery is effective with a checkpoint_dir set)."""
    if config.max_restarts > 0:
        from ddp_practice_tpu.train.elastic import run_with_restarts

        return run_with_restarts(
            lambda resume: Trainer(
                config.replace(resume=config.resume or resume)
            ),
            max_restarts=config.max_restarts,
        )
    return Trainer(config).fit()
