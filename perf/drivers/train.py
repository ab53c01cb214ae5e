"""The training cells: `Trainer` of the program, driven segment by segment.

What is the program's: `ddp_practice_tpu.train.loop.Trainer` built from a
`TrainConfig`, its `train_epoch()`, its data loaders, its host spans. What is
the benchmark's: the data (files written from the seed, which the Trainer
loads through its normal registry), the weights (`lib/weights.py`, put in
place of the Trainer's own initialisation), the segments and their clock, the
reference that follows the first three steps, and every number's arithmetic.

One segment is one `train_epoch()` call capped at `segment_steps` optimizer
steps (`max_steps_per_epoch`), with `log_every_steps` equal to it: the Trainer
reads the loss back to the host exactly once, at the segment's last step, and
then fences. Evaluation and checkpoints belong to `fit()`, which is never
called, and an epoch never runs past its segment, so no segment spans an epoch
boundary, an evaluation or a checkpoint.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import time

import numpy as np

from perf.lib import compare, stats, weights


def family_of(cfg: dict):
    """`perf/families/<family>.py`: what knows this configuration's keys,
    its data and its operations."""
    return importlib.import_module(f"perf.families.{cfg['family']}")


def write_data(cfg: dict, traffic: dict, seed: int, chips: int,
               data_dir: str) -> dict:
    """Files the Trainer's own dataset registry loads, made from the seed;
    rows all differ. Returns {"dataset", "arrays"}: the registry name and
    the benchmark's own copy of what it wrote, for the reference."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xDA7A]))
    return family_of(cfg).write_data(
        cfg, traffic, rng, traffic["batch_per_chip"] * chips,
        traffic["segment_steps"], data_dir)


def rows_fed(trainer, data: dict, cfg: dict, traffic: dict, epoch: int,
             step: int) -> dict:
    """The batch the Trainer's loader feeds at (epoch, step), rebuilt from
    the benchmark's own arrays by the loader's plan."""
    trainer.train_loader.set_epoch(epoch)
    plan, _ = trainer.train_loader.epoch_plan()
    return family_of(cfg).rows_fed(data, traffic, np.asarray(plan[step]))


# ------------------------------------------------------------- the program
def build_trainer(ctx, data: dict, metrics_file: str):
    from ddp_practice_tpu.config import MeshConfig, TrainConfig
    from ddp_practice_tpu.train.loop import Trainer

    cfg, traffic = ctx.config, ctx.traffic
    family = family_of(cfg)
    family.prepare(cfg)
    kw = dict(traffic["trainer"], **family.trainer_options(cfg, traffic))
    train_cfg = TrainConfig(
        model=cfg["program_model"], dataset=data["dataset"],
        data_dir=ctx.data_dir, batch_size=traffic["batch_per_chip"],
        epochs=1, seed=int(ctx.seed) % (2**31 - 1),
        mesh=MeshConfig(data=ctx.chips), max_steps_per_epoch=1,
        log_every_steps=1, metrics_file=metrics_file,
        trace_out=(os.path.join(ctx.outdir, "host_spans.json")
                   if ctx.trace else None),
        **kw,
    )
    return Trainer(train_cfg)


def _adam_mu(opt_state):
    import jax

    hits = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if len(hits) != 1:
        raise RuntimeError(f"{len(hits)} Adam states in the optimizer state")
    return hits[0].mu


def _last_loss(metrics_file: str, n: int) -> list:
    with open(metrics_file) as f:
        recs = [json.loads(line) for line in f]
    return [r["loss"] for r in recs if r.get("kind") == "train"][-n:]


def first_steps(trainer, p0, metrics_file: str, n_steps: int = 3) -> dict:
    """Drive the window's own call (`train_epoch`) for the first steps, one
    a call, and read what the reference will be held against."""
    import jax

    from perf.reference import follow

    out = {"loss": []}
    for e in range(n_steps):
        trainer.train_epoch(e)
        out["loss"].append(_last_loss(metrics_file, 1)[0])
        if e == 0:
            # Adam's first moment after one step is (1 - b1) * gradient
            out["grad_norms"] = follow.leaf_norms(
                _adam_mu(trainer.state.opt_state)) / (1.0 - follow.B1)
    delta = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))(
        trainer.state.params, p0)
    out["delta_norms"] = follow.leaf_norms(delta)
    return out


def start_program(ctx, metrics_file: str) -> tuple:
    """Set-up up to the first three steps: the data, the Trainer, the
    benchmark's weights in place of its own, and what those steps read.
    Returns (trainer, the three batches fed, the program's readings); the
    same object goes on into the window."""
    import jax

    cfg, traffic = ctx.config, ctx.traffic
    if os.path.exists(metrics_file):
        os.remove(metrics_file)
    data = write_data(cfg, traffic, ctx.seed, ctx.chips, ctx.data_dir)
    ctx.clock.mark("data written from the seed")
    trainer = build_trainer(ctx, data, metrics_file)
    if trainer.train_loader.steps_per_epoch < traffic["segment_steps"]:
        raise RuntimeError(
            f"the Trainer sees {trainer.train_loader.steps_per_epoch} steps "
            f"an epoch, a segment needs {traffic['segment_steps']}")
    ctx.clock.mark("Trainer built (model, optimizer, state, data placed)")
    params = weights.make_params(
        trainer.state.params, ctx.seed,
        shardings=trainer.state_shardings.params)
    p0 = jax.tree.map(lambda x: x.copy(), params)
    trainer.state = trainer.state.replace(params=params)
    del params
    ctx.clock.mark("weights made on the device from the seed")
    fed = [rows_fed(trainer, data, cfg, traffic, e, 0) for e in range(3)]
    seen = first_steps(trainer, p0, metrics_file)
    ctx.clock.mark("first three steps through train_epoch (compiles)")
    return trainer, fed, seen


def reference_follows(ctx, fed: list, abstract, quant=None) -> dict:
    """The reference's three steps from the same weights on the same rows
    (`quant` makes it the control)."""
    import jax

    from perf.reference import follow

    ref = importlib.import_module(f"perf.reference.{ctx.config['reference']}")
    trainer_kw = ctx.traffic["trainer"]
    with jax.default_device(jax.devices()[0]):
        p0 = weights.make_params(abstract, ctx.seed)
        return follow.follow(
            ref.loss, p0, fed, ctx.config, lr=trainer_kw["learning_rate"],
            wd=trainer_kw.get("weight_decay", 0.0),
            block_rows=ctx.traffic["reference_block_rows"], quant=quant)


def gaps(got: dict, want: dict) -> dict:
    """The numbers a training cell is judged by, `got` against `want`."""
    grad, gi = compare.worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    delta, di = compare.worst_leaf_gap(got["delta_norms"],
                                       want["delta_norms"])
    return {"loss_rel_gap": [abs(a - b) / abs(b) for a, b in
                             zip(got["loss"], want["loss"])],
            "grad_norm_gap": grad, "grad_leaf": want["names"][gi],
            "param_change_gap": delta, "param_leaf": want["names"][di]}


def segment(trainer, epoch: int) -> tuple:
    """(t_begin, t_end) on the monotonic clock of one segment."""
    t0 = time.monotonic()
    trainer.train_epoch(epoch)
    return t0, time.monotonic()


def host_state() -> tuple:
    """(CPU seconds this process has used, the host's 1-minute load): read
    between segments, so that a stalled segment's series says whether the
    process was computing, waiting, or kept off a busy host's cores."""
    return time.process_time(), os.getloadavg()[0]


# -------------------------------------------------------------------- run
def run(ctx) -> dict:
    import jax

    from perf.lib import xtrace

    cfg, traffic = ctx.config, ctx.traffic
    seg_steps = traffic["segment_steps"]
    metrics_file = os.path.join(ctx.outdir, "train_metrics.jsonl")
    trainer, fed, seen = start_program(ctx, metrics_file)
    resident = trainer.resident_train_step is not None

    # warm-up: whole segments until two in a row agree
    trainer.config = trainer.config.replace(
        max_steps_per_epoch=seg_steps, log_every_steps=seg_steps)
    warm, epoch = [], 3
    agree = traffic["warmup"]["agree_pct"] / 100.0
    while len(warm) < traffic["warmup"]["max_segments"]:
        a, b = segment(trainer, epoch)
        epoch += 1
        warm.append(b - a)
        if len(warm) >= 2 and abs(warm[-1] / warm[-2] - 1.0) <= agree:
            break
    ctx.clock.mark(f"warm-up: {len(warm)} segments until two agreed")

    # ------------------------------------------------------------ window
    traced_at = min(traffic["trace_segment"],
                    max(int(ctx.seconds / max(warm[-1], 1e-6)) - 1, 0))
    trace_dir = os.path.join(ctx.outdir, "xplane")
    segs, traced, host = [], None, [host_state()]
    setup_s = time.monotonic() - ctx.t_start
    w0 = time.monotonic()
    while time.monotonic() - w0 < ctx.seconds or len(segs) < 2:
        tracing = ctx.trace and len(segs) == traced_at
        if tracing:
            ctx.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation("perf:traced"):
                a, b = segment(trainer, epoch)
            ctx.stop_trace()
            traced = (a, b)
        else:
            a, b = segment(trainer, epoch)
        epoch += 1
        segs.append((a, b))
        host.append(host_state())
    w1 = time.monotonic()
    seg_s = [b - a for a, b in segs]
    losses = _last_loss(metrics_file, len(segs))
    compiles = ctx.compiles.count_between(w0, w1)
    peak = ctx.memory_peak()
    # the Trainer's own recorder of its `data` / `dispatch` / `block` spans:
    # it has no public accessor and is only written out by fit(), so a
    # traced run reads the field by name; without it the span metrics are
    # left out of the line (PERF.md, Open questions)
    spans = ctx.program_spans(getattr(trainer, "_tracer", None))

    # ------------------------------------------------------- the numbers
    # the cell's rate is ALL the window's work over ALL its time, the
    # gaps between segments included; the median segment stands beside it
    # as a per-layer reading
    items = traffic["batch_per_chip"] * ctx.chips
    rates = stats.segment_rates(seg_s, seg_steps * items, w1 - w0)
    per_item = family_of(cfg).train_flops_per_item(cfg, traffic)
    denom = ctx.chips * ctx.peaks["bf16_flops_s"]
    mfu = 100.0 * per_item * rates["window_rate"] / denom
    median_mfu = 100.0 * per_item * rates["median_rate"] / denom
    series = {"segment_seconds": seg_s, "segment_loss": losses,
              "segment_begin_s": [a - w0 for a, _ in segs],
              "window_seconds": w1 - w0,
              "segment_cpu_seconds": [b[0] - a[0] for a, b in
                                      zip(host, host[1:])],
              "segment_host_load": [b[1] for b in host[1:]],
              "compiles_in_window": ctx.compiles.between(w0, w1),
              "warmup_segment_seconds": warm, "steps_per_segment": seg_steps,
              "items_per_step": items, "data_resident": resident,
              "flops_per_item": per_item, "median_mfu_pct": median_mfu,
              **rates}

    obs = {"kind": "train", "segments": seg_s, "rates": rates,
           "median_mfu_pct": median_mfu,
           "steps_per_segment": seg_steps, "compiles_in_window": compiles,
           "spans": spans, "window": (w0, w1), "traced": traced,
           "trace": None, "chips": ctx.chips}
    if traced is not None:
        obs["trace"] = xtrace.load(xtrace.find_xplane(trace_dir))

    # ---------------------------------- the reference, after the program
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        trainer.state.params)
    del trainer
    gc.collect()
    t_ref = time.monotonic()
    checks = reference_checks(ctx, seen, fed, abstract)
    series["reference_s"] = time.monotonic() - t_ref

    return {
        "metrics": {"train_mfu_pct": mfu, "setup_s": setup_s},
        "attempted": len(segs) * seg_steps, "failed": 0 if all(
            np.isfinite(losses)) else 1,
        "checks": checks, "obs": obs, "series": series,
        "memory_peak_bytes": peak,
    }


def reference_checks(ctx, seen: dict, fed: list, abstract
                     ) -> compare.Checks:
    """Each number beside its limit (`traffic["limits"]`)."""
    want = reference_follows(ctx, fed, abstract)
    g, lim = gaps(seen, want), ctx.traffic["limits"]
    checks = compare.Checks()
    for i, gap in enumerate(g["loss_rel_gap"]):
        checks.add(f"loss_step{i + 1}_rel_gap", gap, lim["loss_rel_gap"],
                   f"program {seen['loss'][i]:.6f} reference "
                   f"{want['loss'][i]:.6f}")
    checks.add("grad_norm_worst_leaf_gap", g["grad_norm_gap"],
               lim["grad_norm_gap"], g["grad_leaf"])
    checks.add("param_change_worst_leaf_gap", g["param_change_gap"],
               lim["param_change_gap"], g["param_leaf"])
    return checks
