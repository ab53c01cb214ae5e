"""Benchmark harness: honest steady-state training throughput + MFU.

Methodology (see BENCHMARKS.md at the repo root for the full story):

- **Device-resident data.** A pool of uint8 images lives in HBM; every
  step gathers a batch by on-device PRNG index and normalizes uint8 ->
  float on device. This measures the accelerator's training rate — the
  quantity MFU is defined over — rather than the host link. (End-to-end
  numbers with the real input pipeline are recorded separately in
  PARITY.md.)
- **Fenced timing.** Every timing window is closed by a host readback of
  a scalar metric (`float(loss)`), which cannot resolve until the whole
  dependency chain has executed. Round-1 numbers lacked this fence and
  were invalid.
- **The chip only.** Every entry point here asks `_chip()` first: no TPU,
  or a `device_kind` missing from utils/flops.py's peak tables, raises —
  a rate never prints with its utilization silently dropped.
- **K steps per dispatch.** `lax.scan` over K optimizer steps per call
  amortizes dispatch latency; per-call overhead is <2% of the window.
- **Analytic FLOPs.** utils/flops.py; fwd+bwd = 3x forward. XLA's
  cost_analysis undercounts on this backend (~8x vs hand counts).
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def _chip() -> tuple:
    """(device_kind, peak bf16 FLOP/s, HBM bytes/s) of the chip a
    benchmark runs on; raises where there is none or it is unknown."""
    import jax

    from ddp_practice_tpu.utils.flops import (
        chip_hbm_bandwidth,
        chip_peak_flops,
    )

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"benchmarks measure the TPU; jax reports platform "
            f"{dev.platform!r} ({dev.device_kind})"
        )
    peak = chip_peak_flops(dev.device_kind)
    bw = chip_hbm_bandwidth(dev.device_kind)
    if peak is None or bw is None:
        raise RuntimeError(
            f"device_kind {dev.device_kind!r} is not in utils/flops.py's "
            "peak tables — add it with its source before benchmarking on it"
        )
    return dev.device_kind, peak, bw


def bench_train(
    model_name: str,
    *,
    image_shape=(32, 32, 3),
    num_classes: int = 10,
    batch_size: int = 1024,
    steps_per_call: int = 32,
    calls: int = 8,
    warmup_calls: int = 2,
    precision: str = "bf16",
    pool_size: int = 8192,
    optimizer: str = "sgd",
    learning_rate: float = 1e-4,
    model_kwargs: Optional[dict] = None,
    seed: int = 0,
) -> dict:
    """Measure steady-state training throughput of one model, single host.

    Returns a dict with images/sec/chip, ms/step, and (on known TPU chips)
    achieved TFLOP/s and MFU against the bf16 peak.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddp_practice_tpu.config import MeshConfig, PrecisionPolicy, TrainConfig
    from ddp_practice_tpu.models import create_model
    from ddp_practice_tpu.parallel.mesh import (
        batch_sharding,
        build_mesh,
        replicated,
        shard_state,
    )
    from ddp_practice_tpu.parallel.ring import set_current_mesh
    from ddp_practice_tpu.parallel.sharding_rules import param_sharding_rules
    from ddp_practice_tpu.train.state import create_state, make_optimizer
    from ddp_practice_tpu.train.steps import _train_step_fn
    from ddp_practice_tpu.utils.flops import train_flops_per_image

    device_kind, peak, _ = _chip()
    mesh = build_mesh(MeshConfig(data=-1))
    set_current_mesh(mesh)
    try:
        policy = PrecisionPolicy.from_name(precision)
        model = create_model(
            model_name, num_classes=num_classes, policy=policy, axis_name=None,
            **(model_kwargs or {}),
        )
        tcfg = TrainConfig(
            model=model_name, optimizer=optimizer, learning_rate=learning_rate
        )
        tx = make_optimizer(tcfg)

        sample = jnp.zeros((batch_size,) + tuple(image_shape), jnp.float32)

        def init_fn(r):
            return create_state(model, tx, rng=r, sample_input=sample)

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(seed))
        rules = param_sharding_rules(model_name)
        state_shardings = shard_state(abstract, mesh, rules)
        state = jax.jit(init_fn, out_shardings=state_shardings)(
            jax.random.PRNGKey(seed)
        )

        # uint8 pool in HBM; labels alongside (synthetic — the benchmark measures
        # compute rate, not convergence; convergence parity lives in tests/PARITY)
        host_rng = np.random.default_rng(seed)
        pool_img_np = host_rng.integers(
            0, 256, size=(pool_size,) + tuple(image_shape), dtype=np.uint8
        )
        pool_lbl_np = host_rng.integers(
            0, num_classes, size=(pool_size,), dtype=np.int32
        )
        rep = replicated(mesh)
        pool_img = jax.device_put(pool_img_np, rep)
        pool_lbl = jax.device_put(pool_lbl_np, rep)

        bsh = batch_sharding(mesh)
        step_fn = _train_step_fn(model, tx, 0.0)
        base_key = jax.random.PRNGKey(seed + 1)
        k_steps = steps_per_call

        def chunk(state, pimg, plbl):
            def body(st, key):
                idx = jax.random.randint(key, (batch_size,), 0, pool_size)
                img = jnp.take(pimg, idx, axis=0).astype(jnp.float32) / 255.0
                batch = {
                    "image": lax.with_sharding_constraint(img, bsh),
                    "label": lax.with_sharding_constraint(
                        jnp.take(plbl, idx, axis=0), bsh
                    ),
                    "weight": jnp.ones((batch_size,), jnp.float32),
                }
                return step_fn(st, batch)

            keys = jax.random.split(
                jax.random.fold_in(base_key, state.step), k_steps
            )
            state, ms = lax.scan(body, state, keys)
            return state, jax.tree.map(lambda v: v[-1], ms)

        jchunk = jax.jit(
            chunk,
            donate_argnums=0,
            in_shardings=(state_shardings, rep, rep),
            out_shardings=(state_shardings, rep),
        )

        import time

        for _ in range(max(warmup_calls, 1)):  # >=1: the timed loop must not compile
            state, metrics = jchunk(state, pool_img, pool_lbl)
        _fence = float(metrics["loss"])  # forces completion (see module docstring)

        # two independently fenced windows covering exactly `calls`
        # calls: their agreement is the run-to-run stability evidence
        # (the round-3 ConvNet entry swung 62-91k img/s on single short
        # windows — round-4 verdict item 7). calls=1 runs one window and
        # reports no spread.
        w_calls = [calls - calls // 2, calls // 2]
        window_rates = []
        t0 = time.perf_counter()
        for wc in w_calls:
            if wc == 0:
                continue
            tw = time.perf_counter()
            for _ in range(wc):
                state, metrics = jchunk(state, pool_img, pool_lbl)
            final_loss = float(metrics["loss"])  # fence closes the window
            window_rates.append(
                wc * k_steps * batch_size / (time.perf_counter() - tw)
            )
        dt = time.perf_counter() - t0

        n_chips = jax.device_count()
        images = calls * k_steps * batch_size
        ips = images / dt
        ips_chip = ips / n_chips
        ms_per_step = dt / (calls * k_steps) * 1e3
        spread_pct = (
            100.0 * abs(window_rates[0] - window_rates[-1])
            / max(ips, 1e-9)
            if len(window_rates) > 1 else None
        )
        vit_kw = {}
        if model_name.startswith("vit"):
            # read the instantiated module's own config (registry defaults +
            # model_kwargs overrides) so the FLOP count matches what actually ran
            vit_kw = dict(
                patch_size=model.patch_size,
                hidden_dim=model.hidden_dim,
                depth=model.depth,
                mlp_dim=model.mlp_dim,
            )
        flops_img = train_flops_per_image(
            model_name, tuple(image_shape), num_classes, **vit_kw
        )
        out = {
            "model": model_name,
            "image_shape": list(image_shape),
            "batch_size": batch_size,
            "steps_per_call": k_steps,
            "precision": precision,
            "device_kind": device_kind,
            "n_chips": n_chips,
            "images_per_sec": round(ips, 1),
            "images_per_sec_per_chip": round(ips_chip, 1),
            "ms_per_step": round(ms_per_step, 3),
            "final_loss": round(final_loss, 4),
        }
        if spread_pct is not None:
            # agreement of the two fenced half-windows, % of the mean rate
            out["window_spread_pct"] = round(spread_pct, 2)
        if flops_img:
            tflops_chip = ips_chip * flops_img / 1e12
            out["train_flops_per_image"] = flops_img
            out["tflops_per_chip"] = round(tflops_chip, 2)
            out["mfu_pct"] = round(100.0 * tflops_chip * 1e12 / peak, 2)
            out["peak_bf16_tflops"] = peak / 1e12
        return out
    finally:
        set_current_mesh(None)


def bench_lm_train(
    model_name: str = "lm_base",
    *,
    seq_len: int = 2048,
    vocab_size: int = 32768,
    batch_size: int = 8,
    steps_per_call: int = 4,
    calls: int = 4,
    warmup_calls: int = 1,
    precision: str = "bf16",
    attn_impl: str = "flash",
    optimizer: str = "adamw",
    learning_rate: float = 3e-4,
    model_kwargs: Optional[dict] = None,
    seed: int = 0,
    # "random": uniform randint tokens drawn on device (pure compute-rate
    # measurement). "corpus": device-resident windows of the synthetic
    # Markov byte corpus (data/lm_corpus.py) — vocab_size follows the
    # corpus. The MoE entry benches on the corpus: router balance is a
    # property of TRAINED routing, and uniform-random tokens leave
    # embeddings untrained (each of 32k ids seen ~0.5x per batch), so the
    # router chases drifting inputs and the recorded health is
    # meaningless (measured: drop oscillates 0.10-0.45 on random tokens
    # vs <2% warm on the corpus at identical model dims).
    data: str = "random",
) -> dict:
    """Steady-state LM training throughput at long sequence length:
    tokens/sec/chip + MFU. Same fenced-timing methodology as bench_train;
    token batches are drawn on device (randint — measuring compute rate,
    not convergence). Default kernel is the Pallas flash path: at seq 2k+
    the O(seq^2) dense score materialization is exactly what the tiled
    kernel exists to avoid."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ddp_practice_tpu.config import MeshConfig, PrecisionPolicy, TrainConfig
    from ddp_practice_tpu.models import create_model
    from ddp_practice_tpu.parallel.mesh import (
        batch_sharding,
        build_mesh,
        replicated,
        shard_state,
    )
    from ddp_practice_tpu.parallel.ring import set_current_mesh
    from ddp_practice_tpu.parallel.sharding_rules import param_sharding_rules
    from ddp_practice_tpu.train.state import create_state, make_optimizer
    from ddp_practice_tpu.train.steps import _lm_train_step_fn
    from ddp_practice_tpu.utils.flops import lm_train_flops_per_token

    device_kind, peak, _ = _chip()
    mesh = build_mesh(MeshConfig(data=-1))
    set_current_mesh(mesh)
    try:
        corpus_windows = None
        if data == "corpus":
            from ddp_practice_tpu.data.lm_corpus import synthetic_token_corpus

            c = synthetic_token_corpus(n_tokens=1 << 20, seed=seed + 7)
            vocab_size = c.vocab_size
            corpus_windows = jnp.asarray(c.windows(seq_len))
        elif data != "random":
            raise ValueError(f"unknown data source {data!r}")
        policy = PrecisionPolicy.from_name(precision)
        kwargs = dict(
            vocab_size=vocab_size, max_len=seq_len, attn_impl=attn_impl
        )
        kwargs.update(model_kwargs or {})
        model = create_model(model_name, policy=policy, **kwargs)
        tcfg = TrainConfig(
            model=model_name, optimizer=optimizer, learning_rate=learning_rate
        )
        tx = make_optimizer(tcfg)

        sample = jnp.zeros((batch_size, seq_len), jnp.int32)

        def init_fn(r):
            return create_state(model, tx, rng=r, sample_input=sample)

        abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(seed))
        rules = param_sharding_rules(model_name)
        state_shardings = shard_state(abstract, mesh, rules)
        state = jax.jit(init_fn, out_shardings=state_shardings)(
            jax.random.PRNGKey(seed)
        )

        rep = replicated(mesh)
        bsh = batch_sharding(mesh)
        # loss-only metrics: the per-step accuracy argmax is a full
        # extra logits pass the reference's train loop never does
        step_fn = _lm_train_step_fn(model, tx, with_accuracy=False)
        base_key = jax.random.PRNGKey(seed + 1)
        k_steps = steps_per_call

        def chunk(state):
            def body(st, key):
                if corpus_windows is not None:
                    idx = jax.random.randint(
                        key, (batch_size,), 0, corpus_windows.shape[0],
                        dtype=jnp.int32,
                    )
                    tokens = corpus_windows[idx]
                else:
                    tokens = jax.random.randint(
                        key, (batch_size, seq_len + 1), 0, vocab_size,
                        dtype=jnp.int32,
                    )
                batch = {"tokens": lax.with_sharding_constraint(tokens, bsh)}
                return step_fn(st, batch)

            keys = jax.random.split(
                jax.random.fold_in(base_key, state.step), k_steps
            )
            state, ms = lax.scan(body, state, keys)
            return state, jax.tree.map(lambda v: v[-1], ms)

        jchunk = jax.jit(
            chunk,
            donate_argnums=0,
            in_shardings=(state_shardings,),
            out_shardings=(state_shardings, rep),
        )

        import time

        for _ in range(max(warmup_calls, 1)):
            state, metrics = jchunk(state)
        _fence = float(metrics["loss"])

        t0 = time.perf_counter()
        for _ in range(calls):
            state, metrics = jchunk(state)
        final_loss = float(metrics["loss"])
        dt = time.perf_counter() - t0

        n_chips = jax.device_count()
        tokens = calls * k_steps * batch_size * seq_len
        tps = tokens / dt
        tps_chip = tps / n_chips
        flops_tok = lm_train_flops_per_token(
            hidden_dim=model.hidden_dim, depth=model.depth,
            mlp_dim=model.mlp_dim, vocab_size=vocab_size, seq_len=seq_len,
            causal=True,
            moe_every=getattr(model, "moe_every", 0),
            moe_top_k=getattr(model, "moe_top_k", 2),
        )
        out = {
            "model": model_name,
            "seq_len": seq_len,
            "vocab_size": vocab_size,
            "batch_size": batch_size,
            "steps_per_call": k_steps,
            "precision": precision,
            "attn_impl": attn_impl,
            "device_kind": device_kind,
            "n_chips": n_chips,
            "tokens_per_sec": round(tps, 1),
            "tokens_per_sec_per_chip": round(tps_chip, 1),
            "ms_per_step": round(dt / (calls * k_steps) * 1e3, 3),
            "final_loss": round(final_loss, 4),
            "train_flops_per_token": flops_tok,
        }
        tflops_chip = tps_chip * flops_tok / 1e12
        out["tflops_per_chip"] = round(tflops_chip, 2)
        out["mfu_pct"] = round(100.0 * tflops_chip * 1e12 / peak, 2)
        out["peak_bf16_tflops"] = peak / 1e12
        # router health from the final step's metrics (lm_moe)
        for k in ("moe_drop_rate", "moe_load_max", "moe_load_min"):
            if k in metrics:
                out[k] = round(float(metrics[k]), 4)
        return out
    finally:
        set_current_mesh(None)


def bench_lm_decode(
    model_name: str = "lm_base",
    *,
    prompt_len: int = 128,
    max_new_tokens: int = 512,
    batch_size: int = 8,
    vocab_size: int = 256,
    precision: str = "bf16",
    calls: int = 3,
    warmup_calls: int = 1,
    temperature: float = 1.0,
    top_k: int = 0,
    model_kwargs: Optional[dict] = None,
    seed: int = 0,
    # dtype the params are STREAMED in during decode. None follows the
    # precision policy: bf16 compute -> bf16 streaming (inference needs no
    # fp32 masters, and the cast is bit-identical to what every matmul
    # already does per-step — inference.cast_params_for_streaming), fp32
    # policy -> fp32 streaming. Pass explicitly to measure the other path.
    stream_dtype: Optional[str] = None,
    # KV-cache storage: "policy" (the compute dtype — bf16 here) or
    # "int8" (quantized cache + per-(head, position) scales,
    # models/vit.py / ops/decode_attention.py — halves the cache's
    # share of the bandwidth-bound step)
    kv_cache: str = "policy",
    # accepted for bench.py CLI-override uniformity; decode has no chunking
    steps_per_call: int = 0,
) -> dict:
    """Autoregressive generation throughput: KV-cache decode tokens/sec.

    Decode is HBM-bandwidth-bound, not MXU-bound: every generated token
    re-reads the full parameter set (plus the growing KV cache), so the
    roofline metric is model-bandwidth utilization (MBU) = bytes actually
    streamed per second / chip HBM bandwidth — reported alongside
    tokens/sec. Training keeps fp32 master params, but inference does not
    need them: under the bf16 policy the resident params are cast once,
    so the per-step traffic floor is 2 bytes/param + the bf16 KV cache
    read (`--precision fp32` / `stream_dtype="fp32"` measures the
    master-param path at 4 bytes/param — the two knobs move together
    unless stream_dtype is passed explicitly, so the reported precision
    always matches what streams). The whole generation (prefill + lax.scan of
    single-token steps, inference.py) is ONE jitted call; timing fences
    on a host readback of the final tokens.

    tokens_per_sec is the end-to-end generation rate (prefill included —
    that is what a caller of gen() experiences). The per-decode-step
    metrics (ms_per_token_step, mbu_pct) subtract a separately timed
    prefill-only call from the window, so they measure the decode loop
    itself rather than understating MBU by the prefill share. Configs
    where prefill would dominate are rejected rather than silently
    reported as decode rates.
    """
    if prompt_len > max_new_tokens:
        raise ValueError(
            f"prompt_len {prompt_len} > max_new_tokens {max_new_tokens}: "
            "end-to-end tokens_per_sec would be prefill-dominated — "
            "generate more tokens"
        )
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddp_practice_tpu.config import PrecisionPolicy
    from ddp_practice_tpu.inference import make_cache, make_generate_fn
    from ddp_practice_tpu.models import create_model

    device_kind, _, bw = _chip()
    policy = PrecisionPolicy.from_name(precision)
    kwargs = dict(
        vocab_size=vocab_size, max_len=prompt_len + max_new_tokens
    )
    if kv_cache == "int8":
        kwargs["kv_cache_dtype"] = "int8"
    elif kv_cache != "policy":
        raise ValueError(f"kv_cache {kv_cache!r} (want 'policy'|'int8')")
    kwargs.update(model_kwargs or {})
    model = create_model(model_name, policy=policy, **kwargs)
    rng = np.random.default_rng(seed)
    prompt = jnp.asarray(
        rng.integers(0, vocab_size, (batch_size, prompt_len)), jnp.int32
    )
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    if stream_dtype is None:
        stream_dtype = "bf16" if precision == "bf16" else "fp32"
    if stream_dtype not in ("bf16", "fp32"):
        raise ValueError(f"stream_dtype {stream_dtype!r} (want bf16|fp32)")
    param_bytes = 2 if stream_dtype == "bf16" else 4
    if stream_dtype == "bf16":
        from ddp_practice_tpu.inference import cast_params_for_streaming

        params = cast_params_for_streaming(params)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    gen = jax.jit(
        make_generate_fn(
            model,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            top_k=top_k,
        )
    )
    key = jax.random.PRNGKey(seed + 1)
    for i in range(max(warmup_calls, 1)):
        tokens = gen(params, prompt, jax.random.fold_in(key, i))
    _fence = int(jax.device_get(tokens[0, -1]))

    # prefill-only program, timed separately so the decode-step metrics can
    # exclude it (same cache allocation + prompt pass as gen()'s first leg).
    # Both windows are fenced with one dispatch + one host readback per
    # call, so the per-call dispatch + readback overhead appears
    # identically in dt and prefill_dt and cancels in the subtraction,
    # leaving pure decode-scan time.
    @jax.jit
    def prefill_only(params, prompt):
        cache = make_cache(model, batch_size, prompt_len + max_new_tokens)
        logits, _ = model.apply(
            {"params": params, "cache": cache},
            prompt, decode=True, mutable=["cache"],
        )
        return logits[:, -1, 0]

    for _ in range(2):  # compile + one warm rep
        _fence = float(jax.device_get(prefill_only(params, prompt)[0]))
    t0 = time.perf_counter()
    for _ in range(calls):
        _fence = float(jax.device_get(prefill_only(params, prompt)[0]))
    prefill_dt = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(calls):
        tokens = gen(params, prompt, jax.random.fold_in(key, 100 + i))
        _fence = int(jax.device_get(tokens[0, -1]))  # fence every call
    dt = time.perf_counter() - t0
    # decode-only window; prefill can't exceed the whole, but guard the
    # subtraction against timer noise on tiny configs. When the floor
    # engages, the record says so (decode_window_clamped) — the advisor
    # flagged that ms_per_token_step/mbu would otherwise quietly come
    # from the fallback instead of the measurement
    decode_window_clamped = dt - prefill_dt < 0.2 * dt
    decode_dt = max(dt - prefill_dt, 0.2 * dt)
    if decode_window_clamped:
        import sys as _sys

        print(
            "[bench] decode window clamped to 20% of the call: prefill "
            f"timing ({prefill_dt:.3f}s) ate >80% of {dt:.3f}s — "
            "ms_per_token_step/mbu come from the floor, not the "
            "measurement",
            file=_sys.stderr,
        )

    # generation here is an UNSHARDED jit: it runs on one device no matter
    # how many are visible (unlike bench_lm_train's data-parallel mesh),
    # so per-chip rates divide by 1, not jax.device_count()
    n_chips = 1
    new_tokens = calls * batch_size * max_new_tokens
    tps = new_tokens / dt
    # param reads/sec (batched), decode loop only — prefill subtracted
    steps_per_sec = calls * max_new_tokens / decode_dt
    out = {
        "model": model_name,
        "mode": "decode",
        "prompt_len": prompt_len,
        "max_new_tokens": max_new_tokens,
        "batch_size": batch_size,
        "vocab_size": vocab_size,
        "precision": precision,
        "stream_dtype": stream_dtype,
        "device_kind": device_kind,
        "n_chips": n_chips,
        "n_params": n_params,
        "tokens_per_sec": round(tps, 1),
        "tokens_per_sec_per_chip": round(tps / n_chips, 1),
        "ms_per_token_step": round(1e3 / steps_per_sec, 3),
        "seconds_per_call": round(dt / calls, 3),
        "prefill_ms_per_call": round(prefill_dt / calls * 1e3, 1),
        "kv_cache": "int8" if kv_cache == "int8" else policy.name,
    }
    if decode_window_clamped:
        out["decode_window_clamped"] = True
    # mbu_pct: the PARAMS-ONLY floor at the streamed dtype — kept
    # for cross-round comparability, but note it mathematically
    # CAPS below 100% whenever the cache read is a real fraction of
    # traffic (at bs=8/L=640/bf16 the cap is params/(params+cache)
    # ~= 60% — BENCHMARKS.md round-5 decode section).
    bytes_per_sec = n_params * param_bytes * steps_per_sec
    out["mbu_pct"] = round(100.0 * bytes_per_sec / (bw * n_chips), 2)
    # mbu_total_pct: params + the KV bytes the step ACTUALLY reads
    # (the single-block kernel reads the full allocated L each step;
    # int8 adds its fp32 scale rows) — the honest utilization of
    # the memory system.
    depth = getattr(model, "depth", 0)
    dm = getattr(model, "hidden_dim", 0)
    heads = getattr(model, "num_heads", 0)
    L = prompt_len + max_new_tokens
    # cache bytes follow the CACHE dtype — the policy compute dtype
    # (or int8), NOT stream_dtype, which only governs the params
    # (the stream_dtype="fp32" override keeps a bf16-policy cache)
    if kv_cache == "int8":
        kv_elem_bytes = 1
    else:
        kv_elem_bytes = jnp.dtype(policy.compute_dtype).itemsize
    kv_step = 2 * depth * L * dm * batch_size * kv_elem_bytes
    if kv_cache == "int8":
        kv_step += 2 * depth * heads * L * 4 * batch_size
    out["kv_bytes_per_step_mb"] = round(kv_step / 2**20, 1)
    out["mbu_total_pct"] = round(
        100.0 * (n_params * param_bytes + kv_step) * steps_per_sec
        / (bw * n_chips), 2,
    )
    out["hbm_gbps"] = bw / 1e9
    return out


def bench_pipeline(
    *,
    num_stages: int = 4,
    microbatch_counts=(2, 4, 8),
    hidden_dim: int = 256,
    depth: int = 4,
    num_heads: int = 8,
    mlp_dim: int = 1024,
    vocab_size: int = 256,
    seq_len: int = 256,
    mb_rows: int = 4,
    fixed_global_batch: int = 0,
    steps: int = 5,
    warmup: int = 2,
    precision: str = "bf16",
) -> list:
    """Pipeline schedule comparison: GPipe vs 1F1B over the microbatch
    count M, on whatever mesh the current devices allow (pipe=num_stages,
    data=rest).

    Two quantities per (schedule, M):

    - ms/step. Default mode holds the per-microbatch size FIXED (global
      batch grows with M), so pipeline efficiency = ideal/actual falls
      out of the schedule-length model t(M) ~ slope * (M + overhead):
      efficiency = slope * M / t(M), slope estimated from the two largest
      M. With `fixed_global_batch` set, the global batch stays constant
      (microbatches shrink as M grows) — the memory-story mode;
    - compiled temp memory (XLA memory_analysis) — at fixed global batch
      every input/output buffer is M-independent, so this isolates the
      schedules' activation state: GPipe's scan-transpose stash grows
      with M, 1F1B's ring stash must not.

    Run on the 8-virtual-device CPU mesh for the schedule comparison
    (pipe > 1 needs multiple devices; the CI TPU is a single chip) — the
    RELATIVE schedule behavior is device-independent; absolute ms/step on
    CPU is not a TPU number and BENCHMARKS.md never quotes it as one.
    """
    import time

    import jax
    import jax.numpy as jnp

    from ddp_practice_tpu.config import MeshConfig, PrecisionPolicy, TrainConfig
    from ddp_practice_tpu.models import create_model
    from ddp_practice_tpu.parallel.mesh import (
        batch_sharding,
        build_mesh,
        shard_state,
    )
    from ddp_practice_tpu.parallel.ring import set_current_mesh
    from ddp_practice_tpu.parallel.sharding_rules import param_sharding_rules
    from ddp_practice_tpu.train.state import create_state, make_optimizer
    from ddp_practice_tpu.train.steps import make_lm_train_step

    n_dev = jax.device_count()
    if n_dev % num_stages != 0:
        raise ValueError(f"{n_dev} devices not divisible by pipe={num_stages}")
    dp = n_dev // num_stages
    policy = PrecisionPolicy.from_name(precision)
    results = []
    for schedule in ("gpipe", "1f1b"):
        for mb_count in microbatch_counts:
            mesh = build_mesh(MeshConfig(data=dp, pipe=num_stages))
            set_current_mesh(mesh)
            try:
                model = create_model(
                    "lm_pipe", policy=policy, vocab_size=vocab_size,
                    max_len=seq_len, hidden_dim=hidden_dim, depth=depth,
                    num_heads=num_heads, mlp_dim=mlp_dim,
                    num_stages=num_stages, num_microbatches=mb_count,
                    schedule=schedule,
                )
                tx = make_optimizer(
                    TrainConfig(optimizer="adamw", learning_rate=1e-3)
                )
                if fixed_global_batch:
                    if fixed_global_batch % (mb_count * dp):
                        raise ValueError(
                            f"fixed_global_batch {fixed_global_batch} not "
                            f"divisible by M*dp = {mb_count * dp}"
                        )
                    b = fixed_global_batch
                else:
                    b = mb_count * mb_rows * dp
                sample = jnp.zeros((b, seq_len), jnp.int32)

                def init_fn(r):
                    return create_state(
                        model, tx, rng=r, sample_input=sample
                    )

                abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
                shardings = shard_state(
                    abstract, mesh, param_sharding_rules("lm_pipe")
                )
                state = jax.jit(init_fn, out_shardings=shardings)(
                    jax.random.PRNGKey(0)
                )
                step = make_lm_train_step(
                    model, tx, mesh=mesh, state_shardings=shardings,
                    batch_shardings=batch_sharding(mesh),
                )
                rng = np.random.default_rng(0)
                batch = {
                    "tokens": jnp.asarray(
                        rng.integers(0, vocab_size, (b, seq_len + 1)),
                        jnp.int32,
                    )
                }
                temp_bytes = None
                try:
                    compiled = step.lower(state, batch).compile()
                    mem = compiled.memory_analysis()
                    if mem is not None:
                        temp_bytes = int(mem.temp_size_in_bytes)
                except Exception:  # noqa: BLE001 — backend-dependent API
                    pass
                for _ in range(max(warmup, 1)):  # >=1: compile + metrics
                    state, metrics = step(state, batch)
                _ = float(metrics["loss"])
                steps = max(steps, 1)
                t0 = time.perf_counter()
                for _ in range(steps):
                    state, metrics = step(state, batch)
                    _ = float(metrics["loss"])  # fence (serializes on CPU)
                dt = time.perf_counter() - t0
                results.append({
                    "schedule": schedule,
                    "num_stages": num_stages,
                    "microbatches": mb_count,
                    "global_batch": b,
                    "seq_len": seq_len,
                    "ms_per_step": round(dt / steps * 1e3, 1),
                    "temp_bytes": temp_bytes,
                    "loss": round(float(metrics["loss"]), 4),
                })
            finally:
                set_current_mesh(None)
    if fixed_global_batch:
        return results  # constant work per step: the slope model is moot
    # schedule-length model: slope from the two largest M of each schedule
    for schedule in ("gpipe", "1f1b"):
        rs = [r for r in results if r["schedule"] == schedule]
        rs.sort(key=lambda r: r["microbatches"])
        if len(rs) >= 2:
            a, bb = rs[-2], rs[-1]
            slope = (bb["ms_per_step"] - a["ms_per_step"]) / (
                bb["microbatches"] - a["microbatches"]
            )
            for r in rs:
                if slope > 0:
                    r["efficiency_pct"] = round(
                        100.0 * slope * r["microbatches"] / r["ms_per_step"],
                        1,
                    )
    return results


def bench_serve(**kwargs) -> dict:
    """Continuous-batching vs static-batch serving on one Poisson trace.

    Delegates to serve/bench.py serve_bench (the serving subsystem owns
    its methodology — see that module's docstring); registered here so
    the benchmark surface stays one import. Returns the report dict with
    per-mode tokens/sec and TTFT/latency percentiles plus the
    continuous/static throughput ratio (BENCHMARKS.md serving section).
    """
    from ddp_practice_tpu.serve.bench import serve_bench

    return serve_bench(**kwargs)
