"""Generate text from a trained LM checkpoint.

    python -m ddp_practice_tpu.generate --ckpt_dir ckpts \
        --prompt "def main" --max_new_tokens 256 --temperature 0.8 --top_k 40

The training invocation's state-shaping knobs (model, optimizer, seq_len,
vocab) are read back from the checkpoint manifest (train/loop.py save()),
so only the checkpoint directory is required; flags override. The
reference has no inference path to cite — this is framework surface the
reference's training-only design stops short of (origin_main.py:113 saves
and exits).
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from ddp_practice_tpu import checkpoint as ckpt
from ddp_practice_tpu.config import PrecisionPolicy, TrainConfig
from ddp_practice_tpu.inference import (
    cast_params_for_streaming,
    decode_bytes,
    encode_bytes,
    make_generate_fn,
)
from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.train.state import create_state, make_optimizer
from ddp_practice_tpu.utils.backend import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ckpt_dir", required=True)
    p.add_argument("--prompt", default="\n")
    p.add_argument("--max_new_tokens", type=int, default=256)
    p.add_argument("--temperature", type=float, default=0.8,
                   help="0 = greedy argmax")
    p.add_argument("--top_k", type=int, default=0)
    p.add_argument("--top_p", type=float, default=0.0)
    p.add_argument("--eos_id", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--model", default=None,
                   help="override the manifest's model name")
    p.add_argument("--seq_len", type=int, default=0,
                   help="override the manifest's max sequence length")
    p.add_argument("--kv_cache", default="policy",
                   choices=["policy", "int8"],
                   help="KV-cache storage: policy dtype (default) or int8 "
                        "(quantized cache + scales — ~1%% logit error, "
                        "faster past ~768-token contexts; BENCHMARKS.md)")
    return p


def load_lm(ckpt_dir, *, model=None, seq_len=0, kv_cache="policy") -> tuple:
    """(model, params, batch_stats, step) rebuilt from the checkpoint
    manifest + leaves — shared by this CLI and the serving entry point
    (serve/cli.py), which is why it takes plain kwargs rather than the
    parsed argparse namespace."""
    manifest = ckpt.latest_manifest(ckpt_dir)
    if manifest is None:
        raise SystemExit(f"no checkpoint under {ckpt_dir!r}")
    extra = manifest.get("extra", {})
    name = model or extra.get("model")
    if not name or not name.startswith("lm_"):
        raise SystemExit(
            f"checkpoint model {name!r} is not an LM (lm_*) — generation "
            "needs a decoder; pass --model to override"
        )
    if name == "lm_pipe":
        raise SystemExit(
            "lm_pipe has no KV-cache decode path — generate from an "
            "equivalent lm_tiny/lm_base checkpoint instead"
        )
    seq_len = seq_len or int(extra.get("seq_len", 2048))
    vocab = int(extra.get("vocab_size", 256))
    policy = (
        PrecisionPolicy.bf16()
        if extra.get("precision_policy") == "bf16"
        else PrecisionPolicy.fp32()
    )
    model_kw = {}
    if kv_cache == "int8":
        model_kw["kv_cache_dtype"] = "int8"
    model = create_model(
        name, policy=policy, vocab_size=vocab, max_len=seq_len,
        remat=bool(extra.get("remat", False)),
        pos_emb=extra.get("pos_emb", "learned"),
        tied_embeddings=bool(extra.get("tied_embeddings", False)),
        **model_kw,
    )
    # rebuild the train-state TREE abstractly (shapes only, no init FLOPs)
    # so restore()'s strict path check accepts the leaves
    cfg = TrainConfig(
        model=name,
        optimizer=extra.get("optimizer", "sgd"),
        momentum=float(extra.get("momentum", 0.0)),
        clip_norm=float(extra.get("clip_norm", 0.0)),
        weight_decay=float(extra.get("weight_decay", 0.0)),
        accum_steps=int(extra.get("accum_steps", 1)),
    )
    tx = make_optimizer(cfg)
    sample = jnp.zeros((1, seq_len), jnp.int32)
    abstract = jax.eval_shape(
        lambda r: create_state(model, tx, rng=r, sample_input=sample),
        jax.random.PRNGKey(0),
    )
    state = ckpt.restore(ckpt_dir, abstract)
    params = state.params
    if extra.get("precision_policy") == "bf16":
        # inference needs no fp32 masters: stream bf16 params (half the
        # HBM traffic per decode step; bit-identical under this policy)
        params = cast_params_for_streaming(params)
    # non-param state (lm_moe router selection bias) rides along so
    # generation routes like training did (inference.make_generate_fn)
    return (model, jax.device_put(params), state.batch_stats,
            int(extra.get("step", -1)))


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    model, params, batch_stats, step = load_lm(
        args.ckpt_dir, model=args.model, seq_len=args.seq_len,
        kv_cache=args.kv_cache,
    )
    prompt = jnp.asarray(encode_bytes(args.prompt))
    gen = jax.jit(
        make_generate_fn(
            model,
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            eos_id=args.eos_id,
            batch_stats=batch_stats,
        )
    )
    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    tokens = jax.device_get(gen(params, prompt, key))
    dt = time.perf_counter() - t0
    generated = tokens[0, prompt.shape[1]:]
    if args.eos_id is not None:
        # early EOS leaves pad_id (0) in the post-EOS slots (inference.py
        # done-mask); cut at the first EOS so the text carries no NULs
        hits = (generated == args.eos_id).nonzero()[0]
        if hits.size:
            generated = generated[: int(hits[0])]
    text = decode_bytes(generated)
    print(text)
    print(
        f"[generate] ckpt step {step}, {args.max_new_tokens} tokens in "
        f"{dt:.2f}s ({args.max_new_tokens / dt:.1f} tok/s, incl. compile)",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
