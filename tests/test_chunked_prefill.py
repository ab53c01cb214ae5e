"""Sarathi-style chunked prefill (serve/engine.py `prefill_chunk`).

The contract: a chunk-admitted prompt lands in exactly the state a
whole-prompt admission leaves behind — same tokens out, same radix
publication, decode entirely chunk-blind — while each chunk is one
bounded `_prefix_prefill` dispatch so long prompts stop monopolizing
the decode loop (what that does to TTFT on the chip is not measured:
ROADMAP W3 names the paired cell). Config
misuse is rejected at construction; token identity with the whole-prompt
admission is tier-1, the pump's bounds are `slow`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.serve import EngineConfig, PagedEngine
from ddp_practice_tpu.serve.engine import warm_engine

VOCAB = 32

PKW = dict(max_slots=3, block_size=8, max_blocks_per_slot=12,
           prefix_cache=True)


@pytest.fixture(scope="module")
def lm():
    model = create_model(
        "lm_tiny", vocab_size=VOCAB, max_len=128, hidden_dim=64,
        depth=2, num_heads=4, mlp_dim=128, pos_emb="rope",
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _run(eng, prompt, n=12, seed=0):
    """Admit, pump any pending prefill chunks, decode n tokens."""
    slot = eng.admit(prompt, seed=seed, max_positions=n)
    while getattr(eng, "is_prefilling", lambda s: False)(slot):
        eng.prefill_step(slot)
    out = []
    for _ in range(n):
        out.append(int(eng.step_burst()[0][slot]))
    eng.release(slot)
    return out


# ------------------------------------------------------ config validation
def test_chunk_config_gates(lm, devices):
    model, params = lm
    with pytest.raises(ValueError, match="prefix_cache"):
        PagedEngine(model, params, EngineConfig(
            **dict(PKW, prefix_cache=False), prefill_chunk=16))
    with pytest.raises(ValueError, match=">= 1"):
        PagedEngine(model, params, EngineConfig(**PKW, prefill_chunk=-4))
    with pytest.raises(ValueError, match="exceeds"):
        PagedEngine(model, params, EngineConfig(
            **PKW, prompt_buckets=(8,), prefill_chunk=16))


# ----------------------------------------------------------- equivalence
def test_chunked_prefill_matches_whole_prompt(lm, devices):
    """Token identity: the same long prompt through chunk-pumped
    prefill and through one whole-prompt dispatch. One retry for the
    image's XLA-CPU load nondeterminism (near-tied argmax over the toy
    model; same contract as tests/test_kv_pages.py) — a real
    divergence fails both attempts."""
    model, params = lm
    rng = np.random.default_rng(3)
    plain = PagedEngine(model, params, EngineConfig(
        **PKW, prompt_buckets=(8, 16, 64)))
    warm_engine(plain)
    chunked = PagedEngine(model, params, EngineConfig(
        **PKW, prefill_chunk=16))
    warm_engine(chunked)

    for attempt in range(2):
        prompt = rng.integers(1, VOCAB, 50).tolist()
        a = _run(plain, prompt)
        b = _run(chunked, prompt)
        if a == b:
            break
    assert a == b, (a, b)


@pytest.mark.slow
def test_chunk_pump_bounds_and_past_bucket_service(lm, devices,
                                                   compile_guard):
    """The pump runs at most ceil(len/chunk) bounded dispatches and
    the final one activates the slot; chunking also makes prompts past
    the largest bucket servable (each chunk buckets individually) —
    and none of this churn compiles anything after warmup."""
    model, params = lm
    rng = np.random.default_rng(4)
    eng = PagedEngine(model, params, EngineConfig(
        **PKW, prefill_chunk=16))
    warm_engine(eng)

    prompt = rng.integers(1, VOCAB, 50).tolist()
    slot = eng.admit(prompt, seed=0, max_positions=4)
    assert eng.is_prefilling(slot)
    pumps = 0
    while eng.is_prefilling(slot):
        done = eng.prefill_step(slot)
        pumps += 1
        assert done == (not eng.is_prefilling(slot))
    assert pumps <= -(-len(prompt) // 16)
    for _ in range(4):
        eng.step_burst()
    eng.release(slot)

    # past the largest warm bucket: unservable whole, servable chunked
    plain = PagedEngine(model, params, EngineConfig(
        **PKW, prompt_buckets=(8, 16, 64)))
    assert not plain.fits_prompt(90)
    assert eng.fits_prompt(90)
    big = rng.integers(1, VOCAB, 90).tolist()
    assert len(_run(eng, big, n=4)) == 4

    with compile_guard(eng):
        _run(eng, rng.integers(1, VOCAB, 40).tolist(), n=4)
