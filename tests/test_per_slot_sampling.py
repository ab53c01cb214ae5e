"""Per-slot sampling (inference.sample_logits_batch + engine wiring).

The contract: ONE jitted decode program serves a batch mixing greedy
and sampled rows with arbitrary per-request (temperature, top_k,
top_p), bit-identical to the per-request `sample_logits` path, and
never recompiles when the params change — they are traced (b,) arrays,
not compile-time constants. The kernel-level pins are jit-free; the
engine-level identity and zero-recompile pin runs in tier-1 too.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.inference import sample_logits, sample_logits_batch
from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.serve import EngineConfig, PagedEngine
from ddp_practice_tpu.serve.engine import warm_engine

VOCAB = 32


# ------------------------------------------------------ kernel-level pins
@pytest.mark.fast
def test_batch_rows_bit_identical_to_per_request_sampler(devices):
    """Each row of sample_logits_batch == sample_logits called alone on
    that row with the same key and params — including the greedy row
    (temperature 0) and every filter combination."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, VOCAB)), jnp.float32)
    params = [(0.0, 0, 0.0),      # greedy
              (0.8, 5, 0.0),      # top-k only
              (1.2, 0, 0.9),      # top-p only
              (0.7, 3, 0.85)]     # composed k-then-p
    keys = jnp.stack([
        jax.random.PRNGKey(100 + i) for i in range(len(params))
    ])

    got = sample_logits_batch(
        logits, keys,
        temperature=jnp.asarray([p[0] for p in params]),
        top_k=jnp.asarray([p[1] for p in params]),
        top_p=jnp.asarray([p[2] for p in params]),
    )
    for i, (t, k, p) in enumerate(params):
        want = sample_logits(
            logits[i:i + 1], keys[i], temperature=t, top_k=k, top_p=p
        )[0]
        assert int(got[i]) == int(want), (i, params[i])


@pytest.mark.fast
def test_batch_sampler_row_independence(devices):
    """A row's draw depends only on its own key/params — reshuffling
    its batchmates' params must not move it (the property that lets
    the engine mix greedy and sampled requests in one dispatch)."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(3, VOCAB)), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(3)])

    def draw(neighbors):
        t = jnp.asarray([0.9, neighbors[0], neighbors[1]])
        k = jnp.asarray([4, 0, 7])
        p = jnp.asarray([0.0, 0.95, 0.5])
        return int(sample_logits_batch(
            logits, keys, temperature=t, top_k=k, top_p=p)[0])

    assert draw((0.0, 1.5)) == draw((2.0, 0.3))


@pytest.mark.fast
def test_batch_sampler_edge_params(devices):
    """top_k past the vocab is a no-op filter (clamped), negative
    temperature is greedy, and greedy ignores its key entirely."""
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(2, VOCAB)), jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(5), jax.random.PRNGKey(6)])
    a = sample_logits_batch(
        logits, keys, temperature=jnp.asarray([0.8, -1.0]),
        top_k=jnp.asarray([VOCAB + 50, 0]), top_p=jnp.zeros(2))
    b = sample_logits_batch(
        logits, keys, temperature=jnp.asarray([0.8, 0.0]),
        top_k=jnp.asarray([0, 0]), top_p=jnp.zeros(2))
    assert int(a[0]) == int(b[0])            # over-vocab k == no filter
    assert int(a[1]) == int(b[1]) == int(jnp.argmax(logits[1]))
    other = jnp.stack([keys[0], jax.random.PRNGKey(7)])
    c = sample_logits_batch(
        logits, other, temperature=jnp.asarray([0.8, 0.0]),
        top_k=jnp.zeros(2, jnp.int32), top_p=jnp.zeros(2))
    assert int(c[1]) == int(b[1])            # greedy row is key-blind


# ---------------------------------------------------------- engine wiring
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


SKW = dict(max_slots=3, prompt_buckets=(8,), max_len=64)


def _run_slot(eng, prompt, n=10, seed=7, sampling=None):
    kw = {} if sampling is None else {"sampling": sampling}
    slot = eng.admit(prompt, seed=seed, **kw)
    out = []
    for _ in range(n):
        out.append(int(eng.step_burst()[0][slot]))
    eng.release(slot)
    return out


def test_per_slot_stream_identical_to_config_baked_engine(lm, devices,
                                                          compile_guard):
    """A slot sampled at (t, k, p) in the per-slot engine emits the
    same stream as a legacy engine with those params BAKED into its
    decode program — and a greedy-override slot matches a plain greedy
    engine. Then the churn pin: admit/decode/release across wildly
    different per-slot params compiles NOTHING new."""
    model, params = lm
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, VOCAB, 7).tolist()

    legacy = PagedEngine(model, params, EngineConfig(
        **SKW, temperature=0.8, top_k=5, top_p=0.9))
    warm_engine(legacy)
    ps = PagedEngine(model, params, EngineConfig(
        **SKW, per_slot_sampling=True))
    warm_engine(ps)
    greedy = PagedEngine(model, params, EngineConfig(**SKW))
    warm_engine(greedy)

    assert _run_slot(legacy, prompt) == _run_slot(
        ps, prompt, sampling=(0.8, 5, 0.9))
    g = _run_slot(greedy, prompt)
    assert g == _run_slot(ps, prompt, sampling=(0.0, 0, 0.0))
    assert g == _run_slot(ps, prompt)   # defaults = config (greedy)

    with compile_guard(ps):
        slots = [ps.admit(prompt, seed=s, sampling=samp)
                 for s, samp in ((1, (0.0, 0, 0.0)),
                                 (2, (1.3, 7, 0.0)),
                                 (3, (0.5, 0, 0.95)))]
        ps.step_burst()
        for s in slots:
            ps.release(s)


@pytest.mark.slow
def test_sampling_override_without_flag_raises(lm, devices):
    """Silently decoding at the WRONG params is the one outcome this
    must never produce: the legacy engine bakes config params into its
    decode program, so a per-request override it cannot honor raises
    at admit — and leaves no slot half-admitted."""
    model, params = lm
    eng = PagedEngine(model, params, EngineConfig(**SKW))
    warm_engine(eng)
    prompt = [1, 2, 3, 4]
    with pytest.raises(ValueError, match="per_slot_sampling"):
        eng.admit(prompt, sampling=(0.7, 0, 0.0))
    assert eng.num_active == 0
    # config-matching overrides are fine (they change nothing)
    slot = eng.admit(prompt, sampling=(0.0, 0, 0.0))
    eng.release(slot)


def test_spec_decode_excludes_per_slot_sampling(lm, devices):
    """Exact speculative acceptance is greedy string matching; the
    combination is rejected at construction, before any compile."""
    model, params = lm
    with pytest.raises(ValueError, match="per_slot_sampling"):
        PagedEngine(model, params, EngineConfig(
            max_slots=2, prompt_buckets=(8,), max_len=64,
            block_size=8, max_blocks_per_slot=10,
            spec_decode=True, per_slot_sampling=True))
