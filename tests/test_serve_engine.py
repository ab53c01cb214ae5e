"""Engine mechanism (serve/engine.py PagedEngine over serve/kv_pages.py).

Pinned: slot allocation/reuse semantics, the block budget (headroom
falls as slots grow and recovers at release, freed blocks are handed out
again), prompt bucketing, and the model contract (RoPE required — slots
decode at slot-local positions, which only relative encodings survive).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddp_practice_tpu.models import create_model
from ddp_practice_tpu.serve import EngineConfig, PagedEngine, SlotAllocator

VOCAB = 32


@pytest.fixture(scope="module")
def lm():
    model = create_model(
        "lm_tiny", vocab_size=VOCAB, max_len=96, hidden_dim=64,
        depth=2, num_heads=4, mlp_dim=128, pos_emb="rope",
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _engine(lm, **kw):
    model, params = lm
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("prompt_buckets", (8,))
    return PagedEngine(model, params, EngineConfig(**kw))


@pytest.mark.fast
def test_allocator_reuses_freed_slots(devices):
    a = SlotAllocator(2)
    s0, s1 = a.alloc(), a.alloc()
    assert (s0, s1) == (0, 1) and a.alloc() is None
    a.free(s0)
    assert a.num_used == 1 and a.alloc() == 0  # the freed slot comes back
    with pytest.raises(ValueError):
        a.free(7)


@pytest.mark.fast
def test_engine_requires_rope(devices):
    model = create_model(
        "lm_tiny", vocab_size=VOCAB, max_len=96, hidden_dim=64,
        depth=2, num_heads=4, mlp_dim=128,  # learned positions
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    with pytest.raises(ValueError, match="rope"):
        PagedEngine(model, params, EngineConfig())


def test_slot_reuse_after_release(devices, lm):
    """A released slot's successor generates correctly — the admission
    overwrite makes the previous occupant's cache invisible."""
    from ddp_practice_tpu.inference import make_generate_fn

    model, params = lm
    eng = _engine(lm)
    s0 = eng.admit([3, 1, 4])
    s1 = eng.admit([2, 7])
    for _ in range(4):
        eng.step()
    eng.release(s0)
    s2 = eng.admit([5, 5, 1, 2])   # must land in the freed slot
    assert s2 == s0
    n = 5
    got = [int(eng.step()[s2]) for _ in range(n)]
    gen = jax.jit(make_generate_fn(model, max_new_tokens=n, temperature=0.0))
    want = np.asarray(gen(params, jnp.asarray([[5, 5, 1, 2]], jnp.int32)))
    assert got == want[0, 4:].tolist()


def test_admit_when_full_raises(devices, lm):
    eng = _engine(lm)
    eng.admit([1]), eng.admit([2])
    with pytest.raises(RuntimeError, match="free slot"):
        eng.admit([3])


@pytest.mark.fast
def test_bucket_selection_and_overflow(devices, lm):
    eng = _engine(lm, prompt_buckets=(4, 8))
    assert eng.bucket_for(1) == 4
    assert eng.bucket_for(5) == 8
    with pytest.raises(ValueError, match="bucket"):
        eng.bucket_for(9)


def test_headroom_recovers_and_freed_blocks_are_reused(devices, lm):
    """The block budget is per request, not a clock: a slot takes its
    prompt's blocks at admit and one more whenever decode crosses a
    block edge, `headroom` says what is left, and release hands every
    block back — the next admission gets the very blocks just freed."""
    eng = _engine(lm, max_len=24, prompt_buckets=(8,), block_size=8)
    full = eng.headroom
    assert full == 8 * (eng.blocks.num_blocks - 1) == 48
    s = eng.admit([1, 2, 3])               # bucket 8 = one block
    assert eng.headroom == full - 8
    eng.step()                             # position 8 opens a second block
    assert eng.headroom == full - 16 and eng.context_len(s) == 9
    held = [int(b) for b in eng._pt[s, :eng._nblk[s]]]
    eng.release(s)
    assert eng.headroom == full and eng.num_active == 0
    # freed blocks go to the back of the free list: drain the blocks
    # that were never used and the next admission lands on the old ones
    spare = eng.blocks.alloc(eng.blocks.num_free - len(held))
    s2 = eng.admit([4, 4])
    eng.step()
    assert [int(b) for b in eng._pt[s2, :eng._nblk[s2]]] == held
    eng.blocks.free(spare)
    tok = eng.step()
    assert 0 <= int(tok[s2]) < VOCAB


def test_decode_burst_matches_single_steps(devices, lm):
    """A K-step burst dispatch emits exactly the K tokens that K
    token-granular steps would — multi-step scheduling changes dispatch
    cost, not tokens."""
    single = _engine(lm)
    s = single.admit([3, 1, 4, 1, 5])
    want = [int(single.step()[s]) for _ in range(8)]

    burst = _engine(lm, decode_burst=4)
    sb = burst.admit([3, 1, 4, 1, 5])
    got = []
    for _ in range(2):
        got.extend(int(row[sb]) for row in burst.step_burst())
    assert got == want
    assert burst.context_len(sb) == single.context_len(s) == 8 + 8
    with pytest.raises(RuntimeError, match="decode_burst"):
        burst.step()  # token-granular stepping needs decode_burst=1


def test_decode_shapes_stable_across_churn(devices, lm):
    """Admission/release churn leaves exactly one decode program and one
    prefill program per bucket width in the jit caches."""
    eng = _engine(lm, prompt_buckets=(4, 8))
    for i in range(6):
        s = eng.admit([1 + i] * (2 if i % 2 else 6))  # both buckets in play
        eng.step()
        eng.release(s)
    stats = eng.compile_stats()
    # (`cow_compiles` counts one jitted function shared by every engine
    # of the process: what it reads depends on the tests run before)
    del stats["cow_compiles"]
    assert stats == {"prefill_compiles": 2, "decode_compiles": 1,
                     "prefix_prefill_compiles": 0, "verify_compiles": 0}
