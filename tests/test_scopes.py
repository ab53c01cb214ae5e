"""Every device op that does model or optimizer work says whose it is.

The contract the profiler's readers rest on (perf/lib/scopes.py): the
`op_name` of each op of a step program holds a path, and one segment of the
path is a token of a fixed vocabulary: a flax module's name (`attn`, `mlp`,
`mamba3`, `ln1`, `lm_head`, ...), a hand-made scope of `ops/` (`moe_route`,
`ssm_step`, ...), or one of the three that `train/steps.py` and
`serve/engine.py` put around what no module owns: `loss`, `optimizer`,
`sample`. Code moved out of a module, or a new piece of a step written
outside every scope, goes dark in every trace: these cases fail first.

Here: toy models of the five families, compiled on the CPU (a Pallas kernel
is interpreted there, its ops keep the kernel's name in their path). The
cells' own widths, compiled for a described v5e, are in
tests/test_tpu_compile.py (`-k scopes`), which shares what is below.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perf"))

import deepseek_toy  # noqa: E402
import jamba_toy  # noqa: E402
import nemotron_toy  # noqa: E402
from ddp_practice_tpu.config import PrecisionPolicy, TrainConfig  # noqa: E402
from ddp_practice_tpu.models import create_model  # noqa: E402
from ddp_practice_tpu.serve.engine import EngineConfig, PagedEngine  # noqa: E402
from ddp_practice_tpu.train import steps  # noqa: E402
from ddp_practice_tpu.train.state import create_state, make_optimizer  # noqa: E402
from perf.lib import scopes  # noqa: E402

NEW_SCOPES = ("loss", "optimizer", "sample")
OWN_OPS = ("dot", "convolution", "fusion", "reduce", "custom-call")
# What is known to run outside every scope, and why no scope is owed: each a
# pattern over the whole path. PERF.md section 7 lists what they cost on the
# chip. Anything else without a class counts against the 95%.
OUTSIDE = (
    (r"[^/]*", "no path beyond a primitive's name: the compiler's own op (a "
     "relayout, XLA:CPU's tree reductions), or a lowering rule that drops "
     "the name stack (cumsum's reduce-window in moe_route)"),
    (r"jit\(_prefill_admit\)/(dynamic_update_slice|slice)",
     "a prompt's pages and state rows placed in the pool: the engine's "
     "bookkeeping (serve/kv_pages.py scatter_prompt_blocks), no module's"),
    (r"jit\(resident_chunk\)/while/body/closed_call/"
     r"(jit\(_take\)/.*|gather|slice|select_n)",
     "the resident batch gathered from the corpus in HBM"),
    (r"jit\(_decode_burst\)/(slice|concatenate|while/cond/lt"
     r"|while/body/(add|dynamic_update_slice|broadcast_in_dim)"
     r"|while/body/closed_call(/add|/convert_element_type)?)",
     "the burst's own loop: its counter, the lengths it advances, the "
     "tokens it stacks, the expert counters it zeroes and sums"),
    (r".*/(\w+\._unfused|TransformerLM|HybridLM|MLALM|ViT)/add",
     "the residual add between two modules, which is neither's: XLA:TPU "
     "fuses it into a neighbour and it reads that neighbour's scope"),
)
_OUTSIDE = re.compile("|".join(f"(?:{rx})" for rx, _ in OUTSIDE))


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """The persistent compilation cache keys a program WITHOUT its metadata:
    where an earlier test of the process turned it on, the build with the
    scopes patched away would be handed the scoped build's executable,
    paths and all."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def own_ops(text: str) -> list:
    """[(opcode, name, path, (class, direction) or None)] of a compiled
    program's own device ops; a custom call without a name of its own is
    the compiler's (ConcatBitcast, a gather's index check), a Pallas kernel
    carries its `name=`."""
    return [(op, name, path, scopes.classify(path))
            for op, name, path in scopes.hlo_ops(text)
            if op in OWN_OPS and not re.fullmatch(r"custom-call(\.\d+)?",
                                                  name)]


def hold(text: str, want: set, what: str) -> set:
    """The contract on one program's optimized HLO; returns the classes
    seen."""
    ops = [o for o in own_ops(text)
           if o[3] or not _OUTSIDE.fullmatch(o[2] or "")]
    dark = [(name, path) for _, name, path, cls in ops if cls is None]
    assert ops and len(dark) <= 0.05 * len(ops), (what, len(ops), dark[:12])
    seen = {cls[0] for _, _, _, cls in ops if cls}
    assert want <= seen, (what, sorted(seen))
    return seen


def without_metadata(text: str) -> str:
    """An optimized HLO module's text with what a scope can change taken
    out: each instruction's `metadata={...}` (op_name, stack frame) and the
    module's tables of files, functions and stack frames."""
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:.+\n)*", "\n", text)
    return re.sub(r",? ?metadata=\{[^}]*\}", "", text)


def kernels_agree(text: str) -> int:
    """The kernels' names are the ground truth of the direction rule and of
    four classes: every path (of any instruction, inside a fusion too) that
    holds a kernel's name as a segment reads as the name says. Returns how
    many paths were held."""
    rules = ((r"flash_(short_)?bwd\w*", ("attn", "bwd")),
             (r"flash_(short_)?fwd\w*", ("attn", "fwd")),
             (r"rope_flat_bwd", ("attn", "bwd")),
             (r"rope_flat_qk|paged_decode\w*", ("attn", "fwd")),
             (r"(ssm|sel)_(step|scan)", ("mixer", "fwd")),
             (r"moe_gmm\w*", ("mlp", "fwd")))
    held = 0
    for path in set(re.findall(r'op_name="([^"]+)"', text)):
        if not path.startswith("jit("):
            continue   # a kernel body's op that lost its stack: OUTSIDE
        for rx, want in rules:
            if any(re.fullmatch(rx, seg) for seg in path.split("/")):
                assert scopes.classify(path) == want, path
                held += 1
    return held


# ------------------------------------------------------------------ models
def _train_text(model, sample, lm: bool) -> str:
    tx = make_optimizer(TrainConfig(
        model="lm_tiny", optimizer="adamw", learning_rate=3e-4,
        weight_decay=0.01), 14)
    state = jax.eval_shape(
        lambda r: create_state(model, tx, rng=r, sample_input=sample),
        jax.random.PRNGKey(0))
    if lm:
        step = steps.make_lm_train_step(model, tx)
        batch = {"tokens": jax.ShapeDtypeStruct(
            (sample.shape[0], sample.shape[1] + 1), jnp.int32)}
    else:
        step = steps.make_train_step(model, tx)
        batch = {"image": jax.ShapeDtypeStruct(sample.shape, jnp.uint8),
                 "label": jax.ShapeDtypeStruct(sample.shape[:1], jnp.int32)}
    return step.lower(state, batch).compile().as_text()


def _serve_texts(model, params) -> dict:
    """The admission prefill (first bucket) and the decode burst of a
    `PagedEngine` around the model, as the engine itself jits them."""
    engine = PagedEngine(model, params, EngineConfig(
        max_slots=3, prompt_buckets=(8, 16), block_size=8, decode_burst=2,
        max_blocks_per_slot=6, temperature=0.0))
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    slots, mb = 3, engine.max_blocks_per_slot
    logits = sds((slots, model.vocab_size), model.dtype)
    prefill = engine._prefill_jit.lower(
        params, engine._cache, logits, sds((1, 8), i32), sds((), i32),
        sds((1,), i32), sds((), i32))
    decode = engine._decode_jit.lower(
        params, engine._cache, logits, sds((slots,), i32),
        sds((slots,), jnp.bool_), sds((slots, 2), jnp.uint32),
        sds((slots, mb), i32), sds((slots,), i32), None)
    return {"prefill": prefill.compile().as_text(),
            "decode_burst": decode.compile().as_text()}


def _lm(**kw):
    return create_model("lm_tiny", policy=PrecisionPolicy.bf16(),
                        vocab_size=64, max_len=128, pos_emb="rope",
                        tied_embeddings=True, depth=2, **kw)


def _lm_params(model):
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])


TRAIN = {
    "lm_base": lambda: _train_text(
        _lm(), jnp.zeros((2, 128), jnp.int32), True),
    "lm_base_flash": lambda: _train_text(
        _lm(attn_impl="flash"), jnp.zeros((2, 128), jnp.int32), True),
    "lm_base_untied": lambda: _train_text(create_model(
        "lm_tiny", policy=PrecisionPolicy.bf16(), vocab_size=64, max_len=32,
        depth=2), jnp.zeros((2, 32), jnp.int32), True),
    "vit": lambda: _train_text(create_model(
        "vit_tiny", policy=PrecisionPolicy.bf16(), num_classes=10,
        axis_name=None, depth=2, fused=False),
        jnp.zeros((4, 32, 32, 3), jnp.float32), False),
}
SERVE = {
    "lm_base": lambda: (lambda m: (m, _lm_params(m)))(_lm()),
    "nemotron_h": lambda: nemotron_toy.model_and_params(
        nemotron_toy.config()),
    "deepseek_v3": lambda: deepseek_toy.model_and_params(
        deepseek_toy.config()),
    "jamba": lambda: jamba_toy.model_and_params(jamba_toy.config()),
}
# the classes a family's programs must show besides `sample`
SERVE_CLASSES = {
    "lm_base": {"attn", "mlp", "norm", "embed"},
    "nemotron_h": {"attn", "mlp", "mixer", "norm", "head", "embed"},
    "deepseek_v3": {"attn", "mlp", "norm", "head", "embed"},
    "jamba": {"attn", "mlp", "mixer", "norm", "embed"},
}


@pytest.fixture(scope="module")
def train_texts():
    cache = {}
    return lambda family: cache.setdefault(family, TRAIN[family]())


@pytest.fixture(scope="module")
def serve_texts():
    cache = {}
    return lambda family: cache.setdefault(
        family, _serve_texts(*SERVE[family]()))


@pytest.mark.parametrize("family", sorted(TRAIN))
def test_train_step_is_scoped(train_texts, family):
    """Forward and backward under the modules' names, the loss under `loss`
    and the update under `optimizer`: 95% of the step's own ops."""
    text = train_texts(family)
    seen = hold(text, {"loss", "optimizer", "attn", "mlp", "norm"}, family)
    assert seen & {"head", "embed"}, sorted(seen)
    directions = {cls[1] for *_, cls in own_ops(text) if cls}
    assert directions == {"fwd", "bwd", "opt"}, directions


@pytest.mark.parametrize("family", sorted(TRAIN))
def test_optimizer_ops_read_opt_and_nothing_else_does(train_texts, family):
    for _, name, path, cls in own_ops(train_texts(family)):
        if cls:
            assert (cls[1] == "opt") == (cls[0] == "optimizer"), (name, path)
            assert (cls[1] == "opt") == ("/optimizer/" in path), path


def test_flash_kernels_names_agree_with_the_direction_rule(train_texts):
    assert kernels_agree(train_texts("lm_base_flash")) >= 20
    assert kernels_agree(train_texts("lm_base")) == 0     # plain XLA there


def test_a_loss_path_comes_wrapped_and_is_read(train_texts):
    """A hand-made scope inside `value_and_grad` reads `jvp(loss)` and, in
    the backward pass, `transpose(jvp(loss))`."""
    paths = set(re.findall(r'op_name="([^"]+)"', train_texts("lm_base")))
    wrapped = {p for p in paths if "jvp(loss)" in p}
    assert any("transpose(jvp(loss))" in p for p in wrapped)
    assert any("transpose(" not in p for p in wrapped)
    for p in wrapped:
        cls = scopes.classify(p)
        assert cls == ("loss", "bwd" if "transpose(" in p else "fwd"), p


@pytest.mark.parametrize("program", ["prefill", "decode_burst"])
@pytest.mark.parametrize("family", sorted(SERVE))
def test_serve_program_is_scoped(serve_texts, family, program):
    text = serve_texts(family)[program]
    hold(text, {"sample"} | SERVE_CLASSES[family], f"{family} {program}")
    assert {cls[1] for *_, cls in own_ops(text) if cls} == {"fwd"}
    kernels_agree(text)


@pytest.mark.parametrize("family", sorted(SERVE))
def test_sampling_is_under_sample_in_the_burst(serve_texts, family):
    """The argmax over the vocabulary and the finite-logits check of a decode
    step: `sample`, not the head's and not nobody's."""
    paths = {p for p in re.findall(r'op_name="([^"]+)"',
                                   serve_texts(family)["decode_burst"])
             if p.startswith("jit(")}
    finite = [p for p in paths if p.endswith("/is_finite")]
    assert finite and all(
        scopes.classify(p) == ("sample", "fwd") for p in finite), finite
    # the argmax is a reduce over (value, iota) pairs
    assert any(p.endswith("/sample/reduce") for p in paths)
    assert any(p.endswith("/sample/iota") for p in paths)


def test_the_new_scopes_are_metadata(monkeypatch):
    """With `loss`, `optimizer` and `sample` patched away the optimized HLO
    of a train step is the same text but for `metadata={...}`."""
    import contextlib

    def build():
        return _train_text(_lm(), jnp.zeros((2, 128), jnp.int32), True)

    scoped = build()
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: contextlib.nullcontext() if name in NEW_SCOPES
        else real(name))
    bare = build()
    assert "/optimizer/" in scoped and "/optimizer/" not in bare
    assert "jvp(loss)" in scoped and "jvp(loss)" not in bare
    assert without_metadata(scoped) == without_metadata(bare)
