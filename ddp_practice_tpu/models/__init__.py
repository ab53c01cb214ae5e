"""Model zoo.

The reference defines a single ConvNet three times over (origin_main.py:9-31,
ddp_main.py:13-36, ddp_main_torchrun.py:12-35). Here models are flax.linen
modules defined once, parameterized by a precision policy and an optional
data-parallel axis name (which turns every BatchNorm into a SyncBatchNorm,
replacing ddp_main.py:120).

Ladder beyond parity (BASELINE.json configs): ResNet-18/50, ViT-Tiny.

`HybridLM` (models/hybrid_lm.py) is in the registry five times, one layout
of its pattern string each; the defaults are test-sized and the published
widths come as options (perf/families/*.py `model_options`):

    nemotron_h   'M' Mamba-2, 'E' LatentMoE, '*' grouped-query attention
    jamba        'S' Mamba-1, 'D' dense SwiGLU MLP, '*'; tied head
    qwen3_next   'G' Gated DeltaNet, 'A' gated attention (heads of
                 `head_dim`, q/k norms, rotary on `rope_dim` lanes, an
                 output gate), 'Q' GatedMoE with a softmax router and a
                 gated shared expert; zero-centred norms, pos_emb="rope"
    minicpm_sala 'L' lightning (linear) attention on the Mamba-2 kernels,
                 'B' block-sparse attention (compressed keys, top-k pages
                 past `sparse.dense_len`), 'D'; muP scalars on the stream
                 (`embed_scale`, `residual_scale`, `head_scale`),
                 pos_emb="rope"
    smallthinker 'W' window attention with rotary, every fourth '*' with
                 no positional embedding, heads of `head_dim`; 'R' ReGLU
                 experts under a softmax router that reads the mixer's
                 normed input, no shared expert; pos_emb="rope",
                 recurrent=False, a chunk's attention in `window_prefill`

The first four hold recurrent state (`recurrent=True`): `PagedEngine` serves
them, admits a long prompt in chunks over the slot's own state
(`prefill_chunk` without `prefix_cache`) and refuses `prefix_cache`,
`spec_decode` and `fork()`, which need the state at a position that is not
the sequence's end (ROADMAP M6). The fifth holds none, but its window
layers' pages are a group of their own that gives back what lies behind a
slot's window (serve/kv_pages.py CacheSpec): it is admitted in chunks the
same way, and the same three are refused, each for the pages a window gave
back (ROADMAP M3).
"""

from typing import Optional

from ddp_practice_tpu.config import PrecisionPolicy
from ddp_practice_tpu.models.convnet import ConvNet
from ddp_practice_tpu.models.resnet import ResNet, ResNet18, ResNet50
from ddp_practice_tpu.models.vit import ViT, ViTBase, ViTTiny
from ddp_practice_tpu.models.pipeline_lm import PipelinedLM
from ddp_practice_tpu.models.pipeline_vit import PipelinedViT
from ddp_practice_tpu.models.vit_moe import ViTMoE
from ddp_practice_tpu.models.lm import LMBase, LMTiny, TransformerLM
from ddp_practice_tpu.models.hybrid_lm import HybridLM
from ddp_practice_tpu.models.mla_lm import MLALM

_REGISTRY = {}
# registry names whose module exposes the tri-state `fused` field
# (bool | "auto" — models/vit.py EncoderBlock); declared at registration
# so callers (train/loop.py --fused off) never maintain a parallel list
_FUSED_CAPABLE = set()


def register(name, *, fused_capable: bool = False):
    def deco(fn):
        _REGISTRY[name] = fn
        if fused_capable:
            _FUSED_CAPABLE.add(name)
        return fn
    return deco


def accepts_fused(name: str) -> bool:
    """True when `create_model(name, fused=...)` is a valid call."""
    return name.lower() in _FUSED_CAPABLE


def create_model(
    name: str,
    *,
    num_classes: int = 10,
    policy: Optional[PrecisionPolicy] = None,
    axis_name: Optional[str] = None,
    **kwargs,
):
    """Instantiate a model by name.

    axis_name: data-parallel mesh axis for cross-replica batch statistics
    (the SyncBatchNorm equivalent); None for single-device training.
    """
    policy = policy or PrecisionPolicy.fp32()
    name = name.lower()
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](
        num_classes=num_classes, policy=policy, axis_name=axis_name, **kwargs
    )


@register("convnet")
def _convnet(*, num_classes, policy, axis_name, **kw):
    return ConvNet(
        num_classes=num_classes,
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        axis_name=axis_name,
        **kw,
    )


@register("resnet18")
def _resnet18(*, num_classes, policy, axis_name, **kw):
    return ResNet18(
        num_classes=num_classes,
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        axis_name=axis_name,
        **kw,
    )


@register("resnet50")
def _resnet50(*, num_classes, policy, axis_name, **kw):
    return ResNet50(
        num_classes=num_classes,
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        axis_name=axis_name,
        **kw,
    )


@register("vit_tiny", fused_capable=True)
def _vit_tiny(*, num_classes, policy, axis_name, **kw):
    return ViTTiny(
        num_classes=num_classes,
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("vit_base", fused_capable=True)
def _vit_base(*, num_classes, policy, axis_name, **kw):
    return ViTBase(
        num_classes=num_classes,
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("vit_tiny_moe")
def _vit_tiny_moe(*, num_classes, policy, axis_name, **kw):
    kw.setdefault("hidden_dim", 192)
    kw.setdefault("depth", 12)
    kw.setdefault("num_heads", 3)
    kw.setdefault("mlp_dim", 768)
    return ViTMoE(
        num_classes=num_classes,
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("lm_tiny", fused_capable=True)
def _lm_tiny(*, num_classes, policy, axis_name, **kw):
    # LMs have a vocab, not classes: num_classes/axis_name are accepted for
    # registry uniformity and ignored (vocab_size is an explicit kwarg)
    return LMTiny(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("lm_base", fused_capable=True)
def _lm_base(*, num_classes, policy, axis_name, **kw):
    return LMBase(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("vit_tiny_pipe")
def _vit_tiny_pipe(*, num_classes, policy, axis_name, **kw):
    kw.setdefault("hidden_dim", 192)
    kw.setdefault("depth", 12)
    kw.setdefault("num_heads", 3)
    kw.setdefault("mlp_dim", 768)
    return PipelinedViT(
        num_classes=num_classes,
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        axis_name=axis_name,
        **kw,
    )


@register("lm_moe", fused_capable=True)
def _lm_moe(*, num_classes, policy, axis_name, **kw):
    # decoder LM with routed expert MLPs every other block (GShard
    # layout); dims default to lm_tiny's — the bench sizes it up via
    # model_kwargs
    kw.setdefault("moe_every", 2)
    # top-2 capacity headroom 2.0 (the GShard convention): per-GROUP
    # routing correlation (tokens of one sequence share context, so they
    # crowd the same experts) sets a drop floor that no global balancing
    # signal can remove — measured ~10% at cf 1.25 vs <2% at 2.0 with a
    # warm router (BENCHMARKS.md round-4 MoE section)
    kw.setdefault("capacity_factor", 2.0)
    return LMTiny(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("lm_pipe")
def _lm_pipe(*, num_classes, policy, axis_name, **kw):
    # LM registry convention: num_classes/axis_name accepted and ignored
    # (vocab_size is the explicit kwarg); defaults mirror lm_tiny
    kw.setdefault("hidden_dim", 256)
    kw.setdefault("depth", 4)
    kw.setdefault("num_heads", 8)
    kw.setdefault("mlp_dim", 1024)
    return PipelinedLM(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("nemotron_h")
def _nemotron_h(*, num_classes, policy, axis_name, **kw):
    # Mamba-2 + LatentMoE + grouped-query attention by a pattern string;
    # LM registry convention (vocab_size is the explicit kwarg). The
    # defaults are test-sized: the published widths come as options
    # (perf/families/nemotron_h.py model_options)
    return HybridLM(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("jamba")
def _jamba(*, num_classes, policy, axis_name, **kw):
    # the same HybridLM in Jamba's layout: a layer is a mixer sub-layer
    # (Mamba-1 'S' or attention '*') and a dense gated MLP 'D', the head
    # tied to the embedding; test-sized defaults, the published widths come
    # as options (perf/families/jamba.py model_options)
    kw.setdefault("pattern", "SD*DSD")
    kw.setdefault("tie_embeddings", True)
    kw.setdefault("norm_eps", 1e-6)
    return HybridLM(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("qwen3_next")
def _qwen3_next(*, num_classes, policy, axis_name, **kw):
    # the same HybridLM in Qwen3-Next's layout: a layer is a mixer
    # sub-layer (Gated DeltaNet 'G', every fourth gated attention 'A') and
    # an expert sub-layer 'Q' (softmax router, gated shared expert), every
    # norm zero-centred, rotary on part of a head, untied head; test-sized
    # defaults, the published widths come as options
    # (perf/families/qwen3_next.py model_options)
    kw.setdefault("pattern", "GQGQGQAQ")
    kw.setdefault("norm_plus_one", True)
    kw.setdefault("norm_eps", 1e-6)
    kw.setdefault("pos_emb", "rope")
    kw.setdefault("hidden_dim", 64)
    kw.setdefault("head_dim", 32)
    return HybridLM(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("minicpm_sala")
def _minicpm_sala(*, num_classes, policy, axis_name, **kw):
    # the same HybridLM in MiniCPM-SALA's layout: a layer is a mixer
    # sub-layer (lightning attention 'L', every fourth block-sparse
    # attention 'B') and a dense SwiGLU 'D', muP scalars on the stream,
    # untied head; test-sized defaults, the published widths come as
    # options (perf/families/minicpm_sala.py model_options)
    from ddp_practice_tpu.ops.sparse_attention import SparseSpec

    kw.setdefault("pattern", "BDLDLDLD")
    kw.setdefault("pos_emb", "rope")
    kw.setdefault("norm_eps", 1e-6)
    kw.setdefault("hidden_dim", 64)
    kw.setdefault("head_dim", 16)
    kw.setdefault("sparse", SparseSpec(
        block=8, kernel=4, stride=2, window=8, dense_len=32, topk=4))
    if "lightning_layers" not in kw:   # numbered as they stand
        mixers = kw["pattern"][::2]
        kw["lightning_layers"] = tuple(
            i for i, m in enumerate(mixers) if m == "L")
        kw.setdefault("decay_layers", len(mixers))
    return HybridLM(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("smallthinker")
def _smallthinker(*, num_classes, policy, axis_name, **kw):
    # the same HybridLM in SmallThinker's layout: a layer is a mixer
    # sub-layer (window attention with rotary 'W', every fourth '*': no
    # positional embedding, every key) and ReGLU experts 'R' routed on the
    # mixer's normed input; no recurrent state, a prompt's chunks through
    # `window_prefill`; test-sized defaults, the published widths come as
    # options (perf/families/smallthinker.py model_options)
    kw.setdefault("pattern", "*RWRWRWR")
    kw.setdefault("pos_emb", "rope")
    kw.setdefault("recurrent", False)
    kw.setdefault("norm_eps", 1e-6)
    kw.setdefault("window", 8)
    kw.setdefault("attn_prefill", "kernel")
    kw.setdefault("hidden_dim", 48)
    kw.setdefault("head_dim", 16)
    kw.setdefault("num_experts", 8)
    kw.setdefault("experts_held", 8)
    kw.setdefault("top_k", 2)
    kw.setdefault("expert_dim", 3)
    return HybridLM(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("ling3")
def _ling3(*, num_classes, policy, axis_name, layers: int = 3,
           layer_group_size: int = 3, first_dense: int = 1, **kw):
    # the same HybridLM in Ling-3.0-flash's layout, the pattern built from
    # the config's keys: layer i of `layers` is a mixer sub-layer (latent
    # attention 'T' where (i + 1) % layer_group_size == 0, Kimi Delta
    # Attention 'K' otherwise, a gate a head on either's output) and a
    # feed-forward sub-layer (a dense SwiGLU 'D' for the first
    # `first_dense` layers, then experts 'U' under a sigmoid router that
    # keeps `topk_group` of `n_group` groups); a recurrent state AND latent
    # pages in one cache, untied head; test-sized defaults, the published
    # widths come as options (perf/families/ling3.py model_options)
    kw.setdefault("pattern", "".join(
        ("T" if (i + 1) % layer_group_size == 0 else "K")
        + ("D" if i < first_dense else "U") for i in range(layers)))
    kw.setdefault("pos_emb", "rope")
    kw.setdefault("norm_eps", 1e-6)
    kw.setdefault("hidden_dim", 64)
    kw.setdefault("experts_held", 16)
    kw.setdefault("n_group", 4)
    kw.setdefault("topk_group", 2)
    return HybridLM(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


@register("deepseek_v3")
def _deepseek_v3(*, num_classes, policy, axis_name, **kw):
    # multi-head latent attention + gated experts after leading dense
    # layers; test-sized defaults, the published widths come as options
    # (perf/families/deepseek_v3.py model_options)
    return MLALM(
        dtype=policy.compute_dtype,
        param_dtype=policy.param_dtype,
        **kw,
    )


__all__ = [
    "create_model",
    "ConvNet",
    "ResNet",
    "ResNet18",
    "ResNet50",
    "ViT",
    "ViTTiny",
    "ViTBase",
    "PipelinedLM",
    "PipelinedViT",
    "ViTMoE",
    "TransformerLM",
    "HybridLM",
    "MLALM",
    "LMTiny",
    "LMBase",
]
