"""Plain float32 `jax.numpy` pieces the references share.

No kernels, no cache, no batching tricks, nothing imported from the program.
Parameters come as the nested dict the benchmark's `lib/weights.py` fills
(flax's names: `kernel`, `bias`, `scale`, `embedding`). Every matmul goes
through `mm`, so one argument turns the whole forward into the control: the
same mathematics in the next precision down.

    quant=None    float32 operands, precision "highest" (the reference)
    quant="fp8"   both operands of every matmul rounded to a 4-bit
                  exponent and 3-bit mantissa (e4m3) with a per-tensor scale
                  (the control for a configuration that states bfloat16)
    quant="bf16"  operands rounded to bfloat16 (what the program itself
                  computes in; used to read how far bf16 alone moves a
                  number)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, quant):
    """x rounded to the lower precision's grid; the derivative is the
    identity (rounding is piecewise constant, and a cast's own transpose
    would flush small cotangents to zero)."""
    if quant is None:
        return x
    # lax.reduce_precision, not a pair of casts: XLA may drop a cast down
    # and up again as "excess precision", and the control would then be the
    # reference itself (seen on the chip, PR 23: the bf16 reading came out
    # 2e-7 from float32)
    if quant == "bf16":
        y = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    elif quant == "fp8":
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        scale = 224.0 / amax  # inside the e4m3 grid's finite range
        y = jax.lax.reduce_precision(x * scale, exponent_bits=4,
                                     mantissa_bits=3) / scale
    else:
        raise ValueError(f"quant {quant!r}")
    return x + jax.lax.stop_gradient(y - x)


def mm(spec: str, a, b, quant=None):
    """einsum in float32 at the highest precision. As the control, both
    operands are rounded first, and so are the operands of the two backward
    matmuls (the cotangent included, with a scale of its own, so that small
    gradients do not underflow): what a low-precision path would compute."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant is None:
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    def dot(x, y):
        return jnp.einsum(spec, _round(x, quant), _round(y, quant),
                          precision=HIGHEST)

    @jax.custom_vjp
    def low(x, y):
        return dot(x, y)

    def fwd(x, y):
        return dot(x, y), (x, y)

    def bwd(res, g):
        return jax.vjp(dot, *res)[1](_round(g, quant))

    low.defvjp(fwd, bwd)
    return low(a, b)


def layer_norm(x, p, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y * p["scale"].astype(jnp.float32) \
        + p["bias"].astype(jnp.float32)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def rope(x, positions, theta: float = 10000.0):
    """Rotate (b, s, h, d) by position; pairs are (i, i + d/2)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, *, causal: bool, use_rope: bool, quant=None):
    """Multi-head self-attention; p["qkv"]["kernel"] is (d, 3, h, hd) and
    p["out"]["kernel"] is (h, hd, d)."""
    qkv = mm("bsd,dthe->bsthe", x, p["qkv"]["kernel"], quant) \
        + p["qkv"]["bias"].astype(jnp.float32)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s, hd = x.shape[1], q.shape[-1]
    if use_rope:
        pos = jnp.arange(s)
        q, k = rope(q, pos), rope(k, pos)
    scores = mm("bqhe,bkhe->bhqk", q, k, quant) / jnp.sqrt(float(hd))
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = mm("bhqk,bkhe->bqhe", w, v, quant)
    return mm("bqhe,hed->bqd", out, p["out"]["kernel"], quant) \
        + p["out"]["bias"].astype(jnp.float32)


def mlp(x, p, quant=None):
    h = mm("bsd,dm->bsm", x, p["fc_in"]["kernel"], quant) \
        + p["fc_in"]["bias"].astype(jnp.float32)
    return mm("bsm,md->bsd", gelu_tanh(h), p["fc_out"]["kernel"], quant) \
        + p["fc_out"]["bias"].astype(jnp.float32)


def block(x, p, *, causal: bool, use_rope: bool, eps: float, quant=None):
    """Pre-LN transformer block."""
    x = x + attention(layer_norm(x, p["ln1"], eps), p["attn"],
                      causal=causal, use_rope=use_rope, quant=quant)
    return x + mlp(layer_norm(x, p["ln2"], eps), p["mlp"], quant)


def softmax_xent(logits, labels):
    """Mean negative log-likelihood of integer labels."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)
