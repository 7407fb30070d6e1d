"""Core neural-net ops, TPU-shaped.

Conventions: params are plain pytrees of jnp arrays; computation runs in the
array's dtype with float32 accumulation where it matters (layernorm stats,
attention softmax, loss). Matmuls use ``preferred_element_type=float32`` so
bf16 params hit the MXU with f32 accumulation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    """LayerNorm with f32 statistics regardless of input dtype."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


def rms_norm(x, gamma, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)).astype(x.dtype)


def gelu(x):
    # tanh approximation (GPT-2 uses this exact form)
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x * x * x)))


def linear(x, w, b=None):
    y = jnp.einsum("...d,df->...f", x, w, preferred_element_type=jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)


def mm(eq: str, a, b, dtype):
    """``einsum`` with float32 accumulation, cast to ``dtype``."""
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32).astype(dtype)


def gated_ffn(fp, x, dtype):
    """``W_down (silu(W_gate x) * W_up x)``; ``fp`` holds ``w_gate``,
    ``w_up`` [D, F] and ``w_down`` [F, D]."""
    g = jnp.einsum("...d,df->...f", x, fp["w_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("...d,df->...f", x, fp["w_up"],
                   preferred_element_type=jnp.float32)
    return mm("...f,fd->...d", (jax.nn.silu(g) * u).astype(dtype),
              fp["w_down"], dtype)


SPLIT_PARTS = 3


def split3(x):
    """float32 [..., n] -> bfloat16 [..., 3 n]: ``hi | mid | lo`` with ``hi +
    mid + lo == x`` to float32's last bit (8 + 8 + 8 mantissa bits). What the
    state kernels (``ops/gated_delta.py``, ``ops/ssd.py``) hand the MXU so
    that one bfloat16 pass against a 0/1 matrix is exact."""
    parts, rest = [], x.astype(jnp.float32)
    for _ in range(SPLIT_PARTS):
        # An explicit rounding: XLA may drop a float32 -> bfloat16 -> float32
        # round trip (xla_allow_excess_precision), which left ``mid`` and
        # ``lo`` zero on the chip and the kernel's q, k and gates at 8 bits.
        part = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        parts.append(part.astype(jnp.bfloat16))
        rest = rest - part
    return jnp.concatenate(parts, axis=-1)


def softmax_cross_entropy(logits, labels, ignore_index: int = -100):
    """Token-level CE with f32 logits; ignores masked positions.

    Returns (mean_loss, n_valid_tokens).
    """
    logits32 = logits.astype(jnp.float32)
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(logits32, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    n = jnp.maximum(valid.sum(), 1)
    return nll.sum() / n, n


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(base: float, dim: int, scaling=None):
    """The ``dim // 2`` rotary frequencies of a head of ``dim`` numbers,
    float32: ``base ** (-i / (dim // 2))``, or YaRN's blend of them where
    ``scaling`` is a mapping with ``factor``, ``original_max_position_
    embeddings``, ``beta_fast`` and ``beta_slow`` (a model's ``rope_scaling``
    of type yarn; Peng et al., arXiv:2309.00071). A pair that turns more than
    ``beta_fast`` times over the original context keeps its frequency, one
    that turns fewer than ``beta_slow`` times is slowed by ``factor``
    (positions interpolated), and the pairs between the two correction
    dimensions blend linearly. No position enters: the blend is the same at
    any context."""
    half = dim // 2
    freqs = jnp.exp(-jnp.log(base) * jnp.arange(half, dtype=jnp.float32) / half)
    if scaling is None:
        return freqs
    span = float(scaling["original_max_position_embeddings"])

    def correction_dim(turns):
        return dim * math.log(span / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    keep = 1.0 - ramp                  # 1: the frequency as it is, 0: / factor
    return freqs / float(scaling["factor"]) * (1.0 - keep) + freqs * keep


def rope(x, positions, *, base: float = 10000.0, freqs=None):
    """Rotary position embedding on the last dim (pairs interleaved as
    [even|odd] halves). x: [..., L, H, D]. ``freqs`` [D // 2] replaces the
    plain ``base`` frequencies (:func:`rope_frequencies`)."""
    d = x.shape[-1]
    half = d // 2
    if freqs is None:
        freqs = rope_frequencies(base, d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., L, half]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)
