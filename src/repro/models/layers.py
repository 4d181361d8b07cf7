"""Foundational layers: norms, rotary embeddings, MLPs, initializers.

Pure-functional: params are nested dicts of jnp arrays; every `*_init`
returns params and the matching `*_apply` consumes them.  Compute follows a
mixed-precision policy: params f32, matmul compute bf16, norms/softmax f32.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import dispatch as _dispatch
from repro.api import env as _env

COMPUTE_DTYPE = jnp.bfloat16


def _matmul_out_dtype():
    """§Perf lever: bf16 matmul outputs mean TP partial sums cross the ICI
    in bf16 (half the all-reduce wire bytes).  MXU accumulation is f32
    internally either way; only the psum payload narrows.  Enabled with
    REPRO_BF16_PSUM=1 (measured in the hillclimb; see EXPERIMENTS §Perf)."""
    return COMPUTE_DTYPE if _env.BF16_PSUM else jnp.float32


def dense_init(key, d_in: int, d_out: int, scale: Optional[float] = None):
    scale = (d_in ** -0.5) if scale is None else scale
    return jax.random.normal(key, (d_in, d_out), jnp.float32) * scale


@jax.named_scope("proj")
def dense(x, w, bias=None, activation=None, plan=None):
    """act(x @ w + bias).  ``w`` may be a raw [d_in, d_out] matrix OR any
    compressed leaf registered with repro.api.dispatch (e.g. a
    core.sparse_fc.CompressedFC, the AIDA serving mode) — compression is
    transparent to every projection in the model zoo.

    For compressed leaves, bias and activation ride into the kernel
    epilogue (one fused pass, no extra HBM round-trip); the raw-matmul
    path keeps the historical op order bit-for-bit.

    ``plan`` (a shard.ShardingPlan) routes compressed leaves through the
    shard-local tensor-parallel apply — each mesh shard runs its band of
    the compressed matrix through the same kernels (raw matrices are
    GSPMD-partitioned by the plan's param shardings instead, so they
    ignore ``plan`` here)."""
    apply = _dispatch.applier_for(w)
    if apply is not None:
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        y = None
        if plan is not None:
            from repro.core.sparse_fc import CompressedFC
            from repro.shard import apply_fc_sharded
            if isinstance(w, CompressedFC):
                y = apply_fc_sharded(plan, w, x2, bias=bias,
                                     activation=activation)
        if y is None:
            y = apply(w, x2, bias=bias, activation=activation)
        return y.reshape(*lead, y.shape[-1]).astype(COMPUTE_DTYPE)
    y = jnp.matmul(x.astype(COMPUTE_DTYPE), w.astype(COMPUTE_DTYPE),
                   preferred_element_type=_matmul_out_dtype())
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    y = y.astype(COMPUTE_DTYPE)
    if activation is not None:
        y = _act(activation, y.astype(jnp.float32)).astype(COMPUTE_DTYPE)
    return y


def rms_norm_init(d: int):
    return {"scale": jnp.zeros((d,), jnp.float32)}  # gemma-style (1+scale)


def rms_norm(x, params, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * (1.0 + params["scale"])
    return y.astype(COMPUTE_DTYPE)


def layer_norm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layer_norm(x, params, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(COMPUTE_DTYPE)


# ------------------------------------------------------------------ rotary
def rope(x: jnp.ndarray, positions: jnp.ndarray,
         theta: float = 10000.0, table=None) -> jnp.ndarray:
    """Rotary embedding. x [B, H, T, D], positions [B, T] (absolute).
    ``table`` = (inverse frequencies [D/2], scale) of this layer, from
    :func:`rope_tables`; None keeps plain RoPE at ``theta``."""
    d = x.shape[-1]
    half = d // 2
    if table is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = table[0]
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,T,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if table is not None:
        cos, sin = cos * table[1], sin * table[1]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_correction_range(dim: int, theta: float, yarn) -> Tuple[int, int]:
    """The dims between which YaRN ramps from extrapolation to
    interpolation (HF ``find_correction_range``, truncated)."""
    def corr(rot):
        return dim * math.log(yarn.original_max_position
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(corr(yarn.beta_fast)), 0),
            min(math.ceil(corr(yarn.beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies [dim/2] (HF ``_compute_yarn_parameters``):
    interpolation ``1/(factor·θ^(2i/dim))`` and extrapolation
    ``1/θ^(2i/dim)`` blended by a linear ramp over the correction
    range."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extra, inter = 1.0 / pos, 1.0 / (yarn.factor * pos)
    low, high = yarn_correction_range(dim, theta, yarn)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp                         # extrapolation's share
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def rope_tables(cfg):
    """Per-layer RoPE data for the layer scan: (inverse frequencies
    [L, D/2], cos/sin scale [L]) where layer kinds rotate differently
    (YaRN on full-attention layers, plain RoPE on windowed ones), else
    None: every layer rotates at ``rope_theta`` alone."""
    if cfg.yarn is None or cfg.rope_theta is None:
        return None
    dh = cfg.head_dim
    plain = cfg.rope_theta ** (-np.arange(0, dh // 2, dtype=np.float32)
                               / (dh // 2))
    yarn = yarn_inv_freq(dh, cfg.rope_theta, cfg.yarn)
    full = [w < 0 for w in cfg.layer_windows()]
    return (jnp.asarray(np.stack([yarn if f else plain for f in full]),
                        jnp.float32),
            jnp.asarray([cfg.yarn.attention_factor if f else 1.0
                         for f in full], jnp.float32))


# -------------------------------------------------------------------- MLPs
def mlp_init(key, d: int, f: int, gated: bool = True, act: str = "silu"):
    ks = jax.random.split(key, 3)
    p = {"up": dense_init(ks[0], d, f), "down": dense_init(ks[1], f, d)}
    if gated:
        p["gate"] = dense_init(ks[2], d, f)
    return p


def _act(name: str, x):
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "relu":
        return jax.nn.relu(x)
    raise ValueError(name)


@jax.named_scope("proj")
def mlp(x, p, act: str = "silu", plan=None):
    if "gate" in p:
        # activation fuses into the gate projection's kernel epilogue
        up = dense(x, p["gate"], activation=act, plan=plan) \
            * dense(x, p["up"], plan=plan)
    else:
        up = dense(x, p["up"], activation=act, plan=plan)
    return dense(up, p["down"], plan=plan)


# --------------------------------------------------------------- embedding
def embed_init(key, vocab: int, d: int):
    return {"table": jax.random.normal(key, (vocab, d), jnp.float32)
            * (d ** -0.5)}


def embed(tokens, p):
    return jnp.take(p["table"], tokens, axis=0).astype(COMPUTE_DTYPE)


def unembed(x, p):
    """Tied or untied head: logits = x @ table.T (f32 out, vocab-sharded)."""
    return jnp.matmul(x.astype(COMPUTE_DTYPE),
                      p["table"].T.astype(COMPUTE_DTYPE),
                      preferred_element_type=jnp.float32)


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)
