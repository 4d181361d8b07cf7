"""Public jit'd kernel API — dispatch between Pallas kernels and jnp refs.

Pallas kernels lower natively when the jax backend is a TPU and run in
interpret mode on any other backend (:func:`pallas_interpret`); a caller
that needs one or the other passes ``interpret=`` explicitly, as the CPU
tests (``True``) and the TPU compile tests (``False``) do.  Training
paths that need autodiff either use a custom_vjp pairing the fwd/bwd
kernels (attention) or a differentiable lax.scan formulation
(recurrences).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import int8_matmul as _i8
from repro.kernels import linear_scan as _ls
from repro.kernels import lut_matmul as _lm
from repro.kernels import acsr_spmv as _sp
from repro.kernels import tune as _tune
from repro.kernels.util import pallas_interpret


# ---------------------------------------------------------------- attention
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, softcap, scale, bq, bk, interp):
    o, _ = _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   softcap=softcap, scale=scale, bq=bq,
                                   bk=bk, interpret=interp)
    return o.astype(q.dtype)


def _flash_fwd(q, k, v, causal, window, softcap, scale, bq, bk, interp):
    o, lse = _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                     softcap=softcap, scale=scale, bq=bq,
                                     bk=bk, interpret=interp)
    return o.astype(q.dtype), (q, k, v, o, lse)


def _flash_bwd(causal, window, softcap, scale, bq, bk, interp, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _fa.flash_attention_bwd(
        q, k, v, o, lse, do.astype(jnp.float32), causal=causal,
        window=window, softcap=softcap, scale=scale, bq=bq, bk=bk,
        interpret=interp)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              softcap: Optional[float] = None, scale: Optional[float] = None,
              impl: str = "flash", bq: int = 128, bk: int = 128):
    """Self-attention [B,H,T,D]×[B,Hkv,T,D] -> [B,H,T,D] (training/prefill).

    impl="flash": Pallas fwd/bwd kernels via custom_vjp.
    impl="ref":   pure-jnp oracle (XLA-fused; also the dry-run default, so
                  compiled HLO stays kernel-free and cost-analyzable).
    """
    if impl == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale).astype(q.dtype)
    t = q.shape[2]
    bq_, bk_ = min(bq, t), min(bk, t)
    return _flash(q, k, v, causal, window, softcap, scale, bq_, bk_,
                  pallas_interpret())


# ------------------------------------------------------------- recurrences
def rwkv6(r, k, v, w, u, *, impl: str = "scan", chunk: int = 64):
    """RWKV6 WKV. impl="scan" (differentiable, training/dry-run) or
    impl="kernel" (Pallas, serving)."""
    if impl == "kernel":
        return _ls.rwkv6_fwd(r, k, v, w, u, chunk=chunk,
                             interpret=pallas_interpret())
    return _ref.rwkv6_ref(r, k, v, w, u)


def rwkv6_decode_step(S, r, k, v, w, u):
    """Single-token WKV update. S [B,H,Dk,Dv]; r,k,w [B,H,Dk]; v [B,H,Dv]."""
    kv = k[..., :, None] * v[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", S + u[None, :, :, None] * kv, r)
    S = w[..., :, None] * S + kv
    return S, o


def mamba(x, dt, A, B, C):
    """Selective SSM (differentiable lax.scan path)."""
    return _ref.mamba_ref(x, dt, A, B, C)


def mamba_decode_step(h, x, dt, A, B, C):
    """h [B,D,N]; x,dt [B,D]; B,C [B,N] -> (h', y [B,D])."""
    decay = jnp.exp(dt[..., None] * A[None])              # [B,D,N]
    h = decay * h + (dt * x)[..., None] * B[:, None, :]
    return h, jnp.einsum("bdn,bn->bd", h, C)


# --------------------------------------------------------------- quantized
def bias_act_epilogue(y, bias=None, activation=None):
    """The fused kernels' epilogue, replayed in XLA for the ref paths."""
    from repro.kernels.util import apply_activation
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return apply_activation(activation, y)


def lut_matmul(x, codes_packed, centroids, bias=None, activation=None, **kw):
    """Codebook4 FC: dispatches to the Pallas LUT kernel or the XLA ref per
    the autotuned winner for this (shape, batch, backend)."""
    interp = kw.setdefault("interpret", pallas_interpret())
    choice = _tune.get(_tune.lut_key(codes_packed.shape[0],
                                     codes_packed.shape[1] * 2,
                                     x.shape[0], interp))
    if choice is not None and choice.impl == "xla":
        return bias_act_epilogue(
            _ref.lut_matmul_ref(x, codes_packed, centroids),
            bias, activation)
    if choice is not None:
        for t in ("bm", "bn", "bk"):
            if choice.tile(t):
                kw.setdefault(t, choice.tile(t))
    return _lm.lut_matmul(x, codes_packed, centroids, bias=bias,
                          activation=activation, **kw)


def lut_product_matmul(x_codes, codes_packed, lut, **kw):
    kw.setdefault("interpret", pallas_interpret())
    return _lm.lut_product_matmul(x_codes, codes_packed, lut, **kw)


def int8_matmul(x, qt, bias=None, activation=None, **kw):
    """Int8 FC: Pallas kernel with the per-channel dequant folded into the
    epilogue, or the XLA reference when the tuner measured it faster."""
    interp = kw.setdefault("interpret", pallas_interpret())
    choice = _tune.get(_tune.int8_key(qt.q.shape[0], qt.q.shape[1],
                                      x.shape[0], interp))
    if choice is not None and choice.impl == "xla":
        from repro.core import quant as _q
        return bias_act_epilogue(_q.int8_matmul_ref(x, qt), bias,
                                 activation)
    if choice is not None:
        for t in ("bm", "bn", "bk"):
            if choice.tile(t):
                kw.setdefault(t, choice.tile(t))
    return _i8.int8_matmul(x, qt.q, qt.scale, bias=bias,
                           activation=activation, **kw)


def acsr_spmv(blocked, x, bias=None, activation=None, **kw):
    """ACSR / AIDA fused pipeline; (mb, bk) come from the autotuner cache
    when a winner was recorded for this geometry."""
    interp = kw.setdefault("interpret", pallas_interpret())
    choice = _tune.get(_tune.acsr_key(
        blocked.nblocks, blocked.rmax, blocked.block_rows, x.shape[0],
        x.shape[1] if x.ndim == 2 else 1,
        blocked.centroids is not None, interp))
    if choice is not None:
        for t in ("mb", "bk"):
            if choice.tile(t):
                kw.setdefault(t, choice.tile(t))
    return _sp.acsr_spmv(blocked, x, bias=bias, activation=activation, **kw)
