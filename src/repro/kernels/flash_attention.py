"""Blockwise online-softmax attention (flash) — fwd + bwd Pallas kernels.

Prefill/training hot path.  Features needed by the assigned archs:
  * GQA (kv heads < q heads) via BlockSpec index folding — no k/v repeat,
  * causal masking, sliding-window (SWA: danube/mixtral/hymba, gemma2 local),
  * logit softcapping (gemma2), custom scale (gemma2 query_pre_attn_scalar).

Grid layout (canonical Pallas revisiting pattern): (B, H, nq, nk) with the
kv index innermost; running (m, l, acc) live in VMEM scratch and the output
block is finalized on the last kv step.  The backward pass is two kernels
(dq over kv blocks; dk/dv over group×query blocks) using the saved LSE plus
delta = rowsum(dO∘O), the standard recompute formulation (no O(T²) residual).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import interpret_mode

NEG_INF = -1e30


def _mask(iq, jk, bq, bk, tq, causal, window):
    qi = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    ki = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = jnp.ones((bq, bk), jnp.bool_)
    del tq
    if causal:
        m &= ki <= qi
    if window is not None:
        m &= ki > qi - window
    return m


def _scores(q, k, scale, softcap):
    s = jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    return s


# ------------------------------------------------------------------ fwd
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                scale, causal, window, softcap, nk, bq, bk, tq):
    iq, jk = pl.program_id(2), pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = _scores(q, k, scale, softcap)
    s = jnp.where(_mask(iq, jk, bq, bk, tq, causal, window), s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(jk == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l)[None, None]
        lse_ref[...] = (m_scr[...] + jnp.log(l))[None, None, :, 0][..., None]


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                              "bq", "bk", "interpret"))
def flash_attention_fwd(q, k, v, *, causal=True, window=None, softcap=None,
                        scale=None, bq=128, bk=128, interpret=None):
    """q [B,H,T,D], k/v [B,Hkv,T,D] -> (o [B,H,T,D] f32, lse [B,H,T,1])."""
    b, h, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    assert tq == tk, "self-attention kernel (decode uses the JAX path)"
    group = h // hkv
    scale = (d ** -0.5) if scale is None else scale
    bq, bk = min(bq, tq), min(bk, tk)
    assert tq % bq == 0 and tk % bk == 0
    nq, nk = tq // bq, tk // bk
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             window=window, softcap=softcap, nk=nk,
                             bq=bq, bk=bk, tq=tq)
    o, lse = pl.pallas_call(
        kern,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, iq, jk, g=group: (bi, hi // g, jk, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, iq, jk, g=group: (bi, hi // g, jk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
    return o, lse


# ------------------------------------------------------------------ bwd
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, acc_scr,
               *, scale, causal, window, softcap, nk, bq, bk, tq):
    iq, jk = pl.program_id(2), pl.program_id(3)

    @pl.when(jk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = dl_ref[0, 0]
    s_pre = jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    s = softcap * jnp.tanh(s_pre / softcap) if softcap is not None else s_pre
    msk = _mask(iq, jk, bq, bk, tq, causal, window)
    p = jnp.exp(jnp.where(msk, s, NEG_INF) - lse)
    dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if softcap is not None:
        ds = ds * (1.0 - (s / softcap) ** 2)  # d softcap / d s_pre
    acc_scr[...] += jax.lax.dot_general(
        ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    @pl.when(jk == nk - 1)
    def _done():
        dq_ref[...] = acc_scr[...][None, None]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, causal, window, softcap, group, nq, bq, bk, tq):
    jk, g, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((g == 0) & (iq == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = dl_ref[0, 0]
    s_pre = jax.lax.dot_general(q.astype(jnp.float32), k.astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    s = softcap * jnp.tanh(s_pre / softcap) if softcap is not None else s_pre
    msk = _mask(iq, jk, bq, bk, tq, causal, window)
    p = jnp.exp(jnp.where(msk, s, NEG_INF) - lse)          # [bq, bk]
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [bk, d]
    dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if softcap is not None:
        ds = ds * (1.0 - (s / softcap) ** 2)
    dk_scr[...] += jax.lax.dot_general(
        ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [bk, d]

    @pl.when((g == group - 1) & (iq == nq - 1))
    def _done():
        dk_ref[...] = dk_scr[...][None, None]
        dv_ref[...] = dv_scr[...][None, None]


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                              "bq", "bk", "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=None,
                        softcap=None, scale=None, bq=128, bk=128,
                        interpret=None):
    b, h, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    group = h // hkv
    scale = (d ** -0.5) if scale is None else scale
    bq, bk = min(bq, tq), min(bk, tk)
    nq, nk = tq // bq, tk // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                # [B,H,T,1]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, nk=nk,
                          bq=bq, bk=bk, tq=tq),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, iq, jk, g=group: (bi, hi // g, jk, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, iq, jk, g=group: (bi, hi // g, jk, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, iq, jk: (bi, hi, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, tq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, group=group,
                          nq=nq, bq=bq, bk=bk, tq=tq),
        grid=(b, hkv, nk, group, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hk, jk, g, iq, G=group: (bi, hk * G + g, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hk, jk, g, iq: (bi, hk, jk, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hk, jk, g, iq: (bi, hk, jk, 0)),
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hk, jk, g, iq, G=group: (bi, hk * G + g, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda bi, hk, jk, g, iq, G=group: (bi, hk * G + g, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda bi, hk, jk, g, iq, G=group: (bi, hk * G + g, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda bi, hk, jk, g, iq: (bi, hk, jk, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hk, jk, g, iq: (bi, hk, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, tk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, tk, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
