"""Chunked linear-recurrence kernel (RWKV6 "Finch" WKV) — serving hot path.

The matrix-valued state S [Dk, Dv] stays RESIDENT IN VMEM for the whole
sequence while time chunks stream through — the AIDA principle (state never
leaves the memory it is processed in) applied to the recurrence:

    S_t = diag(w_t) · S_{t-1} + k_t v_tᵀ
    o_t = (S_{t-1} + diag(u) · k_t v_tᵀ)ᵀ r_t

Grid (B·H, T/C): Pallas iterates the grid sequentially per core, so the
state scratch carries across chunk steps of the same (b,h) row and is
re-initialized when the chunk index wraps to 0.  Inside a chunk the exact
sequential recurrence runs in registers/VMEM (numerically safe for
arbitrarily small decays, unlike cumprod-factorized chunk algebra — see
DESIGN.md).  Training uses the differentiable `ops.rwkv6(..., impl="scan")`
path; this kernel is the inference engine.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import interpret_mode


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *,
                  chunk: int):
    tb = pl.program_id(1)

    @pl.when(tb == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[...][0]                        # [Dk]

    def step(t, S):
        rt = r_ref[0, t, :].astype(jnp.float32)
        kt = k_ref[0, t, :].astype(jnp.float32)
        vt = v_ref[0, t, :].astype(jnp.float32)
        wt = w_ref[0, t, :].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]                   # [Dk, Dv]
        ot = ((S + u[:, None] * kv) * rt[:, None]).sum(axis=0)
        o_ref[0, t, :] = ot
        return wt[:, None] * S + kv

    s_scr[...] = jax.lax.fori_loop(0, chunk, step, s_scr[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_fwd(r, k, v, w, u, *, chunk: int = 64,
              interpret: Optional[bool] = None):
    """r,k,w [B,H,T,Dk], v [B,H,T,Dv], u [H,Dk] -> o [B,H,T,Dv] f32."""
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0
    bh = b * h
    flat = lambda x: x.reshape(bh, t, x.shape[-1])
    o = pl.pallas_call(
        functools.partial(_rwkv6_kernel, chunk=chunk),
        grid=(bh, t // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda i, tb: (i, tb, 0)),
            pl.BlockSpec((1, chunk, dk), lambda i, tb: (i, tb, 0)),
            pl.BlockSpec((1, chunk, dv), lambda i, tb: (i, tb, 0)),
            pl.BlockSpec((1, chunk, dk), lambda i, tb: (i, tb, 0)),
            pl.BlockSpec((1, dk), lambda i, tb, H=h: (i % H, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda i, tb: (i, tb, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(flat(r), flat(k), flat(v), flat(w), u)
    return o.reshape(b, h, t, dv)
