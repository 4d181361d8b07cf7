"""Fused int8 weight matmul — the MXU-native wordlength point of Fig. 5(b).

Weights live in HBM as int8 with a per-output-channel f32 scale (8x less
HBM traffic than f32, 2x less than bf16).  Each kernel instance feeds the
MXU an int8 [bn x bk] weight tile cast next to the compute unit, and the
epilogue folds the per-channel dequant scale (plus optional bias and
activation) into the final K step — the dequantized weight matrix never
exists in HBM, and y never round-trips for the bias/activation.

Odd shapes are padded up to the tile grid and the output sliced back, so
callers never see the MXU's 128-alignment.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import apply_activation as _act
from repro.kernels.util import cdiv as _cdiv
from repro.kernels.util import interpret_mode


def _int8_kernel(x_ref, q_ref, scale_ref, *opt_refs, n_k_blocks: int,
                 has_bias: bool, activation: Optional[str]):
    """Grid (m, n, k): acc[bm,bn] += x[bm,bk] @ q[bn,bk].T; epilogue
    applies the per-channel scale (+ bias, activation) on the last K step.
    """
    refs = list(opt_refs)
    bias_ref = refs.pop(0) if has_bias else None
    o_ref, acc_ref = refs
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = q_ref[...].astype(jnp.float32)                   # int8 cast in VMEM
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kb == n_k_blocks - 1)
    def _done():
        y = acc_ref[...] * scale_ref[...]                # [bm,bn] * [1,bn]
        if has_bias:
            y = y + bias_ref[...]
        o_ref[...] = _act(activation, y)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "activation", "interpret"))
def int8_matmul(x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray, *,
                bias: Optional[jnp.ndarray] = None,
                activation: Optional[str] = None,
                bm: int = 8, bn: int = 128, bk: int = 512,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """act(x [B,K] @ (q [N,K] * scale [N,1|1,1]).T + bias [N]) -> [B,N] f32.

    BlockSpecs: x tiles [bm,bk] f32, weight tiles [bn,bk] int8 (1 byte/
    weight of VMEM), scale/bias replicated per n tile.  All dims are
    padded to the tile grid and the output sliced back.
    ``interpret=None`` lowers natively on a TPU only.
    """
    b, k = x.shape
    n, k2 = q.shape
    assert k2 == k, "weight K must match activation K"
    bm, bn, bk = min(bm, _cdiv(b, 8) * 8), min(bn, n), min(bk, k)
    bp, np_, kp = _cdiv(b, bm) * bm, _cdiv(n, bn) * bn, _cdiv(k, bk) * bk
    if (bp, kp) != (b, k):
        x = jnp.pad(x, ((0, bp - b), (0, kp - k)))
    if (np_, kp) != (n, k):
        q = jnp.pad(q, ((0, np_ - n), (0, kp - k)))
    scale2d = jnp.broadcast_to(scale.astype(jnp.float32).reshape(1, -1),
                               (1, n))
    scale2d = jnp.pad(scale2d, ((0, 0), (0, np_ - n)))
    grid = (bp // bm, np_ // bn, kp // bk)
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
        pl.BlockSpec((bn, bk), lambda i, j, kb: (j, kb)),
        pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)),
    ]
    args = [x, q, scale2d]
    if has_bias:
        bias2d = jnp.pad(bias.astype(jnp.float32).reshape(1, -1),
                         ((0, 0), (0, np_ - n)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)))
        args.append(bias2d)
    out = pl.pallas_call(
        functools.partial(_int8_kernel, n_k_blocks=grid[2],
                          has_bias=has_bias, activation=activation),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(*args)
    return out[:b, :n]
