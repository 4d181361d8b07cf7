"""Fused codebook-dequant matmul — AIDA's perfect induction on the MXU.

Weights live in HBM as packed 4-bit codebook indices (2 codes/byte, 4× less
HBM traffic than bf16, 8× less than f32).  Each kernel instance expands its
[bn × bk] code tile against the 16-entry centroid table *inside VMEM* and
feeds the MXU — the dense weight matrix never exists in HBM.  This is the
TPU realization of "the bulk of data never leaves the confines of the memory
arrays": compressed weights are only expanded next to the compute unit,
multiplying effective memory bandwidth (decode is memory-bound, so the
roofline's memory term drops ≈4×).

Two modes:
* ``lut_matmul``         — codes × real activations (weights-only coding):
  VMEM dequant-gather then MXU matmul.
* ``lut_product_matmul`` — codes × coded activations through an arbitrary
  16×16 LUT (bit-parallel perfect induction verbatim).  Supports
  non-multiplicative induction tables; one dequant + MXU pass per
  activation code, sized for decode.

The two nibbles of a packed byte are contracted as separate planes against
the even and odd activation columns, and every table lookup is a lane
gather inside one vreg — the forms Mosaic lowers for the TPU.
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


LANES = 128


def _dequant(codes: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """``table[0, codes]`` for int32 codes [R, W] (W a multiple of 128)
    against a ``[1, 128]`` table row: one in-vreg lane gather per 128-lane
    chunk, the only gather the TPU lowers."""
    rows, width = codes.shape
    tb = jnp.broadcast_to(table, (rows, LANES))
    return jnp.concatenate(
        [jnp.take_along_axis(tb, codes[:, c:c + LANES], axis=1)
         for c in range(0, width, LANES)], axis=1)


def _split_k(x: jnp.ndarray, k2p: int, fill=0) -> tuple:
    """x [B, K] -> (even, odd) columns [B, k2p]: byte j of a packed code
    row holds columns 2j (low nibble) and 2j+1 (high nibble), so the
    kernels contract the two nibble planes against these halves instead
    of interleaving the codes in VMEM."""
    ev, od = x[:, 0::2], x[:, 1::2]
    pad = lambda a: jnp.pad(a, ((0, 0), (0, k2p - a.shape[1])),
                            constant_values=fill)
    return pad(ev), pad(od)


def _tiles(b: int, n: int, k2: int, bm: int, bn: int, bk: int):
    """Clamp (bm, bn, bk/2) to the problem and round the packed K tile to
    whole 128-lane chunks; returns (bm, bn, bk2, padded b, n, k2)."""
    bm, bn = min(bm, _cdiv(b, 8) * 8), min(bn, n)
    bk2 = _cdiv(min(max(bk // 2, 1), k2), LANES) * LANES
    return (bm, bn, bk2, _cdiv(b, bm) * bm, _cdiv(n, bn) * bn,
            _cdiv(k2, bk2) * bk2)


def _table_row(values: jnp.ndarray) -> jnp.ndarray:
    """A <=128-entry table as one zero-padded [1, 128] f32 lane row."""
    v = values.reshape(-1).astype(jnp.float32)
    return jnp.pad(v, (0, LANES - v.shape[0])).reshape(1, LANES)


# ------------------------------------------------------- weights-coded
def _lut_matmul_kernel(xe_ref, xo_ref, codes_ref, cents_ref, *opt_refs,
                       n_k_blocks: int, has_bias: bool,
                       activation: Optional[str]):
    """Grid (m, n, k): acc[bm,bn] += x_even @ dequant(lo).T
    + x_odd @ dequant(hi).T over one packed [bn, bk/2] code tile."""
    refs = list(opt_refs)
    bias_ref = refs.pop(0) if has_bias else None
    o_ref, acc_ref = refs
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = codes_ref[...].astype(jnp.int32)                 # [bn, bk/2]
    cents = cents_ref[...]
    dims = (((1,), (1,)), ((), ()))
    for x_ref, plane in ((xe_ref, codes & 0xF), (xo_ref, codes >> 4)):
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), _dequant(plane, cents), dims,
            preferred_element_type=jnp.float32)

    @pl.when(kb == n_k_blocks - 1)
    def _done():
        y = acc_ref[...]
        if has_bias:
            y = y + bias_ref[...]
        o_ref[...] = _act(activation, y)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "activation", "interpret"))
def lut_matmul(x: jnp.ndarray, codes_packed: jnp.ndarray,
               centroids: jnp.ndarray, *,
               bias: Optional[jnp.ndarray] = None,
               activation: Optional[str] = None,
               bm: int = 128, bn: int = 128,
               bk: int = 512, interpret: Optional[bool] = None
               ) -> jnp.ndarray:
    """act(x [B,K] @ dequant(codes [N,K/2], centroids).T + bias) -> [B,N].

    BlockSpecs: even/odd x tiles [bm,bk/2], code tiles [bn,bk/2] (uint8 —
    ½ byte/weight of VMEM), centroid row replicated.  MXU dims are
    128-aligned.  Odd b/n/k are padded up to the tile grid and the output
    sliced back (padded K columns meet zero activations, so they are
    inert).  ``interpret=None`` lowers natively on a TPU only.
    """
    b, k = x.shape
    n, k2 = codes_packed.shape
    assert k2 * 2 == k, "packed codes must cover K"
    bm, bn, bk2, bp, np_, k2p = _tiles(b, n, k2, bm, bn, bk)
    xe, xo = _split_k(jnp.pad(x, ((0, bp - b), (0, 0))), k2p)
    codes_packed = jnp.pad(codes_packed, ((0, np_ - n), (0, k2p - k2)))
    grid = (bp // bm, np_ // bn, k2p // bk2)
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bm, bk2), lambda i, j, kb: (i, kb)),
        pl.BlockSpec((bm, bk2), lambda i, j, kb: (i, kb)),
        pl.BlockSpec((bn, bk2), lambda i, j, kb: (j, kb)),
        pl.BlockSpec((1, LANES), lambda i, j, kb: (0, 0)),
    ]
    args = [xe, xo, codes_packed, _table_row(centroids)]
    if has_bias:
        bias2d = jnp.pad(bias.astype(jnp.float32).reshape(1, -1),
                         ((0, 0), (0, np_ - n)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)))
        args.append(bias2d)
    out = pl.pallas_call(
        functools.partial(_lut_matmul_kernel, n_k_blocks=grid[2],
                          has_bias=has_bias, activation=activation),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(*args)
    return out[:b, :n]


# ---------------------------------------------------------- fully-coded
def _lut_product_kernel(xe_ref, xo_ref, codes_ref, lut_ref, o_ref, acc_ref,
                        *, n_k_blocks: int, n_codes: int):
    """Grid (m, n, k): every multiply is LUT[w_code, x_code], summed as
    sum_a onehot(x == a) @ LUT[w, a].T — one table column per activation
    code, dequantized like the weights-coded kernel and fed to the MXU."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = codes_ref[...].astype(jnp.int32)                 # [bn, bk/2]
    planes = ((xe_ref[...], codes & 0xF), (xo_ref[...], codes >> 4))
    dims = (((1,), (1,)), ((), ()))
    for a in range(n_codes):
        col = lut_ref[pl.ds(a, 1), :]                        # LUT[:, a]
        for xc, plane in planes:
            acc_ref[...] += jax.lax.dot_general(
                (xc == a).astype(jnp.float32), _dequant(plane, col), dims,
                preferred_element_type=jnp.float32)

    @pl.when(kb == n_k_blocks - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def lut_product_matmul(x_codes: jnp.ndarray, codes_packed: jnp.ndarray,
                       lut: jnp.ndarray, *, bm: int = 8, bn: int = 128,
                       bk: int = 256, interpret: Optional[bool] = None
                       ) -> jnp.ndarray:
    """Fully-coded matmul via an arbitrary product LUT (perfect induction).

    x_codes [B,K] uint8, codes_packed [N,K/2] uint8, lut [nc,nc] f32 ->
    [B,N] f32.  Padded K columns carry the activation code -1, which
    matches no table column, so they contribute nothing.
    """
    b, k = x_codes.shape
    n, k2 = codes_packed.shape
    assert k2 * 2 == k
    nc = lut.shape[0]
    bm, bn, bk2, bp, np_, k2p = _tiles(b, n, k2, bm, bn, bk)
    xe, xo = _split_k(jnp.pad(x_codes.astype(jnp.int32),
                              ((0, bp - b), (0, 0)), constant_values=-1),
                      k2p, fill=-1)
    codes_packed = jnp.pad(codes_packed, ((0, np_ - n), (0, k2p - k2)))
    grid = (bp // bm, np_ // bn, k2p // bk2)
    # row a = LUT[:, a], indexed by the weight code
    lut_cols = jnp.pad(lut.T.astype(jnp.float32),
                       ((0, 0), (0, LANES - lut.shape[0])))
    out = pl.pallas_call(
        functools.partial(_lut_product_kernel, n_k_blocks=grid[2],
                          n_codes=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk2), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((bm, bk2), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((bn, bk2), lambda i, j, kb: (j, kb)),
            pl.BlockSpec((nc, LANES), lambda i, j, kb: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(xe, xo, codes_packed, lut_cols)
    return out[:b, :n]
