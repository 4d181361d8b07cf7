"""Blocked-ACSR sparse matvec/matmul — fused multi-block decode pipeline.

The paper's per-nnz stream (value, col_idx, row flags) is re-scheduled at
``block_encode`` time into a *row-balanced slot layout*, EIE's PE schedule
mapped onto TPU lanes: each block owns ``block_rows`` consecutive matrix
rows (one row per lane), and slot step ``s`` consumes the ``s``-th nonzero
of every row in the block simultaneously —

    values:  [nblocks, rmax, block_rows]   (slot-major; lane = matrix row)
    col_idx: [nblocks, rmax, block_rows]
    row_nnz: [nblocks, block_rows]         per-row segment lengths

``row_nnz`` IS the precomputed segment structure: under this schedule the
segment one-hot of the paper's soft reduction becomes the *static* matrix
kron(I_block_rows, 1_rmax), so the segmented sum is a plain slot-axis
reduction and nothing is rebuilt per kernel invocation.  (The previous
kernel materialized a fresh [me, block_rows] one-hot and pushed it through
the MXU on every call — nnz x block_rows MACs per block, 30-80x the work
of the dense matmul it replaced.)

Each grid step of the fused kernel IS the paper's Fig. 3 pipeline for a
*batch* of ``mb`` row blocks:

  activation broadcast -> gather x_tile[col_idx]  (K-tiled: only a [bk, B]
                          slice of x is VMEM-resident; out-of-tile entries
                          are masked and accumulated on a later K step)
  multiplication       -> values * gathered       (VPU, 128 rows in flight)
  soft reduction       -> slot-axis sum           (static segment one-hot)
  epilogue             -> + bias, activation      (fused on the last K step)

Supports matvec (x: [K]) and multi-activation matmul (x: [K, B]), plus
codebook-coded values (uint8 codes dequantized against a 16-entry VMEM
table — combine with sparsity for the full AIDA mode).

Load imbalance caveat: ``rmax`` is the max row population, so a single
dense row pads every other row's slot stream (EIE has the same
pathology).  Magnitude-pruned layers are near-balanced in practice.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import apply_activation as _act
from repro.kernels.util import cdiv as _cdiv
from repro.kernels.util import interpret_mode


# --------------------------------------------------------------- format
@dataclasses.dataclass
class BlockedACSR:
    """Row-blocked ACSR in the balanced slot schedule (TPU layout of the
    paper's Fig. 2, rescheduled for 128-lane execution).

    values:  [nblocks, rmax, block_rows] f32 (or uint8 codes if ``coded``)
    col_idx: [nblocks, rmax, block_rows] int32 (int16 when n_cols allows)
    row_nnz: [nblocks, block_rows] int32 — nonzeros per matrix row; the
             encode-time segment structure (slot >= row_nnz is padding)

    Registered as a pytree (arrays = leaves, geometry = static) so
    compressed weights can live INSIDE jitted model params.
    """
    values: jnp.ndarray
    col_idx: jnp.ndarray
    row_nnz: jnp.ndarray
    shape: Tuple[int, int]
    block_rows: int
    nnz: int
    centroids: Optional[jnp.ndarray] = None  # set when values are codes

    @property
    def nblocks(self) -> int:
        return int(self.values.shape[0])

    @property
    def rmax(self) -> int:
        """Padded slot count (max nonzeros of any row)."""
        return int(self.values.shape[1])


def _bacsr_flatten(b: "BlockedACSR"):
    return ((b.values, b.col_idx, b.row_nnz, b.centroids),
            (b.shape, b.block_rows, b.nnz))


def _bacsr_unflatten(aux, children):
    values, col_idx, row_nnz, centroids = children
    shape, block_rows, nnz = aux
    return BlockedACSR(values=values, col_idx=col_idx, row_nnz=row_nnz,
                       shape=shape, block_rows=block_rows, nnz=nnz,
                       centroids=centroids)


jax.tree_util.register_pytree_node(BlockedACSR, _bacsr_flatten,
                                   _bacsr_unflatten)


def block_encode(dense: np.ndarray, block_rows: int = 128,
                 slot_pad: int = 8,
                 value_dtype: str = "f32") -> BlockedACSR:
    """Pack a dense matrix's nonzeros into the balanced slot schedule.

    Fully vectorized (bincount + cumsum over the whole matrix — no
    per-block Python loops), so offline compression of real layer shapes
    is linear in nnz.  ``value_dtype="bf16"`` stores the nonzeros in
    bfloat16 (half the value bytes; the kernel upcasts in VMEM).
    """
    dense = np.asarray(dense)
    assert dense.ndim == 2, "BlockedACSR encodes 2-D matrices"
    n_rows, n_cols = dense.shape
    nblocks = max(1, _cdiv(n_rows, block_rows))
    rows, cols = np.nonzero(dense)              # row-major by construction
    nnz = len(rows)
    counts = np.bincount(rows, minlength=nblocks * block_rows)
    rmax = int(counts.max(initial=0))
    rmax = max(slot_pad, _cdiv(rmax, slot_pad) * slot_pad)
    # slot of each entry = its index within its row
    starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
    slot = np.arange(nnz) - starts[rows]
    blk, lane = rows // block_rows, rows % block_rows
    # compact index types — the memory footprint IS the paper's argument
    col_t = np.int16 if n_cols < 2 ** 15 else np.int32
    vals = np.zeros((nblocks, rmax, block_rows), np.float32)
    cidx = np.zeros((nblocks, rmax, block_rows), col_t)
    vals[blk, slot, lane] = dense[rows, cols]
    cidx[blk, slot, lane] = cols
    row_nnz = counts.reshape(nblocks, block_rows).astype(np.int32)
    jvals = jnp.asarray(vals)
    if value_dtype == "bf16":
        jvals = jvals.astype(jnp.bfloat16)
    elif value_dtype != "f32":
        raise ValueError(f"unknown value_dtype {value_dtype!r}")
    return BlockedACSR(values=jvals, col_idx=jnp.asarray(cidx),
                       row_nnz=jnp.asarray(row_nnz),
                       shape=(n_rows, n_cols), block_rows=block_rows,
                       nnz=int(nnz))


def block_encode_coded(dense: np.ndarray, centroids: np.ndarray,
                       block_rows: int = 128,
                       slot_pad: int = 8) -> BlockedACSR:
    """Sparse + codebook: store the nonzeros' 4-bit codes, not values."""
    b = block_encode(dense, block_rows, slot_pad)
    cents = np.asarray(centroids, np.float32)
    vals = np.asarray(b.values)
    codes = np.abs(vals[..., None] - cents[None, None, None, :]).argmin(-1)
    codes[vals == 0.0] = 0  # padding slots (masked by row_nnz in-kernel)
    return dataclasses.replace(
        b, values=jnp.asarray(codes.astype(np.uint8)),
        centroids=jnp.asarray(cents))


# --------------------------------------------------------------- kernel
def _fused_spmv_kernel(vals_ref, cols_ref, nnz_ref, x_ref, *opt_refs,
                       bk: int, n_k_blocks: int, coded: bool,
                       has_bias: bool, activation: Optional[str]):
    """One grid step = the Fig. 3 pipeline for ``mb`` row blocks over one
    K tile.  opt_refs order: [cents], [bias], out, acc, w/col scratch.

    Every gather stays inside one vreg, the only gather the TPU lowers:
    centroids are a ``[1, br]`` lane row indexed by code, and the
    activation tile ``x [B, bk]`` is read as ``bk / br`` lane chunks of
    ``[B, br]``, each gathered at the slot's in-chunk column and kept
    where the column falls in that chunk."""
    refs = list(opt_refs)
    cents_ref = refs.pop(0) if coded else None
    bias_ref = refs.pop(0) if has_bias else None
    o_ref, acc_ref, w_scr, col_scr = refs
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mb, rmax, br = vals_ref.shape
    bsz = x_ref.shape[0]
    x = x_ref[...].astype(jnp.float32)                  # [B, bk]
    chunks = [x[:, c * br:(c + 1) * br] for c in range(bk // br)]
    for m in range(mb):                                  # static unroll
        vals = vals_ref[m]                               # [rmax, br]
        if coded:
            vals = jnp.take_along_axis(
                jnp.broadcast_to(cents_ref[...], (rmax, br)),
                vals.astype(jnp.int32), axis=1)
        w_scr[...] = vals.astype(jnp.float32)
        col_scr[...] = cols_ref[m].astype(jnp.int32) - kb * bk
        nnz = nnz_ref[m]                                 # [1, br]

        def slot(s, acc, nnz=nnz):
            # slot s of every row in the block: its column (local to this
            # K tile) and value; slot >= row_nnz is padding
            local = col_scr[pl.ds(s, 1), :]              # [1, br]
            live = (s < nnz) & (local >= 0) & (local < bk)
            w = jnp.where(live, w_scr[pl.ds(s, 1), :], 0.0)
            lc = jnp.clip(local, 0, bk - 1)
            idx = jnp.broadcast_to(jax.lax.rem(lc, br), (bsz, br))
            chunk = jax.lax.div(lc, br)
            g = jnp.zeros((bsz, br), jnp.float32)
            for c, xc in enumerate(chunks):
                g = jnp.where(chunk == c,
                              jnp.take_along_axis(xc, idx, axis=1), g)
            return acc + w * g
        acc_ref[m] += jax.lax.fori_loop(
            0, rmax, slot, jnp.zeros((bsz, br), jnp.float32))

    @pl.when(kb == n_k_blocks - 1)
    def _done():
        y = acc_ref[...]                                 # [mb, B, br]
        if has_bias:
            y = y + bias_ref[...]                        # [mb, 1, br]
        o_ref[...] = _act(activation, y)


@functools.partial(jax.jit, static_argnames=(
    "mb", "bk", "activation", "interpret"))
def _spmv_call(values, col_idx, row_nnz, x2d, centroids, bias, *,
               mb: int, bk: int, activation: Optional[str],
               interpret: bool):
    nblocks, rmax, br = values.shape
    k, bsz = x2d.shape
    coded = centroids is not None
    has_bias = bias is not None
    # pad the block axis to a multiple of mb (padding blocks: row_nnz = 0)
    nsuper = _cdiv(nblocks, mb)
    pad_b = nsuper * mb - nblocks
    if pad_b:
        values = jnp.pad(values, ((0, pad_b), (0, 0), (0, 0)))
        col_idx = jnp.pad(col_idx, ((0, pad_b), (0, 0), (0, 0)))
        row_nnz = jnp.pad(row_nnz, ((0, pad_b), (0, 0)))
    # pad K to a multiple of bk (zero activations never contribute); the
    # activations ride transposed, the batch on whole sublane tiles
    n_k = _cdiv(k, bk)
    bp = _cdiv(bsz, 8) * 8
    x_t = jnp.pad(x2d.T, ((0, bp - bsz), (0, n_k * bk - k)))
    grid = (nsuper, n_k)
    in_specs = [
        pl.BlockSpec((mb, rmax, br), lambda i, kb: (i, 0, 0)),
        pl.BlockSpec((mb, rmax, br), lambda i, kb: (i, 0, 0)),
        pl.BlockSpec((mb, 1, br), lambda i, kb: (i, 0, 0)),
        pl.BlockSpec((bp, bk), lambda i, kb: (0, kb)),
    ]
    args = [values, col_idx, row_nnz.reshape(-1, 1, br), x_t]
    if coded:
        n_cents = centroids.shape[0]
        if n_cents > br:
            raise ValueError(f"{n_cents} centroids do not fit one "
                             f"{br}-lane row")
        cents2d = jnp.pad(centroids.astype(jnp.float32),
                          (0, br - n_cents)).reshape(1, br)
        in_specs.append(pl.BlockSpec((1, br), lambda i, kb: (0, 0)))
        args.append(cents2d)
    if has_bias:
        bias3d = jnp.pad(bias.astype(jnp.float32),
                         (0, (nblocks + pad_b) * br - bias.shape[0])
                         ).reshape(-1, 1, br)
        in_specs.append(pl.BlockSpec((mb, 1, br), lambda i, kb: (i, 0, 0)))
        args.append(bias3d)
    kern = functools.partial(
        _fused_spmv_kernel, bk=bk, n_k_blocks=n_k, coded=coded,
        has_bias=has_bias, activation=activation)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((mb, bp, br), lambda i, kb: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nsuper * mb, bp, br), jnp.float32),
        scratch_shapes=[pltpu.VMEM((mb, bp, br), jnp.float32),
                        pltpu.VMEM((rmax, br), jnp.float32),
                        pltpu.VMEM((rmax, br), jnp.int32)],
        interpret=interpret,
    )(*args)
    return out[:, :bsz].transpose(0, 2, 1).reshape(-1, bsz)  # [rows, B]


def default_tiles(nblocks: int, k: int) -> Tuple[int, int]:
    """Heuristic (mb, bk) when no autotuned choice is cached: fuse up to 8
    row blocks per grid step; keep x resident unless K is large."""
    mb = min(8, max(1, nblocks))
    bk = k if k <= 2048 else 512
    return mb, bk


def acsr_spmv(b: BlockedACSR, x: jnp.ndarray, *,
              bias: Optional[jnp.ndarray] = None,
              activation: Optional[str] = None,
              mb: Optional[int] = None, bk: Optional[int] = None,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sparse (optionally coded) fused pipeline: act(W @ x + bias).

    x: [K] or [K, B]; bias: [n_rows] broadcast over B.  Returns
    [n_rows] / [n_rows, B] f32.  ``mb``/``bk`` select the fused tile
    shape (see kernels.tune for the autotuner that picks them); ``bk``
    rounds up to a whole number of ``block_rows``-lane chunks.
    ``interpret=None`` lowers natively on a TPU and interprets elsewhere.
    """
    squeeze = x.ndim == 1
    x2d = x[:, None] if squeeze else x
    d_mb, d_bk = default_tiles(b.nblocks, x2d.shape[0])
    mb = d_mb if mb is None else min(mb, b.nblocks)
    bk = d_bk if bk is None else min(bk, x2d.shape[0])
    bk = _cdiv(bk, b.block_rows) * b.block_rows
    out = _spmv_call(b.values, b.col_idx, b.row_nnz, x2d, b.centroids,
                     bias, mb=mb, bk=bk, activation=activation,
                     interpret=interpret_mode(interpret))
    out = out[: b.shape[0]]
    return out[:, 0] if squeeze else out
