"""Shard-local compressed FC: the paper's multi-IC partitioning, executed.

`apply_fc_sharded` runs one compressed projection tensor-parallel over
the plan's model axis via `shard_map`: every shard holds a band of the
compressed matrix (a contiguous run of ACSR row blocks, or of
int8/codebook output channels) and runs the *existing* kernel —
Pallas fused SpMV, int8, LUT — on its local band only.  Combine policy:

* ``"gather"`` (default, every mode): row partitioning.  Each output
  element is produced entirely on one shard (identical arithmetic to
  the single-device kernel, so results are bit-identical), and the
  shard outputs concatenate along the feature axis — the all-gather is
  materialized lazily by GSPMD only where a consumer needs the full
  vector.
* ``"psum"`` (int8 only): input partitioning.  Shards hold a band of
  *columns*, contract against their slice of the activation, and
  all-reduce partial sums; the per-channel dequant scale + bias/act
  epilogue runs once on the reduced result.  ACSR modes cannot split
  columns (col_idx addresses the full input vector), which is why
  gather is the default policy everywhere.

Leaves whose partition axis does not divide the tp degree fall back to
the plain (replicated) apply — `partition.pad_params_for_plan` exists
so that fallback never triggers for plan-prepared params.

`paged_attention_sharded` / `paged_attention_chunk_sharded` do the same
for the paged-attention kernels: the head-sharded KV pool (plan
state_specs put Hkv over the model axis) runs the *existing* decode or
chunk kernel shard-local — Pallas scalar-prefetch included — instead of
forcing the XLA gather fallback.  Heads are fully independent in paged
attention (GQA groups ride with their kv head), so with the (impl, pb,
qt) choice resolved from the tune cache at the *global* geometry before
entering shard_map, the mesh output is bit-identical to single-device.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import sparse_fc as sfc
from repro.kernels import ops
from repro.shard import partition


def _local_layer(leaf: sfc.CompressedFC) -> sfc.CompressedFC:
    """Rebuild a CompressedFC whose static ``shape`` matches the local
    array shards shard_map handed us (the pytree aux still carries the
    global shape)."""
    n_in = leaf.shape[1]
    if leaf.mode in ("acsr", "aida"):
        b = leaf.blocked
        rows = b.values.shape[0] * b.block_rows
        blocked = dataclasses.replace(b, shape=(rows, n_in))
        return dataclasses.replace(leaf, blocked=blocked,
                                   shape=(rows, n_in))
    rows = partition.row_axis_len(leaf)
    return dataclasses.replace(leaf, shape=(rows, n_in))


def _row_specs(leaf: sfc.CompressedFC, tp_axis: str) -> sfc.CompressedFC:
    """shard_map in_specs for a single-layer leaf, row-partitioned."""
    from repro.core import quant as q
    from repro.kernels import acsr_spmv as sp
    if leaf.mode in ("acsr", "aida"):
        b = leaf.blocked
        blocked = sp.BlockedACSR(
            values=P(tp_axis, None, None), col_idx=P(tp_axis, None, None),
            row_nnz=P(tp_axis, None), shape=b.shape,
            block_rows=b.block_rows, nnz=b.nnz,
            centroids=None if b.centroids is None else P())
        return sfc.CompressedFC(leaf.mode, leaf.shape, blocked=blocked)
    if leaf.mode == "int8":
        qt = q.QTensor(q=P(tp_axis, None), scale=P(tp_axis, None),
                       bits=leaf.qt.bits)
        return sfc.CompressedFC(leaf.mode, leaf.shape, qt=qt)
    if leaf.mode == "codebook4":
        return sfc.CompressedFC(leaf.mode, leaf.shape,
                                codes_packed=P(tp_axis, None),
                                centroids=P())
    return sfc.CompressedFC(leaf.mode, leaf.shape, dense=P(tp_axis, None))


def _padded_rows(leaf: sfc.CompressedFC) -> int:
    if leaf.mode in ("acsr", "aida"):
        return leaf.blocked.values.shape[-3] * leaf.blocked.block_rows
    return partition.row_axis_len(leaf)


def apply_fc_sharded(plan, layer: sfc.CompressedFC, x: jnp.ndarray,
                     bias: Optional[jnp.ndarray] = None,
                     activation: Optional[str] = None) -> jnp.ndarray:
    """y = act(x @ W.T + bias) for a single-layer compressed leaf,
    computed shard-locally over ``plan``'s model axis.  x: [B, n_in]."""
    tp, ax = plan.tp, plan.tp_axis
    n_out = layer.shape[0]
    if tp == 1 or not partition.shardable(layer, tp):
        return sfc.apply_fc(layer, x, bias=bias, activation=activation)
    policy = plan.policy_for(layer.mode)

    if policy == "psum" and layer.mode == "int8" \
            and layer.shape[1] % tp == 0:
        def local_psum(q_band, x_band):
            acc = jnp.matmul(x_band, q_band.astype(jnp.float32).T,
                             preferred_element_type=jnp.float32)
            return jax.lax.psum(acc, ax)

        acc = jax.shard_map(local_psum, mesh=plan.mesh,
                            in_specs=(P(None, ax), P(None, ax)),
                            out_specs=P(None, None),
                            check_vma=False)(layer.qt.q, x)
        # slice padded rows off BEFORE the epilogue: bias carries the
        # true n_out, the padded q/scale rows are inert
        y = acc[:, :n_out] * layer.qt.scale.reshape(1, -1)[:, :n_out]
        return ops.bias_act_epilogue(y, bias, activation)

    # ------------------------------------------------ gather (default)
    rows_pad = _padded_rows(layer)
    bias_p = None
    if bias is not None:
        bias_p = jnp.pad(bias.astype(jnp.float32),
                         (0, rows_pad - bias.shape[0]))

    if bias_p is None:
        def local(lay, xx):
            return sfc.apply_fc(_local_layer(lay), xx,
                                activation=activation)
        y = jax.shard_map(local, mesh=plan.mesh,
                          in_specs=(_row_specs(layer, ax), P(None, None)),
                          out_specs=P(None, ax), check_vma=False)(layer, x)
    else:
        def local(lay, xx, bb):
            return sfc.apply_fc(_local_layer(lay), xx, bias=bb,
                                activation=activation)
        y = jax.shard_map(local, mesh=plan.mesh,
                          in_specs=(_row_specs(layer, ax), P(None, None),
                                    P(ax)),
                          out_specs=P(None, ax),
                          check_vma=False)(layer, x, bias_p)
    return y[:, :n_out]


# ------------------------------------------------- paged attention (kv)
def _pool_specs(pool, ax: str):
    """PagedKV-shaped shard_map spec tree: pages + scales over heads."""
    from repro.kvstore.pool import PagedKV
    return PagedKV(
        k_pages=P(None, ax, None, None), v_pages=P(None, ax, None, None),
        k_scale=None if pool.k_scale is None else P(None, ax),
        v_scale=None if pool.v_scale is None else P(None, ax))


def _paged_shardable(plan, hkv: int) -> bool:
    # h % tp == 0 follows from hkv % tp == 0 (GQA groups are contiguous
    # per kv head in the [Hkv, G] head layout every kernel uses)
    return plan is not None and plan.tp > 1 and hkv % plan.tp == 0


def paged_attention_sharded(plan, q: jnp.ndarray, pool, table: jnp.ndarray,
                            cur_pos: jnp.ndarray, window, *,
                            scale: Optional[float] = None,
                            cap: Optional[float] = None,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Decode paged attention (q [B, H, Dh]) with the KV pool head-sharded
    over ``plan``'s model axis: each shard runs the tuned kernel on its
    own Hkv/tp heads and local page arrays; outputs concatenate along the
    head axis (gather combine — every head computed entirely on one
    shard, bit-identical to single-device).  Falls back to the plain
    dispatcher when no plan is active or heads do not divide."""
    from repro import kvstore as kv
    b, h, dh = q.shape
    hkv = pool.k_pages.shape[1]
    if not _paged_shardable(plan, hkv):
        return kv.paged_attention(q, pool, table, cur_pos, window,
                                  scale=scale, cap=cap, interpret=interpret)
    # resolve with the GLOBAL geometry so every shard (and the
    # single-device reference) executes the identical kernel
    impl, pb, interp = kv.resolve_paged(b, h, dh, pool, table.shape[1],
                                        interpret)
    ax = plan.tp_axis

    def local(qq, pp, tbl, pos, win):
        return kv.paged_attention(qq, pp, tbl, pos, win, scale=scale,
                                  cap=cap, impl=impl, pb=pb,
                                  interpret=interp)

    return jax.shard_map(
        local, mesh=plan.mesh,
        in_specs=(P(None, ax, None), _pool_specs(pool, ax),
                  P(None, None), P(None), P()),
        out_specs=P(None, ax, None), check_vma=False)(
            q, pool, table, cur_pos, jnp.asarray(window, jnp.int32))


def paged_attention_chunk_sharded(plan, q: jnp.ndarray, pool,
                                  table: jnp.ndarray, q_pos: jnp.ndarray,
                                  window, *,
                                  scale: Optional[float] = None,
                                  cap: Optional[float] = None,
                                  interpret: Optional[bool] = None
                                  ) -> jnp.ndarray:
    """Chunked-prefill paged attention (q [B, H, C, Dh] at positions
    ``q_pos`` [B, C]) run shard-local over the plan's model axis — the
    prefill-side twin of :func:`paged_attention_sharded`."""
    from repro import kvstore as kv
    b, h, c, dh = q.shape
    hkv = pool.k_pages.shape[1]
    if not _paged_shardable(plan, hkv):
        return kv.paged_attention_chunk(q, pool, table, q_pos, window,
                                        scale=scale, cap=cap,
                                        interpret=interpret)
    impl, pb, qt, interp = kv.resolve_paged_chunk(b, h, c, dh, pool,
                                                  table.shape[1], interpret)
    ax = plan.tp_axis

    def local(qq, pp, tbl, pos, win):
        return kv.paged_attention_chunk(qq, pp, tbl, pos, win, scale=scale,
                                        cap=cap, impl=impl, pb=pb, qt=qt,
                                        interpret=interp)

    return jax.shard_map(
        local, mesh=plan.mesh,
        in_specs=(P(None, ax, None, None), _pool_specs(pool, ax),
                  P(None, None), P(None, None), P()),
        out_specs=P(None, ax, None, None), check_vma=False)(
            q, pool, table, q_pos, jnp.asarray(window, jnp.int32))
