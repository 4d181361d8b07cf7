"""Model-level Deep-Compression pipeline: turn trained dense params into
AIDA serving format (prune -> k-means share -> pack), per projection.

Stacked layer weights [L, d_in, d_out] become stacked CompressedFC pytrees
(uniform padded nnz across layers so the scan-over-layers decode still
works); `models.layers.dense` dispatches on the leaf type via
`repro.api.dispatch`, so EVERY architecture's projections can serve
compressed — the paper's "FC layers of DNN" surface, generalized to the zoo.

This is the facade-owned implementation (the old `repro.serve.compress`
shim was removed in PR 2).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import env
from repro.api.spec import CompressionSpec
from repro.core import sparse_fc as sfc
from repro.kernels import acsr_spmv as sp

# projection leaves eligible for compression (2D per layer, stacked to 3D)
TARGET_SUFFIXES = ("wq", "wk", "wv", "wo", "up", "down", "gate",
                   "wr", "wg", "in_proj", "out_proj")
SKIP_SUBSTR = ("ln", "mu", "bq", "bk", "bv", "conv", "A_log", "dt",
               "router", "x_db", "w_A", "w_B", "embed")


def _stack_compressed(per_layer: List[sfc.CompressedFC]) -> sfc.CompressedFC:
    """Stack per-layer CompressedFC into one scan-compatible pytree."""
    mode = per_layer[0].mode
    if mode in ("acsr", "aida"):
        # uniform slot depth across layers (pad the rmax axis; padding
        # slots are masked by row_nnz, so values/cols just zero-pad);
        # per-layer nnz may differ, so the stacked aux records nnz=-1
        rmax = max(c.blocked.rmax for c in per_layer)
        bs = [c.blocked for c in per_layer]

        def stk(arrs, pad_slots=True):
            if pad_slots:
                arrs = [jnp.pad(a, ((0, 0), (0, rmax - a.shape[1]),
                                    (0, 0))) for a in arrs]
            return jnp.stack(arrs)

        b0 = bs[0]
        blocked = sp.BlockedACSR(
            values=stk([b.values for b in bs]),
            col_idx=stk([b.col_idx for b in bs]),
            row_nnz=stk([b.row_nnz for b in bs], pad_slots=False),
            shape=b0.shape, block_rows=b0.block_rows, nnz=-1,
            centroids=(None if b0.centroids is None
                       else jnp.stack([b.centroids for b in bs])))
        return sfc.CompressedFC(mode=mode, shape=per_layer[0].shape,
                                blocked=blocked)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)


def compress_params(params: Dict, spec: CompressionSpec = None, *,
                    mode: str = None, density: float = None, k: int = None,
                    verbose=print) -> Tuple[Dict, Dict]:
    """Replace every eligible stacked projection in params['layers'] with a
    stacked CompressedFC per `spec`.  Returns (new_params, stats).

    `spec` may be a CompressionSpec, a bare mode string, or None; the
    keyword shortcuts (mode/density/k) override the matching spec fields.
    """
    spec = CompressionSpec.coerce(mode if spec is None and mode else spec)
    updates = {kk: v for kk, v in
               [("mode", mode), ("density", density), ("k", k)]
               if v is not None}
    if updates:
        spec = dataclasses.replace(spec, **updates)
    stats = {"n_compressed": 0, "bytes_dense": 0, "bytes_compressed": 0,
             "modes": {}, "spec": spec}

    def leaf_bytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    def transform(path, leaf):
        name = str(path[-1].key if hasattr(path[-1], "key") else path[-1])
        pstr = "/".join(str(getattr(p, "key", p)) for p in path)
        if leaf.ndim != 3 or not name.endswith(TARGET_SUFFIXES):
            return leaf
        if any(s in pstr for s in SKIP_SUBSTR):
            return leaf
        leaf_mode = spec.mode_for(pstr)
        if leaf_mode == "skip":
            return leaf
        L = leaf.shape[0]
        block_rows = spec.block_rows
        if leaf_mode in ("acsr", "aida") and env.TUNE_BLOCK_ROWS:
            # encode-time tile search: pick the row-block height by timing
            # the fused kernel on this projection's pruned layer-0 weights
            from repro.core import acsr as acsr_mod
            from repro.kernels import ops, tune
            w0 = acsr_mod.prune_topk(np.asarray(leaf[0]).T, spec.density)
            block_rows = tune.choose_block_rows(
                w0, leaf_mode, spec.density,
                interpret=ops.pallas_interpret())
        per = [sfc.compress(np.asarray(leaf[i]).T, mode=leaf_mode,
                            density=spec.density, k=spec.k,
                            block_rows=block_rows,
                            kmeans_iters=spec.kmeans_iters,
                            dtype=spec.dtype)
               for i in range(L)]
        out = _stack_compressed(per)
        if spec.shards > 1:
            # shard-aware stacking: pad the partition axis now so a
            # ShardingPlan with tp == shards splits it with zero
            # session-time re-stacking (padded rows are inert)
            from repro.shard.partition import pad_leaf
            out = pad_leaf(out, spec.shards)
        stats["n_compressed"] += L
        stats["modes"][leaf_mode] = stats["modes"].get(leaf_mode, 0) + L
        stats["bytes_dense"] += leaf.size * 2  # bf16-serving baseline
        stats["bytes_compressed"] += leaf_bytes(out)
        if verbose:
            verbose(f"  compressed {pstr} {tuple(leaf.shape)} [{leaf_mode}]")
        return out

    new_layers = jax.tree_util.tree_map_with_path(transform,
                                                  params["layers"])
    out = dict(params)
    out["layers"] = new_layers
    stats["ratio"] = (stats["bytes_dense"]
                      / max(stats["bytes_compressed"], 1))
    return out, stats
