"""Top-level model API: init / sharding specs / forward / loss / decode.

Everything is pure-functional and eval_shape-friendly: the dry-run lowers
`train_step` / `serve_step` against ShapeDtypeStructs produced by
`jax.eval_shape(init_params, ...)` — no parameter is ever materialized for
the full-size configs.

Sharding (GSPMD): parameters carry PartitionSpecs (FSDP over `data`, TP over
`model`, EP over `model` when expert counts divide); batch/cache specs adapt
per shape cell (batch shards over ("pod","data") when divisible, KV caches
shard their *sequence* dimension over `model` — distributed flash-decode —
falling back to ("data","model") sequence sharding for batch-1 long-context).
Cross-entropy is vocab-parallel: logits stay vocab-sharded, the label pick
and logsumexp reduce via one-hot contractions (psum), never gathering [B,S,V].
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import transformer as tfm
from repro.models.layers import (COMPUTE_DTYPE, dense, dense_init, embed,
                                 embed_init, softcap, unembed)
from repro.models.transformer import _norm, _norm_init


# ---------------------------------------------------------------- params
def init_params(cfg: ArchConfig, key) -> Dict:
    ks = jax.random.split(key, 4)
    p = {"embed": embed_init(ks[0], cfg.vocab_padded, cfg.d_model),
         "final_norm": _norm_init(cfg, cfg.d_model),
         "layers": tfm.stack_init(cfg, ks[1])}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_padded)
    if cfg.frontend == "audio":
        p["frontend"] = dense_init(ks[3], cfg.audio_in_dim, cfg.d_model)
    return p


def _layer_specs(cfg: ArchConfig, mesh_shape: Dict[str, int]) -> Dict:
    """PartitionSpecs for ONE layer (leading scan dim added by caller)."""
    fsdp, tp = "data", "model"
    norm = {"scale": P()} if cfg.norm == "rms" else \
        {"scale": P(), "bias": P()}
    if cfg.family == "rwkv6":
        return {
            "ln1": dict(norm), "ln2": dict(norm),
            "tm": {"mu": P(), "w0": P(), "w_A": P(fsdp, None),
                   "w_B": P(None, tp), "wr": P(fsdp, tp), "wk": P(fsdp, tp),
                   "wv": P(fsdp, tp), "wg": P(fsdp, tp), "u": P(tp, None),
                   "ln_scale": P(), "ln_bias": P(), "wo": P(tp, fsdp)},
            "cm": {"mu": P(), "wk": P(fsdp, tp), "wv": P(tp, fsdp),
                   "wr": P(fsdp, tp)},
        }
    sp = {"ln1": dict(norm), "ln2": dict(norm),
          "attn": {"wq": P(fsdp, tp), "wk": P(fsdp, tp), "wv": P(fsdp, tp),
                   "wo": P(tp, fsdp)}}
    if cfg.qkv_bias:
        sp["attn"].update({"bq": P(tp), "bk": P(tp), "bv": P(tp)})
    if cfg.post_norms:
        sp["ln1p"] = dict(norm)
        sp["ln2p"] = dict(norm)
    if cfg.moe:
        ep = cfg.moe.n_experts % mesh_shape.get(tp, 1) == 0
        if ep:
            sp["moe"] = {"router": P(), "gate": P(tp, fsdp, None),
                         "up": P(tp, fsdp, None), "down": P(tp, None, fsdp)}
        else:
            sp["moe"] = {"router": P(), "gate": P(None, fsdp, tp),
                         "up": P(None, fsdp, tp), "down": P(None, tp, fsdp)}
    else:
        mlp_sp = {"up": P(fsdp, tp), "down": P(tp, fsdp)}
        if cfg.gated_mlp:
            mlp_sp["gate"] = P(fsdp, tp)
        sp["mlp"] = mlp_sp
    if cfg.family == "hymba":
        sp["mamba"] = {"in_proj": P(fsdp, tp), "conv": P(None, tp),
                       "x_db": P(tp, None), "dt_proj": P(None, tp),
                       "dt_bias": P(tp), "A_log": P(tp, None), "D": P(tp),
                       "out_proj": P(tp, fsdp)}
        sp["ln_ssm"] = dict(norm)
    return sp


def param_specs(cfg: ArchConfig, mesh_shape: Dict[str, int]) -> Dict:
    add_l = lambda spec: P(*((None,) + tuple(spec)))
    layer = jax.tree.map(add_l, _layer_specs(cfg, mesh_shape),
                         is_leaf=lambda x: isinstance(x, P))
    sp = {"embed": {"table": P("model", None)},
          "final_norm": {"scale": P()} if cfg.norm == "rms"
          else {"scale": P(), "bias": P()},
          "layers": layer}
    if not cfg.tie_embeddings:
        sp["lm_head"] = P(None, "model")
    if cfg.frontend == "audio":
        sp["frontend"] = P(None, None)
    return sp


# --------------------------------------------------------------- forward
def _constrain(x, spec: Optional[P]):
    """with_sharding_constraint that no-ops outside a mesh (unit tests)."""
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def forward(cfg: ArchConfig, params: Dict, batch: Dict, *,
            remat: str = "dots", attn_impl: str = "einsum",
            dp_spec: Optional[Tuple] = None, unroll: bool = False,
            return_hidden: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """-> (logits [B,S,Vpad] f32, aux). batch keys per frontend:
    tokens [B,S] | tokens+img_embeds (vision) | frames (audio).
    dp_spec: tuple of mesh axes the batch dim shards over (None = no mesh)."""
    if cfg.frontend == "audio":
        x = dense(batch["frames"].astype(COMPUTE_DTYPE), params["frontend"])
    else:
        x = embed(batch["tokens"], params["embed"])
        if cfg.frontend == "vision":
            img = batch["img_embeds"].astype(COMPUTE_DTYPE)
            x = jnp.concatenate([img, x], axis=1)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, COMPUTE_DTYPE)
    b, s, _ = x.shape
    x = _constrain(x, P(dp_spec, None, None) if dp_spec else None)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x, aux = tfm.stack_forward(cfg, params["layers"], x, positions,
                               remat=remat, attn_impl=attn_impl,
                               unroll=unroll)
    x = _norm(cfg)(x, params["final_norm"])
    if return_hidden:
        return x, aux
    if cfg.tie_embeddings:
        logits = unembed(x, params["embed"])
    else:
        logits = jnp.matmul(x, params["lm_head"].astype(COMPUTE_DTYPE),
                            preferred_element_type=jnp.float32)
    logits = softcap(logits, cfg.final_softcap)
    logits = _constrain(
        logits, P(dp_spec, None, "model") if dp_spec else None)
    return logits, aux


def _xent(logits: jnp.ndarray, labels: jnp.ndarray,
          mask: jnp.ndarray, vocab: int) -> jnp.ndarray:
    """Vocab-parallel-safe CE: one-hot contractions, no [B,S,V] gather.
    Padded vocab columns (vocab..vpad) are masked out of the logsumexp."""
    vpad = logits.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, vpad), 2)
    logits = jnp.where(col < vocab, logits, -1e30)
    lmax = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = logits - lmax
    lse = jnp.log(jnp.exp(shifted).sum(axis=-1)) + lmax[..., 0]
    onehot = jax.nn.one_hot(labels, vpad, dtype=logits.dtype)
    picked = (shifted * onehot).sum(axis=-1) + lmax[..., 0]
    nll = (lse - picked) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def _xent_streamed(cfg: ArchConfig, params, x, labels, mask,
                   chunk: int = 512, unroll: bool = False) -> jnp.ndarray:
    """CE over SEQ chunks: the [B, S, Vpad] logits tensor never exists —
    per chunk only [B, c, Vpad/tp] lives (§Perf: cuts train temp memory by
    the vocab factor; the psum'd (lse, picked) are [B, c])."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    rem = s % chunk
    if rem:  # pad seq to a chunk multiple; padded positions masked out
        pad = chunk - rem
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        s += pad
    n = s // chunk
    table = params["embed"]["table"] if cfg.tie_embeddings \
        else params["lm_head"]

    def one(ci):
        xs = jax.lax.dynamic_slice_in_dim(x, ci * chunk, chunk, axis=1)
        ls = jax.lax.dynamic_slice_in_dim(labels, ci * chunk, chunk, axis=1)
        if cfg.tie_embeddings:
            lg = jnp.matmul(xs, table.T.astype(COMPUTE_DTYPE),
                            preferred_element_type=jnp.float32)
        else:
            lg = jnp.matmul(xs, table.astype(COMPUTE_DTYPE),
                            preferred_element_type=jnp.float32)
        lg = softcap(lg, cfg.final_softcap)
        vpad = lg.shape[-1]
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, vpad), 2)
        lg = jnp.where(col < cfg.vocab, lg, -1e30)
        lmax = jax.lax.stop_gradient(lg.max(axis=-1, keepdims=True))
        lse = jnp.log(jnp.exp(lg - lmax).sum(axis=-1)) + lmax[..., 0]
        onehot = jax.nn.one_hot(ls, vpad, dtype=lg.dtype)
        picked = ((lg - lmax) * onehot).sum(axis=-1) + lmax[..., 0]
        return lse - picked                                # [B, chunk]

    _, nll = jax.lax.scan(lambda c, ci: (c, one(ci)), (), jnp.arange(n),
                          unroll=n if unroll else 1)
    nll = jnp.moveaxis(nll, 0, 1).reshape(b, s)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(cfg: ArchConfig, params: Dict, batch: Dict, *,
            remat: str = "dots", attn_impl: str = "einsum",
            dp_spec: Optional[Tuple] = None, unroll: bool = False,
            streamed_loss: bool = False,
            loss_chunk: int = 512) -> Tuple[jnp.ndarray, Dict]:
    if streamed_loss and cfg.causal and cfg.family != "encoder":
        x, aux = forward(cfg, params, batch, remat=remat,
                         attn_impl=attn_impl, dp_spec=dp_spec,
                         unroll=unroll, return_hidden=True)
        tokens = batch["tokens"]
        if cfg.frontend == "vision":
            x = x[:, -tokens.shape[1]:]
        labels = tokens[:, 1:]
        mask = (labels >= 0).astype(jnp.float32)
        ce = _xent_streamed(cfg, params, x[:, :-1],
                            jnp.maximum(labels, 0), mask,
                            chunk=loss_chunk, unroll=unroll)
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux}
    logits, aux = forward(cfg, params, batch, remat=remat,
                          attn_impl=attn_impl, dp_spec=dp_spec,
                          unroll=unroll)
    if cfg.family == "encoder" or not cfg.causal:
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        ce = _xent(logits, jnp.maximum(labels, 0), mask, cfg.vocab)
    else:
        tokens = batch["tokens"]
        if cfg.frontend == "vision":  # labels only over the text tail
            logits = logits[:, -tokens.shape[1]:]
        labels = tokens[:, 1:]
        mask = (labels >= 0).astype(jnp.float32)
        ce = _xent(logits[:, :-1], jnp.maximum(labels, 0), mask, cfg.vocab)
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------- decode
def init_decode_state(cfg: ArchConfig, batch: int, max_len: int,
                      kv_cache: str = "full", page_size: int = 16,
                      kv_pool_pages: Optional[int] = None,
                      kv_dtype: str = "int8") -> Dict:
    """Decode state.  kv_cache="paged" swaps the dense per-slot KV cache
    for the kvstore page pool + a shared per-sequence page table (the
    table lives at the top level: one table drives every layer's pool)."""
    if kv_cache == "paged":
        if cfg.family == "rwkv6":
            raise ValueError("paged KV cache needs attention layers; "
                             f"{cfg.name} is attention-free")
        layers = tfm.init_stack_state(cfg, batch, max_len,
                                      kv_cache="paged",
                                      page_size=page_size,
                                      kv_pool_pages=kv_pool_pages,
                                      kv_dtype=kv_dtype)
        from repro import kvstore as kvs
        return {"layers": layers,
                "pos": jnp.zeros((batch,), jnp.int32),
                "page_table": kvs.init_table(batch, max_len, page_size)}
    return {"layers": tfm.init_stack_state(cfg, batch, max_len),
            "pos": jnp.zeros((batch,), jnp.int32)}


def decode_step(cfg: ArchConfig, params: Dict, state: Dict,
                tokens: jnp.ndarray, unroll: bool = False,
                plan=None) -> Tuple[Dict, jnp.ndarray]:
    """tokens [B] -> (state', logits [B, Vpad]).  ``plan`` = the serving
    ShardingPlan threaded down to every projection (None = replicated)."""
    x = embed(tokens[:, None], params["embed"])
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, COMPUTE_DTYPE)
    table = state.get("page_table")        # paged route (static branch)
    new_layers, x = tfm.stack_decode(cfg, params["layers"], state["layers"],
                                     x, state["pos"], unroll=unroll,
                                     page_table=table, plan=plan)
    new_state = {"layers": new_layers, "pos": state["pos"] + 1}
    if table is not None:
        new_state["page_table"] = table
    return new_state, serve_logits(cfg, params, x)[:, 0, :]


@jax.named_scope("logits")
def serve_logits(cfg: ArchConfig, params: Dict, x) -> jnp.ndarray:
    """Final norm, unembedding and softcap of the serving steps:
    x [B, S, D] -> logits [B, S, Vpad] f32."""
    x = _norm(cfg)(x, params["final_norm"])
    if cfg.tie_embeddings:
        logits = unembed(x, params["embed"])
    else:
        logits = jnp.matmul(x, params["lm_head"].astype(COMPUTE_DTYPE),
                            preferred_element_type=jnp.float32)
    return softcap(logits, cfg.final_softcap)


def state_specs(cfg: ArchConfig, batch: int, dp_ok: bool,
                dpax: Tuple[str, ...] = ("data",),
                kv_cache: str = "full", kv_dtype: str = "int8") -> Dict:
    """PartitionSpecs for the decode state (stacked over layers).

    dp_ok: batch divisible by the dp submesh — else batch replicates and the
    cache sequence dim shards over ("data","model") (batch-1 long-context).
    kv_dtype matters for treedef parity: bf16 pools carry None scale
    leaves, so their specs must too.
    """
    bdim = dpax if dp_ok else None
    seq = "model" if dp_ok else ("data", "model")
    if kv_cache == "paged":
        from repro.kvstore import PagedKV
        # pages replicate over data (any sequence may own any page);
        # kv heads shard over model like the dense cache's head dim
        scale_sp = P(None, None, "model") if kv_dtype == "int8" else None
        layers = {"kv": PagedKV(
            k_pages=P(None, None, "model", None, None),
            v_pages=P(None, None, "model", None, None),
            k_scale=scale_sp,
            v_scale=scale_sp)}
        if cfg.family == "hymba":
            layers["mamba"] = {"conv": P(None, bdim, None, "model"),
                               "h": P(None, bdim, "model", None)}
        return {"layers": layers, "pos": P(bdim),
                "page_table": P(bdim, None)}
    if cfg.family == "rwkv6":
        layers = {"tm_prev": P(None, bdim, "model"),
                  "cm_prev": P(None, bdim, "model"),
                  "S": P(None, bdim, "model", None, None)}
    else:
        from repro.models.kvcache import KVCache
        layers = {"kv": KVCache(k=P(None, bdim, None, seq, None),
                                v=P(None, bdim, None, seq, None),
                                pos=P(None, bdim, seq))}
        if cfg.family == "hymba":
            layers["mamba"] = {"conv": P(None, bdim, None, "model"),
                               "h": P(None, bdim, "model", None)}
    return {"layers": layers, "pos": P(bdim)}
