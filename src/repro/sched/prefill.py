"""Chunked prefill: C prompt tokens per model call, written straight into
KV pages.

The decode step moves one token per slot per call, so a P-token prompt
costs P model calls before the first generated token.  This step embeds a
[B, C] token block, runs the layer stack ONCE over all C positions, and
writes each position's K/V into the page pool through the shared page
table — first-token latency drops from P calls to ceil(P/C).

Mixed prefill+decode batches fall out of the per-slot ``n_tok`` vector:
a prefilling slot carries up to C prompt tokens, a decoding slot carries
1 (its next token, sampled host-side from the previous step's logits),
an idle slot carries 0 — padding positions are redirected to the garbage
page by ``update(valid=...)``, so one fixed [B, C] shape serves every
step and the step jits once per (cfg, C).  Only each slot's last fed
position is sampled, so only its hidden state is unembedded: the step
returns one [Vpad] row per slot, as the decode step does.

Within-chunk causality needs no extra machinery: all C tokens' K/V are
written (in ONE vectorized scatter, `kvstore.update_chunk` — same
two-speed int8 semantics as decode, at chunk granularity) *before* the
chunk attends, and the page-table index IS the absolute position, so the
multi-query chunk mask sees in-chunk keys exactly like history.  The
attention itself dispatches through `kvstore.paged_attention_chunk`
(tuned Pallas chunk kernel or the XLA gather reference), shard-local
over the head axis when a ShardingPlan is active.

Scope: paged KV only (that is the point — prefill writes land in pages),
and architectures without per-token recurrent state (rwkv6/hymba step
their SSM state one token at a time; the Session falls back to
token-by-token prefill there, see `supports_chunked_prefill`).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import kvstore as kvs
from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models.layers import COMPUTE_DTYPE, embed
from repro.models.model import serve_logits
from repro.models.transformer import _norm, ffn, layer_xs


def supports_chunked_prefill(cfg: ArchConfig) -> bool:
    """Chunked prefill needs attention-only token mixing: families with a
    per-token recurrent state (rwkv6 time-mix, hymba's mamba branch)
    would have to scan the chunk token-by-token anyway."""
    return cfg.has_decode and cfg.family not in ("rwkv6", "hymba")


def _block_prefill(cfg: ArchConfig, p: Dict, st: Dict, x, positions,
                   valid, window, table, plan=None, rope=None):
    """One layer over a [B, C, D] chunk: write C tokens' K/V into pages,
    then attend all C queries over the (now-updated) page table.
    Returns (state', x', pairs computed here or None)."""
    nrm = _norm(cfg)
    scale = (cfg.head_dim ** -0.5) if cfg.attn_scale is None \
        else cfg.attn_scale
    q, k, v = attn._qkv(p["attn"], nrm(x, p["ln1"]), cfg.n_heads,
                        cfg.n_kv, cfg.head_dim, positions, cfg.rope_theta,
                        plan=plan, rope_table=rope)
    pool = st["kv"]
    pool = kvs.update_chunk(pool, table,
                            k.astype(jnp.float32), v.astype(jnp.float32),
                            positions, valid=valid)
    if plan is not None and plan.tp > 1:
        from repro.shard import paged_attention_chunk_sharded
        o = paged_attention_chunk_sharded(
            plan, q, pool, table, positions,
            jnp.asarray(window, jnp.int32),
            scale=scale, cap=cfg.attn_softcap)
    else:
        o = kvs.paged_attention_chunk(q, pool, table, positions,
                                      jnp.asarray(window, jnp.int32),
                                      scale=scale, cap=cfg.attn_softcap)
    h = attn.dense(attn._merge_heads(o.astype(COMPUTE_DTYPE)),
                   p["attn"]["wo"], plan=plan)
    new_st = dict(st)
    new_st["kv"] = pool
    if cfg.post_norms:
        h = nrm(h, p["ln1p"])
    x = x + h
    h, _, pairs = ffn(cfg, p, nrm(x, p["ln2"]), plan=plan, valid=valid)
    if cfg.post_norms:
        h = nrm(h, p["ln2p"])
    return new_st, x + h, pairs


def _stack_prefill(cfg: ArchConfig, stacked: Dict, states, x, positions,
                   valid, table, plan=None):
    def body(xc, inp):
        p, st, lx = inp
        new_st, xo, pairs = _block_prefill(cfg, p, st, xc, positions,
                                           valid, lx["window"], table,
                                           plan=plan, rope=lx["rope"])
        return xo, (new_st, pairs)

    x, (new_states, pairs) = jax.lax.scan(
        body, x, (stacked, states, layer_xs(cfg)))
    return new_states, x, pairs


def prefill_hidden(cfg: ArchConfig, params: Dict, state: Dict,
                   tokens: jnp.ndarray, n_tok: jnp.ndarray,
                   plan=None) -> Tuple:
    """The layer stack over a chunk: tokens [B, C], n_tok [B] (0 = idle
    slot) -> (state', hidden states [B, C, D] before the final norm),
    and for a MoE configuration the pairs each layer computed here [L].
    Slot i's tokens occupy absolute positions ``state["pos"][i] ..
    +n_tok[i]-1``; the caller ensures those positions' pages exist in
    the table."""
    if not supports_chunked_prefill(cfg):
        raise ValueError(f"{cfg.name} ({cfg.family}) has per-token "
                         "recurrent state; chunked prefill unsupported")
    table = state.get("page_table")
    if table is None:
        raise ValueError("chunked prefill writes into KV pages; "
                         "state has no page_table (kv_cache='paged' only)")
    b, c = tokens.shape
    offs = jnp.arange(c, dtype=jnp.int32)
    positions = state["pos"][:, None] + offs[None, :]        # [B, C]
    valid = offs[None, :] < n_tok[:, None]                   # [B, C]
    x = embed(tokens, params["embed"])
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, COMPUTE_DTYPE)
    new_layers, x, pairs = _stack_prefill(cfg, params["layers"],
                                          state["layers"], x, positions,
                                          valid, table, plan=plan)
    new_state = {"layers": new_layers, "pos": state["pos"] + n_tok,
                 "page_table": table}
    return (new_state, x) if pairs is None else (new_state, x, pairs)


def prefill_step(cfg: ArchConfig, params: Dict, state: Dict,
                 tokens: jnp.ndarray, n_tok: jnp.ndarray,
                 plan=None) -> Tuple:
    """tokens [B, C], n_tok [B] (0 = idle slot) -> (state', logits
    [B, Vpad]): row i is the logits of slot i's last fed position
    ``n_tok[i]-1``, the one its next token is sampled from (an idle
    slot's row is position 0's, and is not read).  A MoE configuration
    also returns the (token, expert) pairs each layer computed here
    ([L] int32), as the decode step does.  ``plan`` = serving
    ShardingPlan (the chunk step stays token-identical under it — see
    tests/test_shard)."""
    new_state, x, *pairs = prefill_hidden(cfg, params, state, tokens,
                                          n_tok, plan=plan)
    last = jnp.maximum(n_tok - 1, 0)[:, None, None]           # [B, 1, 1]
    x = jnp.take_along_axis(x, last, axis=1)                  # [B, 1, D]
    logits = serve_logits(cfg, params, x)[:, 0, :]
    return (new_state, logits, *pairs)


# Compiled chunk steps keyed by (cfg, C): the step is backend-agnostic
# (compressed FC leaves route through repro.api.dispatch inside dense()),
# so sessions on the same geometry share one jitted step per chunk width.
_PREFILL_CACHE: dict = {}


def make_prefill_step(cfg: ArchConfig, chunk: int, plan=None,
                      in_shardings=None, out_shardings=None):
    """The jitted [B, chunk] prefill step for ``cfg``.

    The decode state (argnum 1) is DONATED — same contract as the
    Session's decode step, so the (possibly sharded) KV pool buffers are
    reused in place instead of silently copied every chunk.  Callers
    must treat the state they pass in as consumed.

    plan=None steps are cached per (cfg, chunk); mesh steps compile per
    session because their in/out shardings depend on the session's
    concrete param/state trees."""
    def serve_chunked_step(params, state, tokens, n_tok):
        return prefill_step(cfg, params, state, tokens, n_tok, plan=plan)

    if plan is not None:
        return jax.jit(serve_chunked_step, in_shardings=in_shardings,
                       out_shardings=out_shardings, donate_argnums=(1,))
    key = (cfg, chunk)
    if key not in _PREFILL_CACHE:
        _PREFILL_CACHE[key] = jax.jit(serve_chunked_step,
                                      donate_argnums=(1,))
    return _PREFILL_CACHE[key]
