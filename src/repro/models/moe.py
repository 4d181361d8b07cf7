"""Mixture-of-Experts FFN (mellum2 64e/top-8, dbrx 16e/top-4, mixtral
8e/top-2): a dropless routed layer over the experts this chip holds.

The router scores every expert (f32, softmax, top-k renormalised over
the k chosen); the (token, expert) pairs whose expert is held here are
sorted by expert and run as one grouped matmul per projection
(``jax.lax.ragged_dot``: gate, up, SiLU, down), then weighted and summed
back onto their tokens.  There is no capacity and nothing is dropped, so
a token's output never depends on the other rows of its step.  Pairs
whose expert is held elsewhere contribute nothing here: that is this
chip's share of an expert-parallel deployment (``MoECfg.held``), and the
shares of all chips add up to the whole layer.

Expert stacks are initialised as f32 masters (training updates them);
serving holds them in bf16, the precision the matmuls read them in
(``serving_params``), so a decode step casts nothing.  The Switch
load-balancing aux loss (training) is computed from the router
probabilities.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoECfg
from repro.models.layers import COMPUTE_DTYPE, dense_init


def moe_init(key, d: int, cfg: MoECfg):
    ks = jax.random.split(key, 4)
    h, f = len(cfg.held_experts), cfg.d_expert

    def stack(k, d_in, d_out):
        return jax.random.normal(k, (h, d_in, d_out), jnp.float32) \
            * d_in ** -0.5

    return {"router": dense_init(ks[0], d, cfg.n_experts),
            "gate": stack(ks[1], d, f), "up": stack(ks[2], d, f),
            "down": stack(ks[3], f, d)}


def serving_params(params):
    """``params`` with every layer's expert stacks in bf16, as serving
    holds them (a no-op where they already are)."""
    layers = dict(params["layers"])
    layers["moe"] = {k: v if k == "router" else v.astype(COMPUTE_DTYPE)
                     for k, v in layers["moe"].items()}
    return {**params, "layers": layers}


def moe_apply(p, x, cfg: MoECfg, valid: Optional[jnp.ndarray] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x [B, T, D] -> (y [B, T, D], aux_loss scalar, pairs int32: the
    (token, expert) pairs computed here).  ``valid`` [B, T] marks the
    positions that hold a token (None: all); the others are neither
    routed, computed nor counted, and their output is zero."""
    b, t, d = x.shape
    n, k = b * t, cfg.top_k
    held = cfg.held_experts
    xs = x.reshape(n, d).astype(COMPUTE_DTYPE)
    ok = jnp.ones((n,), bool) if valid is None else valid.reshape(n)
    with jax.named_scope("moe.route"):
        logits = jnp.matmul(xs.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)               # [n, E]
        top_p, top_e = jax.lax.top_k(probs, k)                # [n, k]
        top_p = top_p / top_p.sum(-1, keepdims=True)
        # Switch aux loss over the valid tokens: share of tokens whose
        # first choice is e times the mean probability of e
        w_tok = ok.astype(jnp.float32) / jnp.maximum(ok.sum(), 1)
        me = (probs * w_tok[:, None]).sum(0)
        ce = (jax.nn.one_hot(top_e[:, 0], cfg.n_experts)
              * w_tok[:, None]).sum(0)
        aux = cfg.n_experts * jnp.sum(me * ce)
        # local index of each pair's expert here (-1: held elsewhere)
        local = np.full((cfg.n_experts,), -1, np.int32)
        local[np.asarray(held)] = np.arange(len(held), dtype=np.int32)
        pair_l = jnp.asarray(local)[top_e].reshape(n * k)
        here = (pair_l >= 0) & jnp.repeat(ok, k)
        # pairs here sorted by expert, the rest after them
        order = jnp.argsort(jnp.where(here, pair_l, len(held)),
                            stable=True)
        sizes = jnp.zeros((len(held),), jnp.int32).at[
            jnp.where(here, pair_l, 0)].add(here.astype(jnp.int32))
        rows = xs[order // k]                                 # [n*k, D]
    with jax.named_scope("moe.experts"):
        def gmm(a, w):
            return jax.lax.ragged_dot(a, w.astype(COMPUTE_DTYPE), sizes,
                                      preferred_element_type=jnp.float32)
        hid = (jax.nn.silu(gmm(rows, p["gate"]))
               * gmm(rows, p["up"])).astype(COMPUTE_DTYPE)
        out = gmm(hid, p["down"])                             # [n*k, D]
    with jax.named_scope("moe.combine"):
        # rows past the groups are not computed: select, never multiply
        inv = jnp.argsort(order)
        here_s = here[order]
        out = jnp.where(here_s[:, None], out, 0.0)[inv].reshape(n, k, d)
        wk = jnp.where(here.reshape(n, k), top_p, 0.0)
        y = (out * wk[..., None]).sum(1)
    return (y.reshape(b, t, d).astype(COMPUTE_DTYPE), aux,
            here.sum(dtype=jnp.int32))
