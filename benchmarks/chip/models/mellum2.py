"""Mellum2-12B-A2.5B (hf:JetBrains/Mellum2-12B-A2.5B-Instruct,
``model_type`` mellum) as one chip of an expert-parallel deployment
serves it: the weights the benchmark serves, the plain reference
forward, its lower-precision control, and the operations and bytes the
model needs per token.

Nothing here imports the program under test.  ``make_weights`` writes
the program's parameter layout (the one thing the program dictates), and
the reference reads the same arrays, regenerated from the seed.

The reference follows the published configuration: RMSNorm; grouped
query attention (32 query heads, 4 KV heads of 128, no biases, scale
1/sqrt(128)); layers of the kind ``sliding_attention`` attend the keys
in (q - sliding_window, q] with plain RoPE at ``rope_theta``, layers of
the kind ``full_attention`` attend every earlier key with YaRN RoPE
(factor, original length, beta_fast, beta_slow and attention factor of
``rope_parameters.full_attention``, cos and sin both scaled by the
attention factor); every MLP is sparse: a router over all experts, a
softmax, the top ``num_experts_per_tok`` renormalised over themselves,
and a SwiGLU expert of width ``moe_intermediate_size``; untied output
head.  It computes in float32 at ``Precision.HIGHEST``.  This chip's
share of the deployment (``deployment.experts_held``) is what the
reference gives too: a token's chosen experts held elsewhere add
nothing.  Departures, each a matter of layout and not of arithmetic:

* the RMSNorm weight is stored as ``scale`` and applied as
  ``1 + scale`` (the repository's layout);
* projections are stored ``[d_in, d_out]`` (``x @ w``), stacked over
  layers, and the held experts' stacks ``[layers, held, d_in, d_out]``
  in bfloat16, which is the weight: the reference reads them in
  float32.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import numpy as np


# ------------------------------------------------------------------ sizes
class Dims:
    """The sizes the benchmark reads from a configuration file (HF keys,
    and the ``deployment`` the cut stands for)."""

    def __init__(self, conf: Dict):
        self.d = int(conf["hidden_size"])
        self.f = int(conf["intermediate_size"])
        self.layers = int(conf["num_hidden_layers"])
        self.heads = int(conf["num_attention_heads"])
        self.kv_heads = int(conf["num_key_value_heads"])
        self.head_dim = int(conf["head_dim"])
        self.vocab = int(conf["vocab_size"])
        rope = conf["rope_parameters"]
        self.theta = float(rope["sliding_attention"]["rope_theta"])
        self.full_theta = float(rope["full_attention"]["rope_theta"])
        self.yarn = rope["full_attention"]
        self.eps = float(conf["rms_norm_eps"])
        self.window = int(conf["sliding_window"])
        self.full = [t == "full_attention" for t in conf["layer_types"]]
        dep = conf["deployment"]
        self.experts = int(dep["experts"])            # the router's width
        self.held = [int(e) for e in dep["experts_held"]]
        if len(self.held) != int(conf["num_experts"]):
            raise ValueError(f"num_experts {conf['num_experts']} is not "
                             f"the {len(self.held)} experts held")
        self.top_k = int(conf["num_experts_per_tok"])
        self.d_expert = int(conf["moe_intermediate_size"])
        self.weight_bits = conf.get("weight_format", {}).get("dense_bits",
                                                             16)
        self.kv_bytes = {"bf16": 2, "int8": 1}[conf["serving"]["kv_dtype"]]


def attn_weights(dims: Dims) -> int:
    """Weights of one layer's q, k, v and o projections."""
    q = dims.heads * dims.head_dim
    kv = dims.kv_heads * dims.head_dim
    return 2 * dims.d * q + 2 * dims.d * kv


def expert_weights(dims: Dims) -> int:
    """Weights of one expert (gate, up and down)."""
    return 3 * dims.d * dims.d_expert


def held_share(dims: Dims) -> float:
    """Share of a token's chosen experts that are held here, in
    expectation over an unbiased router."""
    return len(dims.held) / dims.experts


def kv_bytes_per_token(dims: Dims) -> int:
    """K and V of one position in one layer."""
    return 2 * dims.kv_heads * dims.head_dim * dims.kv_bytes


def token_flops(dims: Dims, context: int, sampled: bool) -> float:
    """Operations of one token at a position that attends ``context``
    keys in every layer: attention projections, the router, the experts
    this chip computes for it in expectation (top_k x held / experts),
    attention scores and the weighted sum, and the logits if sampled."""
    per_layer = (2 * attn_weights(dims) + 2 * dims.d * dims.experts
                 + 2 * dims.top_k * held_share(dims) * expert_weights(dims)
                 + 4 * dims.heads * dims.head_dim * context)
    head = 2 * dims.vocab * dims.d if sampled else 0
    return float(dims.layers * per_layer + head)


def step_bytes(dims: Dims, contexts: Sequence[int], sampled: int) -> float:
    """Least bytes one model step moves: the attention projections and
    the router once, every held expert's weights once (at 128 tokens a
    step a held expert goes unrouted with probability about 4e-8), the
    unembedding when a position is sampled, all at ``dense_bits``; and
    the valid KV of every sequence (``contexts``: positions held after
    the step), windowed layers reading at most ``sliding_window``."""
    wb = dims.weight_bits / 8
    w = dims.layers * (attn_weights(dims) + dims.d * dims.experts
                       + len(dims.held) * expert_weights(dims))
    b = w * wb
    if sampled:
        b += dims.vocab * dims.d * wb
    keys = sum((c if full else min(c, dims.window))
               for c in contexts for full in dims.full)
    return b + kv_bytes_per_token(dims) * float(keys)


#: the published pattern: three windowed layers to each full one
WINDOW, PERIOD = 1024, 4


def attention_context(prefix: int, n_new: int) -> float:
    """Keys attended by ``n_new`` consecutive queries after ``prefix``
    positions, summed, averaged over the published layer pattern: a full
    layer's query at position p attends p + 1 keys, a windowed layer's
    min(p + 1, 1,024)."""
    pos = np.arange(prefix, prefix + n_new)
    full = float(np.sum(pos + 1))
    windowed = float(np.sum(np.minimum(pos + 1, WINDOW)))
    return (full + (PERIOD - 1) * windowed) / PERIOD


# ------------------------------------------------------------------- rope
def yarn_range(dims: Dims):
    """(low, high): the dims between which YaRN's ramp runs (HF
    ``find_correction_range`` with truncation)."""
    y, dh, base = dims.yarn, dims.head_dim, dims.full_theta

    def corr(rot):
        return dh * math.log(y["original_max_position_embeddings"]
                             / (rot * 2 * math.pi)) / (2 * math.log(base))
    return (max(math.floor(corr(y["beta_fast"])), 0),
            min(math.ceil(corr(y["beta_slow"])), dh - 1))


def inv_freqs(dims: Dims):
    """(inverse frequencies [layers, head_dim/2], cos/sin scale
    [layers]): plain RoPE on windowed layers, YaRN on full ones."""
    dh = dims.head_dim
    plain = 1.0 / dims.theta ** (np.arange(0, dh, 2, dtype=np.float64)
                                 / dh)
    pos = dims.full_theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    low, high = yarn_range(dims)
    ramp = np.clip((np.arange(dh // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    extra = 1.0 - ramp
    yarn = (1.0 / (dims.yarn["factor"] * pos)) * (1 - extra) \
        + (1.0 / pos) * extra
    freqs = np.stack([yarn if f else plain for f in dims.full])
    scale = np.array([dims.yarn["attention_factor"] if f else 1.0
                      for f in dims.full])
    return freqs.astype(np.float32), scale.astype(np.float32)


# ---------------------------------------------------------------- weights
def key_from_seed(seed: int):
    """A threefry key from a seed of any size (seeds may exceed 32
    bits): two words of numpy's SeedSequence."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _weights(dims: Dims, key):
    import jax
    import jax.numpy as jnp
    d, L = dims.d, dims.layers
    q, kv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    h, fe = len(dims.held), dims.d_expert
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    def proj(d_in, d_out):
        return normal((L, d_in, d_out), d_in ** -0.5)

    def experts(d_in, d_out):
        return normal((L, h, d_in, d_out), d_in ** -0.5) \
            .astype(jnp.bfloat16)

    return {
        "embed": {"table": normal((dims.vocab, d), d ** -0.5)},
        "final_norm": {"scale": normal((d,), 0.1)},
        "lm_head": normal((d, dims.vocab), d ** -0.5),
        "layers": {
            "ln1": {"scale": normal((L, d), 0.1)},
            "ln2": {"scale": normal((L, d), 0.1)},
            "attn": {"wq": proj(d, q), "wk": proj(d, kv), "wv": proj(d, kv),
                     "wo": proj(q, d)},
            "moe": {"router": proj(d, dims.experts),
                    "gate": experts(d, fe), "up": experts(d, fe),
                    "down": experts(fe, d)}}}


def make_weights(dims: Dims, seed: int):
    """Every weight made on the default device in one jitted call from
    ``seed``: projections, the router and the held experts N(0, 1/d_in)
    (expert stacks in bf16), embedding and output head N(0, 1/d), norm
    weights 1 + N(0, 0.01)."""
    import functools
    import jax
    return jax.jit(functools.partial(_weights, dims))(key_from_seed(seed))


def reference_weights(dims: Dims, seed: int):
    """The weights the reference multiplies: those of ``make_weights``."""
    return make_weights(dims, seed)


# -------------------------------------------------------------- reference
def _hidden(dims: Dims, w, tokens, *, control: bool, qblock: int):
    """Hidden states [T, d] after the final norm of the causal forward
    over ``tokens`` [T]; attention runs ``qblock`` queries at a time.

    ``control`` rounds every matmul operand to float8 e4m3 (accumulating
    in f32): the reference one precision below the bf16 compute the
    configuration states."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def rnd(a):
        a = a.astype(f32)
        return a.astype(jnp.float8_e4m3fn).astype(f32) if control else a

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=hi)

    def norm(x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + dims.eps) * (1.0 + scale)

    t = tokens.shape[0]
    h, hkv, dh = dims.heads, dims.kv_heads, dims.head_dim
    g, half = h // hkv, dh // 2
    freqs, fscale = inv_freqs(dims)
    windows = np.array([-1 if f else dims.window for f in dims.full],
                       np.int32)
    held = np.asarray(dims.held)
    nq = t // qblock
    pos = jnp.arange(t)

    def rope(z, fr, sc):                         # [T, heads, dh]
        ang = pos.astype(f32)[:, None] * fr[None, :]
        cos = (jnp.cos(ang) * sc)[:, None, :]
        sin = (jnp.sin(ang) * sc)[:, None, :]
        z1, z2 = z[..., :half], z[..., half:]
        return jnp.concatenate([z1 * cos - z2 * sin, z2 * cos + z1 * sin],
                               axis=-1)

    def layer(x, inp):
        p, fr, sc, win = inp
        a = p["attn"]
        y = norm(x, p["ln1"]["scale"])
        q = rope(mm(y, a["wq"]).reshape(t, h, dh), fr, sc)
        k = rope(mm(y, a["wk"]).reshape(t, hkv, dh), fr, sc)
        v = mm(y, a["wv"]).reshape(t, hkv, dh)
        qg = q.reshape(t, hkv, g, dh)

        def attend(i):
            qs = jax.lax.dynamic_slice_in_dim(qg, i * qblock, qblock)
            s = jnp.einsum("qkgd,ckd->kgqc", rnd(qs), rnd(k),
                           precision=hi) * dh ** -0.5
            qi = (i * qblock + jnp.arange(qblock))[:, None]
            ok = (pos[None, :] <= qi) & ((win < 0) | (pos[None, :] > qi - win))
            s = jnp.where(ok[None, None], s, -jnp.inf)
            pr = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("kgqc,ckd->qkgd", rnd(pr), rnd(v),
                              precision=hi).reshape(qblock, h * dh)

        o = jax.lax.map(attend, jnp.arange(nq)).reshape(t, h * dh)
        x = x + mm(o, a["wo"])
        y = norm(x, p["ln2"]["scale"])
        e = p["moe"]
        probs = jax.nn.softmax(mm(y, e["router"]), axis=-1)   # [T, E]
        top_p, top_e = jax.lax.top_k(probs, dims.top_k)
        top_p = top_p / top_p.sum(-1, keepdims=True)
        # each token's weight on each expert held here (0 if unchosen)
        wgt = (top_p[:, :, None] * (top_e[:, :, None] == held)).sum(1)
        gate = jnp.einsum("td,edf->tef", rnd(y), rnd(e["gate"]),
                          precision=hi)
        up = jnp.einsum("td,edf->tef", rnd(y), rnd(e["up"]), precision=hi)
        hid = jax.nn.silu(gate) * up * wgt[:, :, None]
        x = x + jnp.einsum("tef,efd->td", rnd(hid), rnd(e["down"]),
                           precision=hi)
        return x, None

    x = w["embed"]["table"][tokens]
    x, _ = jax.lax.scan(layer, x, (w["layers"], jnp.asarray(freqs),
                                   jnp.asarray(fscale),
                                   jnp.asarray(windows)))
    return norm(x, w["final_norm"]["scale"]), mm


def _forward(dims: Dims, w, tokens, read_pos, score_ids, *, control: bool,
             block: int, qblock: int):
    """Logits at ``read_pos`` of the causal forward over ``tokens`` [T]:
    returns (best logit [n], logits of ``score_ids`` [n, m], argmax
    [n])."""
    import jax
    import jax.numpy as jnp
    x, mm = _hidden(dims, w, tokens, control=control, qblock=qblock)
    xr = x[read_pos]
    n = xr.shape[0]

    def head(_, i):
        rows = jax.lax.dynamic_slice_in_dim(xr, i * block, block)
        ids = jax.lax.dynamic_slice_in_dim(score_ids, i * block, block)
        lg = mm(rows, w["lm_head"])[:, :dims.vocab]
        return None, (lg.max(-1), jnp.take_along_axis(lg, ids, axis=1),
                      lg.argmax(-1))

    _, (best, scored, top) = jax.lax.scan(head, None,
                                          jnp.arange(n // block))
    return best.reshape(n), scored.reshape(n, -1), top.reshape(n)


def reference_scores(dims: Dims, w, tokens: Sequence[int],
                     read_pos: Sequence[int], score_ids: np.ndarray, *,
                     pad_to: int, control: bool = False,
                     block: int = 256, qblock: int = 256):
    """Run the reference (or its control) over one sequence padded to
    ``pad_to`` positions; returns numpy (best, scored, argmax) at the
    ``read_pos`` positions.  Padding sits after the sequence, so the
    causal mask keeps it out of every position that is read."""
    import jax.numpy as jnp
    n = len(read_pos)
    n_pad = max(block, -(-n // block) * block)
    qblock = math.gcd(qblock, pad_to)
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    pos = np.zeros(n_pad, np.int32)
    pos[:n] = read_pos
    ids = np.zeros((n_pad, score_ids.shape[1]), np.int32)
    ids[:n] = score_ids
    fn = _jitted(dims, control, block, qblock)
    best, scored, top = fn(w, jnp.asarray(toks), jnp.asarray(pos),
                           jnp.asarray(ids))
    return (np.asarray(best)[:n], np.asarray(scored)[:n],
            np.asarray(top)[:n])


def reference_logits(dims: Dims, w, tokens: Sequence[int], *,
                     control: bool = False, qblock: int = 8):
    """Every position's logits [T, vocab] over ``tokens`` (tests; small
    sizes only)."""
    import jax.numpy as jnp
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    qblock = math.gcd(qblock, len(tokens))
    x, mm = _hidden(dims, w, toks, control=control, qblock=qblock)
    return np.asarray(mm(x, w["lm_head"])[:, :dims.vocab])


_JIT: Dict = {}


def _jitted(dims: Dims, control: bool, block: int, qblock: int):
    import functools
    import jax
    key = (id(dims), control, block, qblock)
    if key not in _JIT:
        _JIT[key] = jax.jit(functools.partial(
            _forward, dims, control=control, block=block, qblock=qblock))
    return _JIT[key]
