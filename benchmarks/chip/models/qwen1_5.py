"""Qwen1.5 (hf:Qwen/Qwen1.5-0.5B, model_type ``qwen2``): the weights the
benchmark serves, the plain reference forward, its lower-precision
control, and the operations and bytes the model needs per token.

Nothing here imports the program under test.  ``make_weights`` writes
the program's parameter layout (the one thing the program dictates), and
the reference reads the same arrays, regenerated from the seed.

The reference follows the published description (Qwen2 decoder layer:
RMSNorm, rotary embedding with theta ``rope_theta`` over the whole head,
multi-head attention with biases on q/k/v and none on o, SwiGLU MLP,
tied embeddings) in float32 at ``Precision.HIGHEST``.  Departures, each
a matter of layout and not of arithmetic:

* the RMSNorm weight is stored as ``scale`` and applied as
  ``1 + scale`` (the repository's layout); the generator draws
  ``scale`` so the weight is ``1 + scale`` either way;
* projections are stored ``[d_in, d_out]`` (``x @ w``), stacked over
  layers;
* no attention or residual dropout and no sliding window
  (``use_sliding_window`` is false in the published config).
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

# Projections the AIDA configuration compresses, by parameter path.
PROJECTIONS = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
               ("attn", "wo"), ("mlp", "gate"), ("mlp", "up"),
               ("mlp", "down"))


# ------------------------------------------------------------------ sizes
class Dims:
    """The sizes the benchmark reads from a configuration file (HF keys)."""

    def __init__(self, conf: Dict):
        self.d = int(conf["hidden_size"])
        self.f = int(conf["intermediate_size"])
        self.layers = int(conf["num_hidden_layers"])
        self.heads = int(conf["num_attention_heads"])
        self.kv_heads = int(conf["num_key_value_heads"])
        self.head_dim = self.d // self.heads
        self.vocab = int(conf["vocab_size"])
        self.theta = float(conf["rope_theta"])
        self.eps = float(conf["rms_norm_eps"])
        comp = conf.get("compression") or {}
        self.density = comp.get("density")
        self.codebook = comp.get("codebook_size")
        self.kmeans_iters = comp.get("kmeans_iters")
        fmt = conf.get("weight_format", {})
        self.weight_bits = fmt.get("dense_bits", 16)
        self.code_bits = fmt.get("code_bits")
        self.col_index_bits = fmt.get("col_index_bits")
        self.kv_bytes = {"bf16": 2, "int8": 1}[conf["serving"]["kv_dtype"]]

    def proj_shapes(self):
        """(d_in, d_out) of each projection in ``PROJECTIONS`` order."""
        d, f = self.d, self.f
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return ((d, q), (d, kv), (d, kv), (q, d), (d, f), (d, f), (f, d))


def proj_nnz(dims: Dims):
    """Weights each projection keeps: all, or ``round(density * size)``
    under magnitude pruning."""
    sizes = [a * b for a, b in dims.proj_shapes()]
    if dims.density is None:
        return sizes
    return [max(1, int(round(dims.density * s))) for s in sizes]


def fc_weights(dims: Dims) -> int:
    """Weights of all projections of all layers that a token multiplies."""
    return dims.layers * sum(proj_nnz(dims))


def fc_weight_bytes(dims: Dims) -> float:
    """Bytes of the projections at the widths the configuration states:
    dense weights at ``dense_bits``; compressed ones as a code of
    ``code_bits`` and a column index of ``col_index_bits`` per kept
    weight (the codebook and row lengths are a few KB)."""
    if dims.density is None:
        return fc_weights(dims) * dims.weight_bits / 8
    return fc_weights(dims) * (dims.code_bits + dims.col_index_bits) / 8


def kv_bytes_per_token(dims: Dims) -> int:
    """K and V of one position in every layer."""
    return dims.layers * 2 * dims.kv_heads * dims.head_dim * dims.kv_bytes


def unembed_weights(dims: Dims) -> int:
    return dims.vocab * dims.d


def token_flops(dims: Dims, context: int, sampled: bool) -> float:
    """Operations of one token at a position that attends ``context``
    keys (itself included): the projections, attention scores and the
    weighted sum in every layer, and the logits if it is sampled."""
    fc = 2 * fc_weights(dims)
    attn = dims.layers * 4 * dims.heads * dims.head_dim * context
    head = 2 * unembed_weights(dims) if sampled else 0
    return float(fc + attn + head)


def step_bytes(dims: Dims, contexts: Sequence[int], sampled: int) -> float:
    """Least bytes one model step moves: the projections once, the
    unembedding (bf16) when a position is sampled, and the valid KV of
    every sequence in the step (``contexts``: positions held after the
    step, the new ones' writes included)."""
    b = fc_weight_bytes(dims)
    if sampled:
        b += unembed_weights(dims) * 2
    return b + kv_bytes_per_token(dims) * float(sum(contexts))


def aida_call_work(dims: Dims, columns: int):
    """(operations, bytes) of the fused AIDA SpMV calls of one model
    step that multiplies ``columns`` activation vectors: per projection,
    2 * nnz * columns operations; bytes of its codes and indices, plus
    the activations in and out in f32."""
    ops = byt = 0.0
    for (d_in, d_out), nnz in zip(dims.proj_shapes(), proj_nnz(dims)):
        ops += 2.0 * nnz * columns
        byt += nnz * (dims.code_bits + dims.col_index_bits) / 8
        byt += 4.0 * (d_in + d_out) * columns
    return ops * dims.layers, byt * dims.layers


# ---------------------------------------------------------------- weights
def key_from_seed(seed: int):
    """A threefry key from a seed of any size (the driver's exceed 32
    bits): two words of numpy's SeedSequence."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _weights(dims: Dims, key):
    import jax
    import jax.numpy as jnp
    d, f, L = dims.d, dims.f, dims.layers
    q, kv = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    def proj(d_in, d_out):
        return normal((L, d_in, d_out), d_in ** -0.5)

    return {
        "embed": {"table": normal((dims.vocab, d), d ** -0.5)},
        "final_norm": {"scale": normal((d,), 0.1)},
        "layers": {
            "ln1": {"scale": normal((L, d), 0.1)},
            "ln2": {"scale": normal((L, d), 0.1)},
            "attn": {"wq": proj(d, q), "wk": proj(d, kv), "wv": proj(d, kv),
                     "wo": proj(q, d), "bq": normal((L, q), 0.5),
                     "bk": normal((L, kv), 0.5), "bv": normal((L, kv), 0.5)},
            "mlp": {"gate": proj(d, f), "up": proj(d, f),
                    "down": proj(f, d)}}}


def make_weights(dims: Dims, seed: int):
    """Every weight, f32, made on the default device in one jitted call
    from ``seed``: projections N(0, 1/d_in), embedding N(0, 1/d), biases
    N(0, 0.25) and norm weights 1 + N(0, 0.01), so that biases and
    norm weights are exercised."""
    import functools
    import jax
    return jax.jit(functools.partial(_weights, dims))(key_from_seed(seed))


# ------------------------------------------------- AIDA compression, again
def _aida_dense(w, density: float, k: int, iters: int):
    """The dense equivalent of one projection ``w`` [d_in, d_out] after
    the AIDA recipe the configuration states: magnitude pruning of the
    [d_out, d_in] matrix to ``density``, Lloyd's k-means over the kept
    weights with ``k - 1`` clusters (linear initialisation between the
    smallest and largest kept weight), and a codebook of those
    centroids plus an exact zero; each kept weight takes its nearest
    code.  Cluster sums are one-hot reductions, not scatters, which a
    TPU serialises."""
    import jax
    import jax.numpy as jnp
    wt = w.T
    n_keep = max(1, int(round(density * wt.size)))
    mag = jnp.abs(wt)
    keep = mag >= jnp.sort(mag.ravel())[-n_keep]
    (idx,) = jnp.nonzero(keep.ravel(), size=n_keep)
    x = wt.ravel()[idx]
    c = k - 1
    lo, hi = x.min(), x.max()
    cents = lo + (hi - lo) * (jnp.arange(c, dtype=jnp.float32) + 0.5) / c

    def lloyd(cents, _):
        assign = jnp.argmin(jnp.abs(x[:, None] - cents[None, :]), axis=1)
        onehot = (assign[:, None] == jnp.arange(c)[None, :])
        sums = jnp.where(onehot, x[:, None], 0.0).sum(0)
        cnts = onehot.sum(0).astype(jnp.float32)
        return jnp.where(cnts > 0, sums / jnp.maximum(cnts, 1.0),
                         cents), None

    cents, _ = jax.lax.scan(lloyd, cents, None, length=iters)
    book = jnp.concatenate([jnp.zeros((1,), jnp.float32), jnp.sort(cents)])
    code = jnp.argmin(jnp.abs(wt[..., None] - book), axis=-1)
    return jnp.where(keep, book[code], 0.0).T


def aida_weights(dims: Dims, weights):
    """``weights`` with every projection replaced by its AIDA dense
    equivalent (one jitted map over the layers per projection)."""
    import functools
    import jax
    one = functools.partial(_aida_dense, density=dims.density,
                            k=dims.codebook, iters=dims.kmeans_iters)
    fn = jax.jit(lambda stacked: jax.lax.map(one, stacked))
    layers = {g: dict(v) for g, v in weights["layers"].items()}
    for group, name in PROJECTIONS:
        layers[group][name] = fn(layers[group][name])
    return {**weights, "layers": layers}


# -------------------------------------------------------------- reference
def _forward(dims: Dims, w, tokens, read_pos, score_ids, *, control: bool,
             block: int):
    """Logits at ``read_pos`` of the causal forward over ``tokens`` [T]:
    returns (best logit [n], logits of ``score_ids`` [n, m], argmax [n]).

    ``control`` rounds every matmul operand to float8 e4m3 (accumulating
    in f32): the reference one precision below the bf16 compute the
    configuration states."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def rnd(a):
        return a.astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            if control else a

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=hi)

    def norm(x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + dims.eps) * (1.0 + scale)

    t = tokens.shape[0]
    h, dh = dims.heads, dims.head_dim
    half = dh // 2
    freqs = dims.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(z):                                   # [T, heads, dh]
        z1, z2 = z[..., :half], z[..., half:]
        return jnp.concatenate([z1 * cos - z2 * sin, z2 * cos + z1 * sin],
                               axis=-1)

    causal = jnp.tril(jnp.ones((t, t), bool))
    table = w["embed"]["table"]
    x = table[tokens]

    def layer(x, p):
        a = p["attn"]
        y = norm(x, p["ln1"]["scale"])
        q = (mm(y, a["wq"]) + a["bq"]).reshape(t, h, dh)
        k = (mm(y, a["wk"]) + a["bk"]).reshape(t, dims.kv_heads, dh)
        v = (mm(y, a["wv"]) + a["bv"]).reshape(t, dims.kv_heads, dh)
        q, k = rope(q), rope(k)
        rep = h // dims.kv_heads
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(k), precision=hi)
        s = jnp.where(causal[None], s * dh ** -0.5, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", rnd(pr), rnd(v), precision=hi)
        x = x + mm(o.reshape(t, h * dh), a["wo"])
        y = norm(x, p["ln2"]["scale"])
        g = p["mlp"]
        x = x + mm(jax.nn.silu(mm(y, g["gate"])) * mm(y, g["up"]),
                   g["down"])
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = norm(x, w["final_norm"]["scale"])
    xr = x[read_pos]                                      # [n, d]
    n = xr.shape[0]
    nb = n // block

    def head(_, i):
        rows = jax.lax.dynamic_slice_in_dim(xr, i * block, block)
        ids = jax.lax.dynamic_slice_in_dim(score_ids, i * block, block)
        lg = mm(rows, table.T)[:, :dims.vocab]
        return None, (lg.max(-1), jnp.take_along_axis(lg, ids, axis=1),
                      lg.argmax(-1))

    _, (best, scored, top) = jax.lax.scan(head, None, jnp.arange(nb))
    return (best.reshape(n), scored.reshape(n, -1), top.reshape(n))


def reference_scores(dims: Dims, w, tokens: Sequence[int],
                     read_pos: Sequence[int], score_ids: np.ndarray, *,
                     pad_to: int, control: bool = False,
                     block: int = 256):
    """Run the reference (or its control) over one sequence padded to
    ``pad_to`` positions; returns numpy (best, scored, argmax) at the
    ``read_pos`` positions.  Padding sits after the sequence, so the
    causal mask keeps it out of every position that is read."""
    import jax.numpy as jnp
    n = len(read_pos)
    n_pad = max(block, -(-n // block) * block)
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    pos = np.zeros(n_pad, np.int32)
    pos[:n] = read_pos
    ids = np.zeros((n_pad, score_ids.shape[1]), np.int32)
    ids[:n] = score_ids
    fn = _jitted(dims, control, block)
    best, scored, top = fn(w, jnp.asarray(toks), jnp.asarray(pos),
                           jnp.asarray(ids))
    return (np.asarray(best)[:n], np.asarray(scored)[:n],
            np.asarray(top)[:n])


_JIT: Dict = {}


def _jitted(dims: Dims, control: bool, block: int):
    import functools
    import jax
    key = (id(dims), control, block)
    if key not in _JIT:
        _JIT[key] = jax.jit(functools.partial(
            _forward, dims, control=control, block=block))
    return _JIT[key]


def reference_weights(dims: Dims, seed: int):
    """The weights the reference multiplies: those of ``make_weights``,
    and for a compressed configuration their AIDA dense equivalent."""
    w = make_weights(dims, seed)
    if dims.density is not None:
        w = aida_weights(dims, w)
    return w


def attention_context(prefix: int, n_new: int) -> float:
    """Keys attended by ``n_new`` consecutive queries after ``prefix``
    positions, summed (each query attends itself and all before it)."""
    return n_new * prefix + n_new * (n_new + 1) / 2
