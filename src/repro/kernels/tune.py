"""Per-layer kernel autotuner — tile search + winner cache for the FC paths.

Decode FC shapes are few and static, so the right tile parameters can be
searched *once per (shape, mode, backend)* on real timings and then read
back at trace time by the `ops` dispatchers:

  acsr / aida   (mb, bk)       — fused row blocks per grid step, K tile
  int8 / lut    impl + (bm, bn, bk) — Pallas tiles, or the XLA reference
                                 (the MXU tiling that wins on TPU loses to
                                 a fused XLA matmul on interpret-mode hosts;
                                 the tuner measures instead of guessing)
  block_rows    — encode-time row-block height (searched at compress time
                  when REPRO_TUNE_BLOCK_ROWS=1; one encoding per candidate)

`Engine.session()` calls :func:`tune_params` before compiling the decode
step, so every unique CompressedFC geometry is tuned eagerly (outside any
jit trace) and the jitted step picks the winners up at trace time.
`Engine.benchmark` embeds :func:`snapshot` into BENCH_api.json so the
chosen tiles ship with every recorded perf number.

The cache is process-global and keyed on everything that changes the
winner: kind, geometry, batch width, and interpret vs native lowering.
Tiles are read at trace time — re-tuning after a step has been compiled
does not retroactively change that step.

A candidate that fails to compile or run is kept with its error on the
winner (``failed``, shown by :func:`snapshot`); a key whose Pallas
candidates all fail raises :class:`TuneError` instead of quietly serving
the XLA reference.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.api import env

Key = Tuple


class TuneError(RuntimeError):
    """Every Pallas candidate of a key failed to compile or run."""


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """One point in a kernel's implementation/tile space."""
    impl: str = "pallas"
    tiles: Tuple[Tuple[str, int], ...] = ()
    us: float = float("nan")          # measured microseconds (best run)
    #: (candidate label, error) of every candidate that failed to run
    failed: Tuple[Tuple[str, str], ...] = ()

    def tile(self, name: str, default: Optional[int] = None) -> Optional[int]:
        return dict(self.tiles).get(name, default)

    @property
    def label(self) -> str:
        return "/".join([self.impl] + [f"{k}={v}" for k, v in self.tiles])

    def to_json(self) -> dict:
        d = {"impl": self.impl, **dict(self.tiles)}
        if np.isfinite(self.us):
            d["us"] = round(self.us, 1)
        if self.failed:
            d["failed"] = dict(self.failed)
        return d


_CACHE: Dict[Key, KernelChoice] = {}


def get(key: Key) -> Optional[KernelChoice]:
    return _CACHE.get(key)


def record(key: Key, choice: KernelChoice) -> None:
    _CACHE[key] = choice


def clear() -> None:
    _CACHE.clear()


def snapshot() -> dict:
    """JSON-ready view of every tuned winner (key -> impl/tiles/us)."""
    return {"/".join(str(p) for p in key): choice.to_json()
            for key, choice in sorted(_CACHE.items(), key=lambda kv: kv[0])}


def enabled() -> bool:
    return env.AUTOTUNE


# ------------------------------------------------------------------- keys
def acsr_key(nblocks: int, rmax: int, block_rows: int, k: int, batch: int,
             coded: bool, interpret: bool) -> Key:
    return ("aida" if coded else "acsr", nblocks, rmax, block_rows, k,
            batch, "interp" if interpret else "tpu")


def int8_key(n: int, k: int, batch: int, interpret: bool) -> Key:
    return ("int8", n, k, batch, "interp" if interpret else "tpu")


def lut_key(n: int, k: int, batch: int, interpret: bool) -> Key:
    return ("codebook4", n, k, batch, "interp" if interpret else "tpu")


def paged_key(hkv: int, group: int, d_head: int, page_size: int, npp: int,
              batch: int, quantized: bool, interpret: bool) -> Key:
    # npp is bucketed to the padded table width the kernel actually runs,
    # so a growing page table hits one cache entry instead of re-tuning
    # (and recompiling) at every width
    from repro.kvstore.paged_attention import npp_bucket
    return ("paged-attn", hkv, group, d_head, page_size, npp_bucket(npp),
            batch, "q8" if quantized else "bf16",
            "interp" if interpret else "tpu")


def paged_chunk_key(hkv: int, group: int, d_head: int, page_size: int,
                    npp: int, batch: int, chunk: int, quantized: bool,
                    interpret: bool) -> Key:
    from repro.kvstore.paged_attention import npp_bucket
    return ("paged-attn-chunk", hkv, group, d_head, page_size,
            npp_bucket(npp), batch, chunk,
            "q8" if quantized else "bf16",
            "interp" if interpret else "tpu")


# ------------------------------------------------------------- candidates
def acsr_candidates(nblocks: int, k: int) -> List[KernelChoice]:
    mbs = sorted({m for m in (1, 2, 4, 8) if m <= max(1, nblocks)})
    bks = sorted({min(k, b) for b in (256, 512, k)}) if k > 256 else [k]
    return [KernelChoice("pallas", (("mb", mb), ("bk", bk)))
            for mb in mbs for bk in bks]


def int8_candidates(n: int, k: int) -> List[KernelChoice]:
    tiles = [(8, 128, 512), (8, 256, 256), (16, 128, 128), (8, 512, 512)]
    cands = [KernelChoice("xla")]
    for bm, bn, bk in tiles:
        cands.append(KernelChoice("pallas", (
            ("bm", bm), ("bn", min(bn, n)), ("bk", min(bk, k)))))
    return cands


def lut_candidates(n: int, k: int) -> List[KernelChoice]:
    tiles = [(8, 128, 512), (8, 128, 256), (8, 256, 512)]
    cands = [KernelChoice("xla")]
    for bm, bn, bk in tiles:
        cands.append(KernelChoice("pallas", (
            ("bm", bm), ("bn", min(bn, n)), ("bk", min(bk, k)))))
    return cands


def paged_candidates(npp: int) -> List[KernelChoice]:
    """XLA gather reference vs the Pallas kernel at a few page-block
    widths (pb = table slots folded per grid step)."""
    from repro.kvstore.paged_attention import npp_bucket
    cands = [KernelChoice("xla")]
    for pb in sorted({min(p, npp_bucket(npp)) for p in (1, 2, 4)}):
        cands.append(KernelChoice("pallas", (("pb", pb),)))
    return cands


def paged_chunk_candidates(npp: int, chunk: int,
                           group: int) -> List[KernelChoice]:
    """Chunked-prefill space: XLA gather reference vs the Pallas chunk
    kernel over (pb page blocks) x (qt query tiles the kernel can run
    natively at this GQA group size)."""
    from repro.kvstore.paged_attention import legal_query_tile, npp_bucket
    cands = [KernelChoice("xla")]
    pbs = sorted({min(p, npp_bucket(npp)) for p in (1, 2, 4)})
    qts = sorted({q for q in (1, 2, 4, 8, 16, chunk)
                  if legal_query_tile(q, chunk, group)})
    for pb in pbs:
        for qt in qts:
            cands.append(KernelChoice("pallas", (("pb", pb), ("qt", qt))))
    return cands


# ---------------------------------------------------------------- search
def autotune(key: Key, candidates: Sequence[KernelChoice],
             runner: Callable[[KernelChoice], object], *,
             reps: int = 3, inner: int = 3) -> KernelChoice:
    """Time each candidate (1 warmup, then ``reps`` samples of ``inner``
    back-to-back calls, best sample) and cache the winner under ``key``.
    Sub-ms kernels need the inner loop — single-call samples are noise on
    a busy host and a wrong pick taxes every decode step afterwards.
    A candidate that fails to compile or run is recorded with its error
    on the winner; if no Pallas candidate runs, :class:`TuneError` is
    raised and nothing is cached.  An already-cached key returns
    immediately."""
    from repro.obs import timeit
    cached = get(key)
    if cached is not None:
        return cached
    timed: List[KernelChoice] = []
    failed: List[Tuple[str, str]] = []
    for cand in candidates:
        try:
            t_best = timeit(runner, cand, reps=reps, inner=inner)
        except Exception as e:  # a refused candidate is data, kept below
            msg = str(e).strip().splitlines()
            failed.append((cand.label, f"{type(e).__name__}: "
                           f"{msg[0][:300] if msg else ''}"))
            continue
        timed.append(dataclasses.replace(cand, us=t_best * 1e6))
    if not any(c.impl == "pallas" for c in timed):
        raise TuneError(
            f"no Pallas candidate of {'/'.join(map(str, key))} ran: "
            + "; ".join(f"{lab}: {err}" for lab, err in failed))
    best = dataclasses.replace(min(timed, key=lambda c: c.us),
                               failed=tuple(failed))
    record(key, best)
    return best


# ------------------------------------------------------- layer-level entry
def _layer0_view(layer):
    """A single-layer view of a (possibly [L, ...]-stacked) CompressedFC."""
    import jax
    import jax.numpy as jnp
    from repro.core import sparse_fc as sfc

    def unstack(x):
        return x[0] if isinstance(x, jnp.ndarray) else x

    leaves, treedef = jax.tree_util.tree_flatten(layer)
    ndims = {"dense": 2, "int8": 2, "codebook4": 2, "acsr": 3, "aida": 3}
    # stacked leaves carry one extra leading dim vs the single-layer layout
    want = ndims[layer.mode]
    probe = leaves[0]
    if probe.ndim > want:
        leaves = [unstack(x) for x in leaves]
    lay = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(lay, sfc.CompressedFC)
    return lay


def tune_layer(layer, batch: int, interpret: bool) -> Optional[KernelChoice]:
    """Search tiles for one CompressedFC (stacked or single-layer) at the
    given decode batch width.  Returns the winner (or None for modes with
    nothing to tune)."""
    import jax
    import jax.numpy as jnp
    from repro.core import sparse_fc as sfc
    from repro.kernels import int8_matmul as i8
    from repro.kernels import lut_matmul as lm
    from repro.kernels import acsr_spmv as sp
    from repro.kernels import ref

    lay = _layer0_view(layer)
    n_out, n_in = lay.shape
    rng = np.random.default_rng(0)
    if lay.mode in ("acsr", "aida"):
        b = lay.blocked
        key = acsr_key(b.nblocks, b.rmax, b.block_rows, n_in, batch,
                       b.centroids is not None, interpret)
        if get(key) is not None:
            return get(key)
        x = jnp.asarray(rng.normal(size=(n_in, batch)).astype(np.float32))

        def run(c):
            return sp.acsr_spmv(b, x, mb=c.tile("mb"), bk=c.tile("bk"),
                                interpret=interpret)
        return autotune(key, acsr_candidates(b.nblocks, n_in), run)
    if lay.mode == "int8":
        key = int8_key(n_out, n_in, batch, interpret)
        if get(key) is not None:
            return get(key)
        x = jnp.asarray(rng.normal(size=(batch, n_in)).astype(np.float32))
        from repro.core import quant as q
        # jit the XLA candidate — inside a decode step it runs XLA-fused
        xla_run = jax.jit(lambda xx: q.int8_matmul_ref(xx, lay.qt))

        def run(c):
            if c.impl == "xla":
                return xla_run(x)
            return i8.int8_matmul(x, lay.qt.q, lay.qt.scale,
                                  bm=c.tile("bm"), bn=c.tile("bn"),
                                  bk=c.tile("bk"), interpret=interpret)
        return autotune(key, int8_candidates(n_out, n_in), run)
    if lay.mode == "codebook4":
        key = lut_key(n_out, n_in, batch, interpret)
        if get(key) is not None:
            return get(key)
        x = jnp.asarray(rng.normal(size=(batch, n_in)).astype(np.float32))
        xla_run = jax.jit(lambda xx: ref.lut_matmul_ref(
            xx, lay.codes_packed, lay.centroids))

        def run(c):
            if c.impl == "xla":
                return xla_run(x)
            return lm.lut_matmul(x, lay.codes_packed, lay.centroids,
                                 bm=c.tile("bm"), bn=c.tile("bn"),
                                 bk=c.tile("bk"), interpret=interpret)
        return autotune(key, lut_candidates(n_out, n_in), run)
    return None


def _filled_pool(cfg, batch: int, max_len: int, page_size: int,
                 kv_dtype: str):
    """A synthetic pool in which every table slot owns a page and every
    position is written (one jitted chunk write): the full-occupancy
    gather, the steady-state cost of a long sequence."""
    import jax
    import jax.numpy as jnp
    from repro import kvstore as kvsto

    hkv, dh = cfg.n_kv, cfg.head_dim
    npp = -(-max_len // page_size)
    pool = kvsto.init_pool(1 + batch * npp, hkv, page_size, dh,
                           kv_dtype=kv_dtype)
    table = jnp.asarray(
        1 + np.arange(batch * npp).reshape(batch, npp), jnp.int32)
    rng = np.random.default_rng(0)
    k, v = (jnp.asarray(rng.normal(size=(batch, hkv, max_len, dh)),
                        jnp.float32) for _ in range(2))
    pos = jnp.broadcast_to(jnp.arange(max_len, dtype=jnp.int32),
                           (batch, max_len))
    return jax.jit(kvsto.update_chunk)(pool, table, k, v, pos), table


def tune_paged(cfg, batch: int, max_len: int, page_size: int,
               kv_dtype: str, interpret: bool) -> Optional[KernelChoice]:
    """Search the paged-attention impl/tile space for one serving
    geometry (cfg attention shape x batch x table width) on a synthetic
    fully-populated pool — the worst-case gather the decode step runs."""
    import jax
    import jax.numpy as jnp
    from repro import kvstore as kvsto

    hkv, dh = cfg.n_kv, cfg.head_dim
    group = cfg.n_heads // hkv
    npp = -(-max_len // page_size)
    quantized = kv_dtype == "int8"
    key = paged_key(hkv, group, dh, page_size, npp, batch, quantized,
                    interpret)
    if get(key) is not None:
        return get(key)
    rng = np.random.default_rng(0)
    pool, table = _filled_pool(cfg, batch, max_len, page_size, kv_dtype)
    q = jnp.asarray(rng.normal(size=(batch, cfg.n_heads, dh)), jnp.float32)
    cur = jnp.full((batch,), max_len - 1, jnp.int32)
    win = jnp.int32(-1)
    # jit the XLA candidate — inside a decode step it runs XLA-fused
    xla_run = jax.jit(lambda qq, pp, tt, cc, ww: kvsto.paged_attention_xla(
        qq, pp, tt, cc, ww, scale=cfg.attn_scale, cap=cfg.attn_softcap))

    def run(c):
        if c.impl == "xla":
            return xla_run(q, pool, table, cur, win)
        return kvsto.paged_attention_pallas(
            q, pool, table, cur, win, scale=cfg.attn_scale,
            cap=cfg.attn_softcap, pb=c.tile("pb", 2), interpret=interpret)
    return autotune(key, paged_candidates(npp), run)


def tune_paged_chunk(cfg, batch: int, max_len: int, page_size: int,
                     chunk: int, kv_dtype: str,
                     interpret: bool) -> Optional[KernelChoice]:
    """Search the chunked-prefill paged-attention space for one serving
    geometry: a [batch, H, chunk, Dh] query block over a fully-populated
    synthetic pool — the steady-state cost of the last prefill chunk of a
    long prompt."""
    import jax
    import jax.numpy as jnp
    from repro import kvstore as kvsto

    if chunk <= 1:
        return None
    hkv, dh = cfg.n_kv, cfg.head_dim
    group = cfg.n_heads // hkv
    npp = -(-max_len // page_size)
    quantized = kv_dtype == "int8"
    key = paged_chunk_key(hkv, group, dh, page_size, npp, batch, chunk,
                          interpret=interpret, quantized=quantized)
    if get(key) is not None:
        return get(key)
    rng = np.random.default_rng(0)
    pool, table = _filled_pool(cfg, batch, max_len, page_size, kv_dtype)
    q = jnp.asarray(rng.normal(size=(batch, cfg.n_heads, chunk, dh)),
                    jnp.float32)
    # query the trailing chunk of the sequence (the worst-case mask span)
    q_pos = jnp.broadcast_to(
        jnp.arange(max_len - chunk, max_len, dtype=jnp.int32)[None, :],
        (batch, chunk))
    win = jnp.int32(-1)
    xla_run = jax.jit(
        lambda qq, pp, tt, qp, ww: kvsto.paged_attention_xla_chunk(
            qq, pp, tt, qp, ww, scale=cfg.attn_scale, cap=cfg.attn_softcap))

    def run(c):
        if c.impl == "xla":
            return xla_run(q, pool, table, q_pos, win)
        return kvsto.paged_attention_pallas_chunk(
            q, pool, table, q_pos, win, scale=cfg.attn_scale,
            cap=cfg.attn_softcap, pb=c.tile("pb", 2),
            qt=c.tile("qt", chunk), interpret=interpret)
    return autotune(key, paged_chunk_candidates(npp, chunk, group), run)


def tune_params(params, batch: int, interpret: bool) -> int:
    """Tune every unique CompressedFC geometry found in a param pytree.
    Returns the number of newly tuned cache entries."""
    import jax
    from repro.core import sparse_fc as sfc

    before = len(_CACHE)

    def visit(leaf):
        # no (mode, shape)-level dedupe: same-shape projections can still
        # differ in geometry (rmax varies per weight matrix), and the
        # cache key is the real dedupe — tune_layer returns immediately
        # on a key hit
        if isinstance(leaf, sfc.CompressedFC) and leaf.mode != "dense":
            tune_layer(leaf, batch, interpret)
        return leaf

    jax.tree_util.tree_map(
        visit, params,
        is_leaf=lambda x: isinstance(x, sfc.CompressedFC))
    return len(_CACHE) - before


# --------------------------------------------------- encode-time block_rows
def block_rows_key(shape: Tuple[int, int], mode: str, density: float,
                   interpret: bool) -> Key:
    return ("block_rows", mode, *shape, density,
            "interp" if interpret else "tpu")


def choose_block_rows(w: np.ndarray, mode: str, density: float,
                      batch: int = 2,
                      candidates: Sequence[int] = (64, 128, 256),
                      interpret: Optional[bool] = None) -> int:
    """Encode-time tile search over the row-block height: encode the
    pruned matrix once per candidate and time the fused kernel on each.
    Cached by (shape, mode, density, lowering); only consulted when
    REPRO_TUNE_BLOCK_ROWS=1 since one encoding per candidate is much
    slower than the (mb, bk) search."""
    import jax.numpy as jnp
    from repro.kernels import acsr_spmv as sp

    from repro.kernels.util import interpret_mode
    interpret = interpret_mode(interpret)
    key = block_rows_key(w.shape, mode, density, interpret)
    if get(key) is None:
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(w.shape[1], batch))
                        .astype(np.float32))
        if mode == "aida":
            # time the coded kernel the real decode will run
            nz = w[w != 0]
            cents = np.concatenate(
                [[0.0], np.quantile(nz, np.linspace(0.02, 0.98, 15))]
            ).astype(np.float32) if nz.size else np.zeros(16, np.float32)
            blocked = {br: sp.block_encode_coded(w, cents, block_rows=br)
                       for br in candidates}
        else:
            blocked = {br: sp.block_encode(w, block_rows=br)
                       for br in candidates}

        def run(c):
            return sp.acsr_spmv(blocked[c.tile("block_rows")], x,
                                interpret=interpret)
        autotune(key, [KernelChoice("pallas", (("block_rows", br),))
                       for br in candidates], run)
    return get(key).tile("block_rows")
