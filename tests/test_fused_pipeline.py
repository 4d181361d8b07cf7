"""Fused multi-block decode pipeline: kernels vs dense references.

Covers the rearchitected compressed hot path end to end:
  * fused ACSR / AIDA kernel vs ``dense_equivalent`` across shapes, batch
    widths, densities and (mb, bk) tilings — including rows that are not a
    multiple of the 128-lane block and K-tiles smaller than K
  * the Pallas int8 kernel vs the XLA reference, odd shapes included
  * lut_matmul shape padding (no more divisibility asserts)
  * bias + activation epilogue fusion on every mode
  * the per-layer autotuner: cache behavior, snapshot, ops dispatch

Property-based sweeps additionally run when `hypothesis` is installed.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sparse_fc as sfc
from repro.core.quant import int8_matmul_ref, quantize_int
from repro.kernels import ops, ref, tune
from repro.kernels.acsr_spmv import (BlockedACSR, acsr_spmv, block_encode,
                                     block_encode_coded)
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.lut_matmul import lut_matmul


def sparse(rng, n, k, density):
    return (rng.normal(size=(n, k)) * (rng.random((n, k)) < density)
            ).astype(np.float32)


# -------------------------------------------------- fused ACSR pipeline
@pytest.mark.parametrize("n,k,density,bsz,mb,bk", [
    (300, 512, 0.10, 0, 1, 512),     # matvec, 3 blocks (300 = 2*128+44)
    (300, 512, 0.10, 4, 2, 128),     # K-tiled, fused pairs of blocks
    (257, 128, 0.05, 2, 4, 128),     # mb > nblocks clamps
    (128, 256, 0.50, 3, 1, 96),      # bk not a divisor of K
    (64, 48, 0.30, 2, 1, 48),        # sub-block matrix
    (1, 1, 1.00, 0, 8, 512),         # degenerate
])
def test_fused_acsr_matches_dense(rng, n, k, density, bsz, mb, bk):
    w = sparse(rng, n, k, density)
    x = rng.normal(size=(k,) if bsz == 0 else (k, bsz)).astype(np.float32)
    b = block_encode(w, block_rows=128)
    out = np.asarray(acsr_spmv(b, jnp.asarray(x), mb=mb, bk=min(bk, k),
                               interpret=True))
    np.testing.assert_allclose(out, w @ x, rtol=2e-4, atol=2e-4)


def test_fused_acsr_matches_blocked_ref(rng):
    """The Pallas kernel agrees with the slot-schedule jnp oracle."""
    w = sparse(rng, 200, 160, 0.2)
    x = jnp.asarray(rng.normal(size=(160, 3)).astype(np.float32))
    b = block_encode(w, block_rows=128)
    got = np.asarray(acsr_spmv(b, x, interpret=True))
    want = np.asarray(ref.blocked_acsr_spmv_ref(
        b.values, b.col_idx, b.row_nnz, x, b.block_rows))[:200]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fused_acsr_coded_nonzero_centroid0(rng):
    """Padding slots are masked by row_nnz, so correctness cannot depend
    on the codebook containing an exact zero."""
    w = sparse(rng, 140, 96, 0.15)
    nz = w[w != 0]
    cents = np.quantile(nz, np.linspace(0.02, 0.98, 16)).astype(np.float32)
    assert not (cents == 0).any()
    b = block_encode_coded(w, cents, block_rows=128)
    x = rng.normal(size=(96, 2)).astype(np.float32)
    wq = cents[np.abs(w[..., None] - cents).argmin(-1)] * (w != 0)
    out = np.asarray(acsr_spmv(b, jnp.asarray(x), interpret=True))
    np.testing.assert_allclose(out, wq @ x, rtol=2e-4, atol=2e-4)


def test_fused_epilogue_bias_activation(rng):
    w = sparse(rng, 130, 64, 0.3)
    x = rng.normal(size=(64, 2)).astype(np.float32)
    bias = rng.normal(size=(130,)).astype(np.float32)
    b = block_encode(w, block_rows=128)
    for act, f in [("relu", lambda y: np.maximum(y, 0.0)),
                   ("silu", lambda y: y / (1 + np.exp(-y))),
                   ("gelu", None), (None, lambda y: y)]:
        out = np.asarray(acsr_spmv(b, jnp.asarray(x),
                                   bias=jnp.asarray(bias), activation=act,
                                   bk=32, interpret=True))
        want = w @ x + bias[:, None]
        if act == "gelu":
            want = np.asarray(jax.nn.gelu(want, approximate=True))
        else:
            want = f(want)
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)


def test_block_encode_vectorized_layout(rng):
    """Slot schedule invariants: lane = row % block_rows, slots dense from
    0, row_nnz = true per-row population."""
    w = sparse(rng, 70, 40, 0.25)
    b = block_encode(w, block_rows=32)
    assert b.nblocks == 3 and b.values.shape[2] == 32
    counts = (w != 0).sum(axis=1)
    got = np.asarray(b.row_nnz).reshape(-1)[:70]
    np.testing.assert_array_equal(got, counts)
    assert np.asarray(b.row_nnz).reshape(-1)[70:].sum() == 0
    # decode via dense_equivalent round-trips exactly
    layer = sfc.CompressedFC("acsr", (70, 40), blocked=b)
    np.testing.assert_array_equal(sfc.dense_equivalent(layer), w)


def test_block_encode_imbalanced_rows(rng):
    """A single dense row sets rmax but stays correct (EIE pathology)."""
    w = sparse(rng, 90, 64, 0.05)
    w[17] = rng.normal(size=64).astype(np.float32)  # fully dense row
    b = block_encode(w, block_rows=128)
    assert b.rmax >= 64
    x = rng.normal(size=(64,)).astype(np.float32)
    out = np.asarray(acsr_spmv(b, jnp.asarray(x), interpret=True))
    np.testing.assert_allclose(out, w @ x, rtol=2e-4, atol=2e-4)


# --------------------------------------------------------- int8 kernel
@pytest.mark.parametrize("b,n,k", [(8, 128, 256), (3, 130, 100),
                                   (1, 64, 512), (5, 257, 33)])
def test_int8_kernel_matches_ref(rng, b, n, k):
    w = rng.normal(size=(n, k)).astype(np.float32)
    qt = quantize_int(jnp.asarray(w), bits=8, axis=0)
    x = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    out = int8_matmul(x, qt.q, qt.scale, bm=8, bn=128, bk=64,
                      interpret=True)
    want = int8_matmul_ref(x, qt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_int8_kernel_fused_epilogue(rng):
    w = rng.normal(size=(96, 64)).astype(np.float32)
    qt = quantize_int(jnp.asarray(w), bits=8, axis=0)
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(96,)).astype(np.float32))
    out = int8_matmul(x, qt.q, qt.scale, bias=bias, activation="relu",
                      interpret=True)
    want = np.maximum(np.asarray(int8_matmul_ref(x, qt))
                      + np.asarray(bias)[None, :], 0.0)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------ lut padding
@pytest.mark.parametrize("b,n,k", [(3, 100, 130), (1, 128, 256),
                                   (9, 65, 514)])
def test_lut_matmul_odd_shapes(rng, b, n, k):
    k += k % 2  # packed codes need even K
    cents = jnp.asarray(np.sort(rng.normal(size=16)).astype(np.float32))
    codes = rng.integers(0, 16, size=(n, k)).astype(np.uint8)
    packed = jnp.asarray(codes[:, 0::2] | (codes[:, 1::2] << 4))
    x = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    out = lut_matmul(x, packed, cents, bm=8, bn=128, bk=256,
                     interpret=True)
    want = ref.lut_matmul_ref(x, packed, cents)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-3)


# ------------------------------------------------------------ autotuner
def test_tuner_cache_and_dispatch(rng):
    tune.clear()
    w = sparse(rng, 96, 80, 0.2)
    layer = sfc.compress(w, mode="acsr", density=0.2)
    c1 = tune.tune_layer(layer, batch=2, interpret=True)
    assert c1.impl == "pallas" and np.isfinite(c1.us)
    assert c1.tile("mb") is not None and c1.tile("bk") is not None
    # second call is a cache hit (same object, no re-timing)
    assert tune.tune_layer(layer, batch=2, interpret=True) is c1
    # snapshot is JSON-able and keyed by geometry
    snap = tune.snapshot()
    import json
    json.dumps(snap)
    assert any(key.startswith("acsr/") for key in snap)
    # ops dispatch picks the tuned tiles up and still matches dense
    x = jnp.asarray(rng.normal(size=(80, 2)).astype(np.float32))
    got = np.asarray(ops.acsr_spmv(layer.blocked, x, interpret=True))
    np.testing.assert_allclose(
        got, sfc.dense_equivalent(layer) @ np.asarray(x),
        rtol=2e-4, atol=2e-4)
    tune.clear()
    assert tune.snapshot() == {}


def test_tuner_stacked_params(rng):
    """tune_params finds stacked CompressedFC leaves inside model params."""
    tune.clear()
    per = [sfc.compress(sparse(rng, 64, 48, 0.3), mode="aida", density=0.3)
           for _ in range(2)]
    from repro.api.compress import _stack_compressed
    stacked = _stack_compressed(per)
    n_new = tune.tune_params({"layers": {"blk": {"wq": stacked}}},
                             batch=2, interpret=True)
    assert n_new == 1
    assert any(key.startswith("aida/") for key in tune.snapshot())
    tune.clear()


def test_tuner_reports_refused_native_candidates():
    """Under native lowering a candidate the compiler refuses is kept with
    its error in snapshot(); a key whose Pallas candidates all fail
    raises instead of caching a marker (or quietly serving XLA)."""
    tune.clear()
    cands = tune.int8_candidates(256, 512)
    refused = cands[1].label

    def run(c):
        if c.label == refused:
            raise RuntimeError("Mosaic failed to compile TPU kernel\ndetail")
        return jnp.zeros(())
    best = tune.autotune(tune.int8_key(256, 512, 4, interpret=False),
                         cands, run, reps=1, inner=1)
    assert best.label != refused
    entry = tune.snapshot()["int8/256/512/4/tpu"]
    assert entry["failed"] == {
        refused: "RuntimeError: Mosaic failed to compile TPU kernel"}

    def run_xla_only(c):
        if c.impl == "pallas":
            raise RuntimeError("refused")
        return jnp.zeros(())
    key = tune.lut_key(256, 512, 4, interpret=False)
    with pytest.raises(tune.TuneError, match="no Pallas candidate"):
        tune.autotune(key, tune.lut_candidates(256, 512), run_xla_only,
                      reps=1, inner=1)
    assert tune.get(key) is None
    tune.clear()


# ---------------------------------------------- mode x dense_equivalent
@pytest.mark.parametrize("mode", ["int8", "codebook4", "acsr", "aida"])
def test_apply_fc_fused_epilogue_all_modes(rng, mode):
    n, k = (128, 256) if mode == "codebook4" else (130, 100)
    w = rng.normal(size=(n, k)).astype(np.float32)
    layer = sfc.compress(w, mode=mode, density=0.2)
    x = jnp.asarray(rng.normal(size=(3, k)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    got = np.asarray(sfc.apply_fc(layer, x, bias=bias, activation="silu"))
    pre = np.asarray(x) @ sfc.dense_equivalent(layer).T \
        + np.asarray(bias)[None, :]
    want = pre / (1 + np.exp(-pre))
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


# ------------------------------------------------- bf16 values variant
def test_bf16_acsr_values_variant(rng):
    """CompressionSpec(dtype='bf16'): bf16-stored nonzeros keep the fused
    kernel within bf16 tolerance of the ORIGINAL pruned weights and beat
    the f32 variant on bytes (the ROADMAP 'win on bytes' item)."""
    w = sparse(rng, 300, 256, 0.25)
    f32 = sfc.compress(w, mode="acsr", density=1.0)     # keep all nnz
    b16 = sfc.compress(w, mode="acsr", density=1.0, dtype="bf16")
    assert b16.blocked.values.dtype == jnp.bfloat16
    x = rng.normal(size=(256, 3)).astype(np.float32)
    y16 = np.asarray(sfc.apply_fc(b16, jnp.asarray(x).T)).T
    # matches its own dense_equivalent tightly ...
    np.testing.assert_allclose(y16, sfc.dense_equivalent(b16) @ x,
                               rtol=2e-4, atol=2e-4)
    # ... and the f32 kernel within accumulated bf16 weight rounding
    # (~0.4% per nonzero, K=256 random-sign accumulation)
    y32 = np.asarray(sfc.apply_fc(f32, jnp.asarray(x).T)).T
    np.testing.assert_allclose(y16, y32, rtol=2e-2, atol=1e-1)

    def nbytes(c):
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(c))
    assert nbytes(b16) < nbytes(f32)


def test_bf16_acsr_through_engine(rng):
    """Engine-level: dtype='bf16' halves acsr value bytes (ratio now
    beats the bf16-serving baseline at 25% density) and still serves."""
    from repro.api import CompressionSpec, Engine, Request
    from repro.configs import get, reduced
    cfg = reduced(get("llama3-8b"), n_layers=2, d_model=64, d_ff=128,
                  vocab=256)
    eng = Engine(cfg)
    e32 = Engine(cfg, params=eng.params).compress(
        CompressionSpec(mode="acsr", density=0.25, block_rows=64),
        verbose=None)
    e16 = Engine(cfg, params=eng.params).compress(
        CompressionSpec(mode="acsr", density=0.25, dtype="bf16",
                        block_rows=64), verbose=None)
    assert e16.stats["ratio"] > e32.stats["ratio"]
    assert e16.stats["ratio"] > 1.0      # finally beats the bf16 baseline
    res = e16.serve([Request(prompt=[1, 2, 3], max_new=6, rid=0)],
                    batch_slots=1, max_len=16)
    assert len(res[0].tokens) == 6


def test_compression_spec_rejects_bad_dtype():
    from repro.api import CompressionSpec
    with pytest.raises(ValueError):
        CompressionSpec(dtype="fp4")


# ----------------------------------------------------- property sweeps
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover - optional dependency
    HAVE_HYP = False

if HAVE_HYP:
    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(1, 200), k=st.integers(1, 160),
           density=st.floats(0.0, 1.0), bsz=st.integers(0, 3),
           mb=st.sampled_from([1, 2, 4]),
           seed=st.integers(0, 99))
    def test_prop_fused_acsr(n, k, density, bsz, mb, seed):
        rng = np.random.default_rng(seed)
        w = sparse(rng, n, k, density)
        x = rng.normal(size=(k,) if bsz == 0 else (k, bsz)
                       ).astype(np.float32)
        layer = sfc.CompressedFC("acsr", (n, k),
                                 blocked=block_encode(w, block_rows=64))
        out = np.asarray(acsr_spmv(layer.blocked, jnp.asarray(x), mb=mb,
                                   bk=min(64, k), interpret=True))
        np.testing.assert_allclose(out, sfc.dense_equivalent(layer) @ x,
                                   rtol=2e-4, atol=2e-4)

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(2, 120), k=st.integers(2, 120),
           density=st.floats(0.05, 0.8), seed=st.integers(0, 99))
    def test_prop_fused_aida(n, k, density, seed):
        rng = np.random.default_rng(seed)
        w = sparse(rng, n, k, density)
        if not (w != 0).any():
            w[0, 0] = 1.0
        layer = sfc.compress(w, mode="aida", density=min(0.9, density),
                             kmeans_iters=4)
        x = rng.normal(size=(k, 2)).astype(np.float32)
        out = np.asarray(sfc.apply_fc(layer, jnp.asarray(x).T)).T
        np.testing.assert_allclose(out, sfc.dense_equivalent(layer) @ x,
                                   rtol=3e-4, atol=3e-4)

    @settings(max_examples=10, deadline=None)
    @given(b=st.integers(1, 9), n=st.integers(1, 140),
           k=st.integers(1, 140), seed=st.integers(0, 99))
    def test_prop_int8_kernel(b, n, k, seed):
        rng = np.random.default_rng(seed)
        w = rng.normal(size=(n, k)).astype(np.float32)
        qt = quantize_int(jnp.asarray(w), bits=8, axis=0)
        x = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
        out = int8_matmul(x, qt.q, qt.scale, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(int8_matmul_ref(x, qt)),
                                   rtol=2e-4, atol=2e-4)
