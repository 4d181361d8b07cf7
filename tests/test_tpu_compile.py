"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU v5e.

Each test lowers one kernel natively (``interpret=False``) at
qwen1.5-0.5b widths (d_model 1024, d_ff 2816, 16 heads of 64, 16-token
pages, a 1024-token table) and compiles it for a described v5e chip, so
a kernel Mosaic would refuse fails here without an attached device.
Nothing runs: results are checked by the interpret-mode tests and on the
chip by ``chip_smoke.py``.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kvstore as kvs
from repro.kernels import acsr_spmv as sp
from repro.kernels import int8_matmul as i8
from repro.kernels import lut_matmul as lm

D_MODEL, D_FF, HEADS, D_HEAD = 1024, 2816, 16, 64
BATCH, PAGE, MAX_LEN, CHUNK = 4, 16, 1024, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, f, *shapes):
    """Compile ``f`` for the described chip; return the optimized HLO."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    return jax.jit(f).lower(*args).compile().as_text()


# (n_out, n_in, rmax): the down projection has the widest rows
@pytest.mark.parametrize("coded", [False, True], ids=["acsr", "aida"])
@pytest.mark.parametrize("n,k,rmax", [(D_FF, D_MODEL, 320),
                                      (D_MODEL, D_FF, 768)],
                         ids=["up", "down"])
def test_acsr_spmv_compiles(one_chip, coded, n, k, rmax):
    nb = n // 128
    shapes = [jax.ShapeDtypeStruct((nb, rmax, 128),
                                   jnp.uint8 if coded else jnp.float32),
              jax.ShapeDtypeStruct((nb, rmax, 128), jnp.int16),
              jax.ShapeDtypeStruct((nb, 128), jnp.int32),
              jax.ShapeDtypeStruct((k, BATCH), jnp.float32)]
    if coded:
        shapes.append(jax.ShapeDtypeStruct((16,), jnp.float32))

    def f(vals, cols, nnz, x, cents=None):
        b = sp.BlockedACSR(vals, cols, nnz, (n, k), 128, -1, cents)
        return sp.acsr_spmv(b, x, interpret=False)
    assert "tpu_custom_call" in _compile(one_chip, f, *shapes)


@pytest.mark.parametrize("kernel", ["lut", "int8"])
def test_dense_shaped_fc_compiles(one_chip, kernel):
    x = jax.ShapeDtypeStruct((BATCH, D_MODEL), jnp.float32)
    if kernel == "lut":
        hlo = _compile(
            one_chip,
            lambda x, c, ce: lm.lut_matmul(x, c, ce, interpret=False),
            x, jax.ShapeDtypeStruct((D_FF, D_MODEL // 2), jnp.uint8),
            jax.ShapeDtypeStruct((16,), jnp.float32))
    else:
        hlo = _compile(
            one_chip,
            lambda x, q, s: i8.int8_matmul(x, q, s, interpret=False),
            x, jax.ShapeDtypeStruct((D_FF, D_MODEL), jnp.int8),
            jax.ShapeDtypeStruct((D_FF, 1), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_lut_product_compiles(one_chip):
    hlo = _compile(
        one_chip,
        lambda x, c, t: lm.lut_product_matmul(x, c, t, interpret=False),
        jax.ShapeDtypeStruct((8, D_MODEL), jnp.uint8),
        jax.ShapeDtypeStruct((D_MODEL, D_MODEL // 2), jnp.uint8),
        jax.ShapeDtypeStruct((16, 16), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("shape", ["decode", "chunk"])
def test_paged_attention_compiles(one_chip, kv_dtype, shape):
    npp = MAX_LEN // PAGE
    pool = jax.eval_shape(lambda: kvs.init_pool(
        1 + BATCH * npp, HEADS, PAGE, D_HEAD, kv_dtype=kv_dtype))
    table = jax.ShapeDtypeStruct((BATCH, npp), jnp.int32)
    win = jax.ShapeDtypeStruct((), jnp.int32)
    if shape == "decode":
        hlo = _compile(
            one_chip,
            lambda q, p, t, c, w: kvs.paged_attention_pallas(
                q, p, t, c, w, pb=2, interpret=False),
            jax.ShapeDtypeStruct((BATCH, HEADS, D_HEAD), jnp.float32),
            pool, table, jax.ShapeDtypeStruct((BATCH,), jnp.int32), win)
    else:
        hlo = _compile(
            one_chip,
            lambda q, p, t, c, w: kvs.paged_attention_pallas_chunk(
                q, p, t, c, w, pb=2, qt=CHUNK, interpret=False),
            jax.ShapeDtypeStruct((BATCH, HEADS, CHUNK, D_HEAD), jnp.float32),
            pool, table, jax.ShapeDtypeStruct((BATCH, CHUNK), jnp.int32),
            win)
    assert "tpu_custom_call" in hlo
