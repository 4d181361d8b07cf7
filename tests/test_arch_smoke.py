"""Per-architecture smoke tests: reduced config of the same family, one
forward + one train step on CPU, asserting output shapes and no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, cell_supported, get, names, reduced
from repro.data.pipeline import PipelineConfig, make_batch
from repro.models import model as M
from repro.train import trainer

ALL_ARCHS = names()


def smoke_cfg(name):
    cfg = reduced(get(name))
    if cfg.frontend == "vision":
        cfg = dataclasses.replace(cfg, n_img_tokens=8)
    return cfg


def smoke_batch(cfg, b=2, s=32):
    pc = PipelineConfig(seed=0, global_batch=b, seq_len=s)
    return {k: jnp.asarray(v) for k, v in make_batch(cfg, pc, 0).items()}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_no_nan(arch):
    cfg = smoke_cfg(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = smoke_batch(cfg)
    logits, aux = M.forward(cfg, params, batch, remat="none")
    assert logits.shape == (2, 32, cfg.vocab_padded)
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_no_nan(arch):
    cfg = smoke_cfg(arch)
    state = trainer.init_state(cfg, jax.random.PRNGKey(0))
    tc = trainer.TrainConfig(remat="none")
    step = jax.jit(trainer.make_train_step(cfg, tc))
    state, metrics = step(state, smoke_batch(cfg))
    assert float(metrics["loss"]) > 0 and np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually moved
    delta = jax.tree_util.tree_reduce(
        lambda a, x: a + float(jnp.sum(jnp.abs(x))),
        jax.tree.map(jnp.subtract, state.params,
                     trainer.init_state(cfg, jax.random.PRNGKey(0)).params),
        0.0)
    assert delta > 0


@pytest.mark.parametrize("arch", [a for a in ALL_ARCHS
                                  if get(a).has_decode])
def test_decode_matches_forward(arch):
    cfg = smoke_cfg(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    if cfg.frontend == "vision":
        pytest.skip("decode-vs-forward needs pure-text prefix")
    full, _ = M.forward(cfg, params, {"tokens": toks}, remat="none")
    state = M.init_decode_state(cfg, B, S)
    outs = []
    for t in range(S):
        state, lg, *_ = M.decode_step(cfg, params, state, toks[:, t])
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    scale = float(jnp.max(jnp.abs(full))) + 1e-6
    assert float(jnp.max(jnp.abs(dec - full))) / scale < 0.02


def test_cell_matrix_counts():
    """48 cells total (12 archs x 4 shapes); 42 runnable; the 6
    documented skips."""
    total, ok, skips = 0, 0, []
    for a in ALL_ARCHS:
        for s in SHAPES.values():
            total += 1
            good, why = cell_supported(get(a), s)
            if good:
                ok += 1
            else:
                skips.append((a, s.name, why))
    assert total == 48
    assert ok == 42
    skip_set = {(a, s) for a, s, _ in skips}
    assert ("hubert-xlarge", "decode_32k") in skip_set
    assert ("hubert-xlarge", "long_500k") in skip_set
    assert ("llama3-8b", "long_500k") in skip_set
    assert ("qwen1.5-0.5b", "long_500k") in skip_set
    assert ("phi-3-vision-4.2b", "long_500k") in skip_set
    assert ("dbrx-132b", "long_500k") in skip_set


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_full_config_param_specs_cover_params(arch):
    """Sharding specs tree must exactly match the param tree structure
    (checked via eval_shape — no allocation of the full config)."""
    cfg = get(arch)
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    specs = M.param_specs(cfg, {"data": 16, "model": 16})
    jax.tree.map(lambda sh, sp: None, shapes, specs,
                 is_leaf=lambda x: hasattr(x, "shape") or x is None)
    # every spec'd axis must divide the corresponding dim on a 16×16 mesh
    from jax.sharding import PartitionSpec
    flat_sh = jax.tree.leaves(shapes)
    flat_sp = jax.tree.flatten(specs,
                               is_leaf=lambda x: isinstance(x, PartitionSpec))[0]
    sizes = {"data": 16, "model": 16}
    for sh, sp in zip(flat_sh, flat_sp):
        for dim, axis in zip(sh.shape, tuple(sp)):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            need = 1
            for a in axes:
                need *= sizes[a]
            assert dim % need == 0, (arch, sh.shape, tuple(sp))
