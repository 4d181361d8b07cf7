"""Multi-device semantics tests.  These spawn subprocesses that set
--xla_force_host_platform_device_count (the main test process must keep 1
device, per the dry-run isolation rule)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get, reduced
from repro.data.pipeline import PipelineConfig, make_batch
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.train import trainer

cfg = reduced(get("llama3-8b"), n_layers=2, d_model=64, d_ff=128, vocab=256)
mesh = make_host_mesh(n_model=4, n_data=2)
mdict = dict(zip(mesh.axis_names, mesh.devices.shape))

batch_np = make_batch(cfg, PipelineConfig(seed=0, global_batch=4, seq_len=32), 0)
batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
state = trainer.init_state(cfg, jax.random.PRNGKey(0))

# single-device result
tc = trainer.TrainConfig(remat="none")
s1, m1 = jax.jit(trainer.make_train_step(cfg, tc))(state, batch)

# sharded result on the 2x4 mesh
with mesh:
    sspecs = trainer.state_specs(cfg, mdict)
    named = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                         is_leaf=lambda x: isinstance(x, P))
    state_sh = jax.tree.map(lambda x, s: jax.device_put(x, s), state, named)
    bspec = {k: NamedSharding(mesh, P("data", *([None] * (v.ndim - 1))))
             for k, v in batch.items()}
    batch_sh = {k: jax.device_put(v, bspec[k]) for k, v in batch.items()}
    step = jax.jit(trainer.make_train_step(cfg, tc, dp_spec=("data",)),
                   in_shardings=(named, bspec))
    s2, m2 = step(state_sh, batch_sh)

d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                 s1.params, s2.params)
print(json.dumps({
    "loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
    "max_param_diff": max(jax.tree.leaves(d)),
    "n_devices": jax.device_count(),
}))
"""

DECODE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get, reduced
from repro.launch.mesh import make_host_mesh
from repro.models import model as M

cfg = reduced(get("llama3-8b"), n_layers=2, d_model=64, d_ff=128, vocab=256)
mesh = make_host_mesh(n_model=4, n_data=2)
params = M.init_params(cfg, jax.random.PRNGKey(0))
B, S = 4, 16
toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)

# single-device decode
st = M.init_decode_state(cfg, B, S)
outs = []
for t in range(S):
    st, lg = M.decode_step(cfg, params, st, toks[:, t])
    outs.append(lg)
ref = jnp.stack(outs, 1)

# sharded decode: KV cache sequence-sharded over the model axis
with mesh:
    sspecs = M.state_specs(cfg, B, dp_ok=True, dpax=("data",))
    named_st = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                            is_leaf=lambda x: isinstance(x, P))
    pspecs = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          M.param_specs(cfg, dict(data=2, model=4)),
                          is_leaf=lambda x: isinstance(x, P))
    params_sh = jax.tree.map(jax.device_put, params, pspecs)
    st2 = jax.tree.map(jax.device_put, M.init_decode_state(cfg, B, S),
                       named_st)
    step = jax.jit(lambda p, s, t: M.decode_step(cfg, p, s, t),
                   in_shardings=(pspecs, named_st,
                                 NamedSharding(mesh, P("data"))))
    outs2 = []
    for t in range(S):
        st2, lg = step(params_sh, st2,
                       jax.device_put(toks[:, t],
                                      NamedSharding(mesh, P("data"))))
        outs2.append(lg)
got = jnp.stack(outs2, 1)
print(json.dumps({
    "max_diff": float(jnp.max(jnp.abs(got - ref))),
    "scale": float(jnp.max(jnp.abs(ref))),
}))
"""


def run_sub(script):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    r = run_sub(SCRIPT)
    assert r["n_devices"] == 8
    assert abs(r["loss1"] - r["loss2"]) < 5e-3
    assert r["max_param_diff"] < 5e-3


def test_seq_sharded_decode_matches_single_device():
    r = run_sub(DECODE_SCRIPT)
    assert r["max_diff"] / (r["scale"] + 1e-9) < 0.02
