"""Production training launcher.

Multi-host: every host runs this same script; `jax.distributed.initialize`
wires the pods together (env: COORDINATOR_ADDR, NUM_PROCESSES, PROCESS_ID).
The mesh/shardings are identical to the dry-run's — what compiled there
runs here.  Single host (no env): degrades to local devices for smoke use.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
      --steps 100 --global-batch 256 --seq 4096 [--multi-pod] \
      [--microbatches 4] [--grad-compression int8] [--ckpt-dir /ckpts]
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.ckpt import CheckpointManager
from repro.configs import get
from repro.data.pipeline import DataIterator, PipelineConfig
from repro.launch import mesh as mesh_lib
from repro.optim.adamw import AdamWConfig
from repro.runtime.fault_tolerance import RestartLoop, StragglerDetector
from repro.train import trainer


def maybe_init_distributed():
    if "COORDINATOR_ADDR" in os.environ:
        jax.distributed.initialize(
            coordinator_address=os.environ["COORDINATOR_ADDR"],
            num_processes=int(os.environ["NUM_PROCESSES"]),
            process_id=int(os.environ["PROCESS_ID"]))
        return True
    return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--grad-compression", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--local-mesh", action="store_true",
                    help="use whatever local devices exist (smoke mode)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    distributed = maybe_init_distributed()
    cfg = get(args.arch)
    if args.local_mesh or (not distributed
                           and jax.device_count() < 256):
        n = jax.device_count()
        mesh = mesh_lib.make_mesh((1, n), ("data", "model"))
        print(f"[launch] local mesh 1x{n} (smoke mode)")
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)
        print(f"[launch] production mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    mdict = mesh_lib.mesh_shape_dict(mesh)
    dpax = mesh_lib.dp_axes(mesh)

    tc = trainer.TrainConfig(
        remat=args.remat, microbatches=args.microbatches,
        grad_compression=args.grad_compression,
        opt=AdamWConfig(lr=args.lr, total_steps=args.steps))
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    straggler = StragglerDetector()

    with mesh:
        sspecs = trainer.state_specs(cfg, mdict)
        named = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                             is_leaf=lambda x: isinstance(x, P))
        step_fn = jax.jit(
            trainer.make_train_step(cfg, tc, dp_spec=dpax),
            in_shardings=(named, None), donate_argnums=(0,))

        start = 0
        if mgr is not None and mgr.latest_step() is not None:
            template = jax.eval_shape(
                lambda k: trainer.init_state(cfg, k), jax.random.PRNGKey(0))
            host_template = jax.tree.map(
                lambda s: np.zeros(s.shape, s.dtype), template)
            state, extra = mgr.restore(host_template, shardings=named)
            start = extra["data"]["step"]
            print(f"[launch] restored step {start}")
        else:
            init = jax.jit(lambda k: trainer.init_state(cfg, k),
                           out_shardings=named)
            state = init(jax.random.PRNGKey(0))

        data = DataIterator(cfg, PipelineConfig(
            seed=0, global_batch=args.global_batch, seq_len=args.seq),
            start_step=start)

        def run_once(_resume):
            nonlocal state, data
            for i in range(start, args.steps):
                t0 = time.perf_counter()
                batch = {k: jnp.asarray(v) for k, v in next(data).items()}
                state, metrics = step_fn(state, batch)
                dt = time.perf_counter() - t0
                if straggler.record(dt):
                    print(f"[ft] straggler step ({dt:.2f}s)")
                if i % 10 == 0:
                    print(f"step {i} loss={float(metrics['loss']):.4f} "
                          f"({dt*1e3:.0f} ms)")
                if mgr and (i + 1) % args.ckpt_every == 0:
                    mgr.save(i + 1, state, extra={"data": data.state()})

        if mgr is not None:
            RestartLoop(mgr).supervise(run_once)
            mgr.wait()
        else:
            run_once(None)


if __name__ == "__main__":
    main()
