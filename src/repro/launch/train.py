"""Production training launcher: mesh construction, sharded state init,
fault-tolerant driver. This is the entry point a real TPU job runs; on CPU
it works with small meshes (tests) and is the companion of dryrun.py.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --mesh 2x4 --steps 20 --preset reduced --batch 8 --seq 64
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import all_archs, get_config, get_reduced
from repro.data import DataConfig, TokenDataset
from repro.launch.mesh import axis_sizes, batch_axes, make_mesh
from repro.models import build
from repro.models.layers import Axes
from repro.optim import AdamWConfig, Compressor
from repro.runtime import DriverConfig, TrainDriver
from repro.sharding import named_shardings, param_pspecs
from repro.train import TrainConfig, make_train_step
from repro.train.step import init_train_state


def parse_mesh(spec: str):
    dims = tuple(int(x) for x in spec.split("x"))
    names = {1: ("data",), 2: ("data", "model"),
             3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, names)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b", choices=all_archs())
    ap.add_argument("--preset", default="reduced", choices=["reduced", "full"])
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    args = ap.parse_args()

    mesh = parse_mesh(args.mesh)
    sizes = axis_sizes(mesh)
    cfg = (get_reduced(args.arch) if args.preset == "reduced"
           else get_config(args.arch))
    model = build(cfg)
    axes = Axes(batch=batch_axes(mesh), model="model", fsdp="data",
                sizes=tuple(sizes.items()))

    tcfg = TrainConfig(
        optimizer=AdamWConfig(total_steps=args.steps, mixed_precision=False),
        compressor=Compressor(kind=args.compress),
        microbatches=args.microbatches,
        xent_chunk=64,
    )
    state = init_train_state(model, tcfg, jax.random.PRNGKey(0))
    pspecs = param_pspecs(state["params"], sizes)
    state_specs = {
        "params": pspecs,
        "opt": {"step": P(), "m": pspecs, "v": pspecs,
                **({"master": pspecs} if "master" in state["opt"] else {})},
        "error": jax.tree_util.tree_map(lambda _: P(), state["error"]),
    }
    state_sh = named_shardings(state_specs, mesh)
    state = jax.tree_util.tree_map(jax.device_put, state, state_sh)

    baxes = batch_axes(mesh)
    batch_sh = NamedSharding(mesh, P(baxes, None))

    with jax.set_mesh(mesh):
        step = jax.jit(make_train_step(model, axes, tcfg),
                       in_shardings=(state_sh,
                                     {"tokens": batch_sh, "labels": batch_sh}),
                       donate_argnums=(0,))

        ds = TokenDataset(DataConfig(vocab_size=cfg.vocab_size,
                                     seq_len=args.seq,
                                     global_batch=args.batch))

        def to_device(b):
            return {k: jax.device_put(jnp.asarray(v), batch_sh)
                    for k, v in b.items()}

        driver = TrainDriver(
            DriverConfig(total_steps=args.steps,
                         checkpoint_every=max(args.steps // 4, 1),
                         checkpoint_dir=args.ckpt_dir),
            step, ds, to_device)
        report = driver.run(state, shardings=state_sh)
    print(f"steps={report.steps_run} restarts={report.restarts} "
          f"metrics={report.final_metrics}")


if __name__ == "__main__":
    main()
