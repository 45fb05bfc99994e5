"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train \
        --arch smollm-360m --smoke --steps 200 --batch 8 --seq 128 \
        --ckpt-dir /tmp/run0

On a real TPU slice this builds the production mesh and the sharded
train step (launch/steps.py); on CPU it runs single-device with the
same code path.  Fault tolerance (auto-resume, preemption checkpoint,
straggler log) comes from runtime/Trainer.
"""
from __future__ import annotations

import argparse
import logging

import jax

from ..configs import get_config
from ..configs.base import ShapeSpec
from ..data import SyntheticLM
from ..models import get_model, init_params
from ..optim import AdamW, cosine_schedule
from ..parallel.rules import make_plan
from ..runtime import Trainer, TrainerConfig
from .compile_cache import use_compile_cache
from .steps import build_step
from ..core.hw import MeshDescriptor
from .mesh import make_mesh_from_descriptor


def train_mesh(n_dev: int) -> MeshDescriptor:
    """The trainer's (data, model) mesh over the first ``n_dev``
    devices: a model axis of 2 from four devices up, else data only."""
    if n_dev >= 4:
        return MeshDescriptor((n_dev // 2, 2), ("data", "model"))
    return MeshDescriptor((n_dev, 1), ("data", "model"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--opt-bits", type=int, default=32, choices=[8, 32])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--strategy", default="auto")
    ap.add_argument("--data", default="synthetic",
                    help="'synthetic' or a packed-token file path")
    args = ap.parse_args(argv)
    use_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    shape = ShapeSpec("cli_train", args.seq, args.batch, "train")

    desc = train_mesh(len(jax.devices()))
    mesh = make_mesh_from_descriptor(desc)
    plan = make_plan(cfg, shape, desc, args.strategy)
    optimizer = AdamW(lr=cosine_schedule(args.lr, warmup=20,
                                         total=args.steps),
                      state_bits=args.opt_bits)

    with mesh:
        bundle = build_step(cfg, shape, plan, mesh, optimizer=optimizer,
                            impl="auto")
        api = get_model(cfg)
        params = init_params(api.param_defs(cfg), jax.random.PRNGKey(0))
        opt_state = optimizer.init(params)

        if args.data == "synthetic":
            data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                               global_batch=args.batch, seed=0)
        else:
            from ..data import PackedFileDataset
            data = PackedFileDataset(args.data, cfg.vocab, args.seq,
                                     args.batch)

        trainer = Trainer(bundle.fn, data, TrainerConfig(
            total_steps=args.steps, ckpt_every=args.ckpt_every,
            ckpt_dir=args.ckpt_dir, log_every=10))
        params, opt_state, step = trainer.run(params, opt_state)
    print(f"finished at step {step}; "
          f"last loss {trainer.metrics_history[-1]['loss']:.4f}"
          if trainer.metrics_history else "no steps ran")
    return cfg, params


if __name__ == "__main__":
    main()
