"""Distributed training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2p5_3b --smoke \
        --steps 100 --mesh-data 1 --mesh-model 1

Production posture: build the mesh, derive shardings from the arch's param
specs, auto-resume from the newest valid checkpoint, watchdog stragglers,
checkpoint atomically. On a real cluster each host runs this same entrypoint
under `jax.distributed.initialize` (flags pass through); in this container it
drives the local device set.
"""
from __future__ import annotations

import argparse

import jax

from repro.data.synthetic import SyntheticConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.registry import get_model
from repro.train.optim import OptConfig, select_optimizer
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="checkpoints/launch_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--abort-on-straggler", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    model = get_model(args.arch, smoke=args.smoke)
    print(f"[train] arch={args.arch} params={model.n_params():,} "
          f"mesh=({args.mesh_data},{args.mesh_model})")
    mesh = make_local_mesh(args.mesh_data, args.mesh_model)
    data = SyntheticLM(SyntheticConfig(vocab_size=model.cfg.vocab_size,
                                       batch=args.batch, seq_len=args.seq))
    opt = select_optimizer(
        model.n_params(),
        OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                  total_steps=args.steps))
    tr = Trainer(model, opt, mesh,
                 TrainerConfig(total_steps=args.steps,
                               ckpt_every=args.ckpt_every,
                               ckpt_dir=args.ckpt_dir, log_every=10,
                               n_microbatches=args.microbatches,
                               abort_on_straggler=args.abort_on_straggler,
                               metrics_path=f"{args.ckpt_dir}/metrics.jsonl"))
    params, _, last = tr.fit(data)
    print(f"[train] done: final loss {last:.4f} (ckpts: {args.ckpt_dir})")


if __name__ == "__main__":
    main()
