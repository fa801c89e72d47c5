"""End-to-end training driver: a ~100M-parameter model for a few hundred
steps, with checkpointing and crash recovery, on whatever devices exist.

Default is a CPU-friendly depth/width reduction of mamba2-130m (~15M
params, seq 128) so the loss curve finishes in minutes on one core; pass
``--full`` on real hardware to train the actual 130M configuration.

    PYTHONPATH=src python examples/train_e2e.py --steps 300
    PYTHONPATH=src python examples/train_e2e.py --full --steps 300
"""
import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro import configs
from repro.ckpt import CheckpointManager
from repro.data import DataConfig, SyntheticLM
from repro.launch import mesh as M
from repro.launch.steps import build_train_step
from repro.models import api
from repro.optim import OptConfig, opt_init


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--full", action="store_true",
                    help="true 130M config (use on real hardware)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_e2e_ckpt")
    args = ap.parse_args()

    spec = configs.get("mamba2-130m")
    if not args.full:
        spec = dataclasses.replace(
            spec, cfg=dataclasses.replace(
                spec.cfg, n_layers=6, d_model=384, vocab=8192, chunk=64))
    n_params = spec.cfg.param_count()
    print(f"[e2e] {spec.name}: {n_params / 1e6:.1f}M params, "
          f"seq={args.seq} batch={args.batch} steps={args.steps}")

    mesh = M.make_debug_mesh(len(jax.devices()))
    opt_cfg = OptConfig(lr=6e-4, warmup=50)
    _, jit_for, (psh, osh) = build_train_step(spec, mesh, opt_cfg)
    with jax.set_mesh(mesh):
        params = api.init(jax.random.key(0), spec)
        opt = opt_init(params, opt_cfg)
        params, opt = jax.device_put((params, opt), (psh, osh))

    data = SyntheticLM(DataConfig(vocab=spec.cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch))
    mgr = CheckpointManager(args.ckpt_dir, every=100, keep=2)
    restored, start = mgr.resume({"p": params, "o": opt})
    if restored is not None:
        params = jax.tree.map(jnp.asarray, restored["p"])
        opt = jax.tree.map(jnp.asarray, restored["o"])
        print(f"[e2e] resumed from step {start}")

    b0 = data.batch(0)
    step = jit_for(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), b0))
    t0, first_loss = time.time(), None
    for s in range(start, args.steps):
        params, opt, stats = step(params, opt, data.batch(s))
        if s % 25 == 0 or s == args.steps - 1:
            loss = float(stats["loss"])
            first_loss = first_loss if first_loss is not None else loss
            tput = args.batch * args.seq * (s - start + 1) / \
                (time.time() - t0)
            print(f"step {s:4d} loss {loss:7.4f} "
                  f"gnorm {float(stats['grad_norm']):6.2f} "
                  f"{tput:8.0f} tok/s", flush=True)
        mgr.maybe_save(s + 1, {"p": params, "o": opt})
    print(f"[e2e] loss {first_loss:.3f} -> {float(stats['loss']):.3f} "
          f"in {time.time() - t0:.0f}s; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
