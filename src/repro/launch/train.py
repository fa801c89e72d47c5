"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 200 --batch 8 --seq 256 --reduced --ckpt-dir /tmp/ckpt

Features exercised here (small-scale versions of the fleet design):
  * jit train step with full param/opt/batch shardings on a local mesh;
  * deterministic synthetic data (stateless by (seed, step, shard));
  * checkpoint every N steps, atomic commit, ``--restore auto`` resume;
  * simulated preemption (``--die-at``) to demonstrate crash recovery.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.ckpt import CheckpointManager
from repro.data import DataConfig, SyntheticLM
from repro.models import api
from repro.optim import OptConfig, opt_init
from repro.launch import mesh as M
from repro.launch.steps import build_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config of the same family")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--restore", default=None, choices=[None, "auto"])
    ap.add_argument("--die-at", type=int, default=None,
                    help="simulate a node failure at this step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    spec = configs.get(args.arch)
    if args.reduced:
        spec = configs.reduced(spec)
    if spec.family in ("vlm", "audio"):
        raise SystemExit("use examples/multimodal_train.py for vlm/audio")

    n_dev = len(jax.devices())
    mesh = M.make_debug_mesh(n_dev)
    opt_cfg = OptConfig(lr=args.lr)
    _, jit_for, (psh, osh) = build_train_step(spec, mesh, opt_cfg)

    key = jax.random.key(args.seed)
    with jax.set_mesh(mesh):
        params = api.init(key, spec)
        opt_state = opt_init(params, opt_cfg)
        params, opt_state = jax.device_put((params, opt_state), (psh, osh))

    data = SyntheticLM(DataConfig(vocab=_vocab(spec), seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))
    start = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every)
        if args.restore == "auto":
            restored, start = mgr.resume({"params": params,
                                          "opt": opt_state})
            if restored is not None:
                params = jax.tree.map(jnp.asarray, restored["params"])
                opt_state = jax.tree.map(jnp.asarray, restored["opt"])
                print(f"[restore] resumed from step {start}")

    batch0 = data.batch(0)
    step_fn = jit_for(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch0))

    t0 = time.time()
    for step in range(start, args.steps):
        if args.die_at is not None and step == args.die_at:
            print(f"[failure-sim] dying at step {step} (restart with "
                  f"--restore auto)")
            raise SystemExit(42)
        batch = data.batch(step)
        params, opt_state, stats = step_fn(params, opt_state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(stats["loss"])
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(stats['grad_norm']):7.3f} "
                  f"({(time.time() - t0):6.1f}s)", flush=True)
        if mgr:
            mgr.maybe_save(step + 1, {"params": params, "opt": opt_state})
    print(f"[done] {args.steps - start} steps in {time.time() - t0:.1f}s")
    return params


def _vocab(spec):
    cfg = spec.cfg
    return cfg.lm.vocab if spec.family == "vlm" else cfg.vocab


if __name__ == "__main__":
    main()
