"""Batched serving driver: prefill + token-by-token decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --batch 4 --prompt-len 32 --gen 32

Decode uses the donated, sharded decode-state (KV caches / SSD states)
and one jitted single-token step — the ``serve_step`` that the decode
dry-run cells lower for the production mesh.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import api
from repro.launch import mesh as M
from repro.launch.steps import build_serve_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    spec = configs.get(args.arch)
    if args.reduced:
        spec = configs.reduced(spec)
    mesh = M.make_debug_mesh(len(jax.devices()))
    max_seq = args.prompt_len + args.gen

    with jax.set_mesh(mesh):
        params = api.init(jax.random.key(args.seed), spec)
        state = api.decode_state(spec, args.batch, max_seq)
        _, jit_for, _ = build_serve_step(spec, mesh, donate=True)
        tok_shape = jax.ShapeDtypeStruct((args.batch, 1), jnp.int32)
        state_shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
        step_fn, _ = jit_for(state_shapes, tok_shape)

        vocab = spec.cfg.lm.vocab if spec.family == "vlm" else spec.cfg.vocab
        rng = np.random.default_rng(args.seed)
        prompt = rng.integers(0, vocab, (args.batch, args.prompt_len))

        # prefill token-by-token (simple; a chunked-prefill path is the
        # prefill_32k dry-run cell)
        t0 = time.time()
        tok = None
        for i in range(args.prompt_len):
            tok, state = step_fn(params,
                                 state, jnp.asarray(prompt[:, i:i + 1],
                                                    jnp.int32),
                                 jnp.asarray(i, jnp.int32))
        prefill_t = time.time() - t0

        out = []
        t0 = time.time()
        for i in range(args.gen):
            tok, state = step_fn(params, state, tok[:, None],
                                 jnp.asarray(args.prompt_len + i,
                                             jnp.int32))
            out.append(np.asarray(tok))
        decode_t = time.time() - t0

    gen = np.stack(out, 1)
    print(f"[serve] batch={args.batch} prefill={args.prompt_len}tok "
          f"({prefill_t:.2f}s) decode={args.gen}tok ({decode_t:.2f}s, "
          f"{args.gen * args.batch / max(decode_t, 1e-9):.1f} tok/s)")
    print("first sequences:", gen[:2, :12].tolist())
    assert np.isfinite(gen).all()
    return gen


if __name__ == "__main__":
    main()
