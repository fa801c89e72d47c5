"""Quickstart: the two faces of the framework in ~60 seconds.

1. The paper's soft-GPGPU overlay: assemble a CUDA-style kernel, run it
   on the jitted SIMT interpreter, inspect cycles/energy/variant.
2. The LM stack: train a small model a few steps on the same runtime.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import asm, customize, energy, scheduler
from repro.core.machine import MachineConfig


def overlay_demo():
    print("=== 1. soft-GPGPU overlay (the paper) " + "=" * 30)
    # a SAXPY-ish integer kernel, written like CUDA SASS
    kernel = """
        S2R    r0, srtid           ; r0 = threadIdx
        S2R    r1, srcta           ; r1 = blockIdx
        S2R    r2, srntid          ; r2 = blockDim
        IMAD   r3, r1, r2, r0      ; gid = blockIdx*blockDim + tid
        LDG    r4, [r3+0]          ; x[gid]
        LDG    r5, [r3+64]         ; y[gid]
        MOV    r6, #3
        IMAD   r7, r4, r6, r5      ; 3*x + y
        STG    [r3+128], r7
        EXIT
    """
    code = asm.assemble(kernel, pad_to=96)
    gmem = np.zeros(192, np.int32)
    gmem[0:64] = np.arange(64)
    gmem[64:128] = 1000
    res = scheduler.run_grid(code, (2, 1), (32, 1), gmem)
    out = res.gmem[128:192]
    assert (out == 3 * np.arange(64) + 1000).all()
    print("result ok:", out[:8], "...")
    print(f"cycles(1 SM, 8 SP): {res.sm_cycles(1)}   "
          f"2 SM: {res.sm_cycles(2)}")
    variant = customize.select_variant(code)
    print("smallest catalog variant that runs it:", variant)
    rep = energy.simt_energy(res, MachineConfig())
    print("dynamic energy:", rep)


def lm_demo():
    print("=== 2. LM stack on the same runtime " + "=" * 32)
    from repro import configs
    from repro.data import DataConfig, SyntheticLM
    from repro.launch import mesh as M
    from repro.launch.steps import build_train_step
    from repro.models import api
    from repro.optim import OptConfig, opt_init

    spec = configs.reduced(configs.get("qwen3-0.6b"))
    mesh = M.make_debug_mesh(1)
    opt_cfg = OptConfig(lr=1e-3)
    _, jit_for, _ = build_train_step(spec, mesh, opt_cfg)
    with jax.set_mesh(mesh):
        params = api.init(jax.random.key(0), spec)
        opt = opt_init(params, opt_cfg)
    data = SyntheticLM(DataConfig(vocab=spec.cfg.vocab, seq_len=64,
                                  global_batch=8))
    b0 = data.batch(0)
    step = jit_for(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), b0))
    for s in range(10):
        params, opt, stats = step(params, opt, data.batch(s))
        if s % 3 == 0:
            print(f"step {s}: loss {float(stats['loss']):.4f}")
    print("done — see launch/train.py for checkpoints & fault tolerance")


if __name__ == "__main__":
    overlay_demo()
    lm_demo()
