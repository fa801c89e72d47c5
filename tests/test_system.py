"""End-to-end system behaviour: training converges, crash recovery is
bit-exact, serving decodes greedily and deterministically."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.ckpt import CheckpointManager
from repro.data import DataConfig, SyntheticLM
from repro.launch import mesh as M
from repro.launch.steps import build_serve_step, build_train_step
from repro.models import api
from repro.optim import OptConfig, opt_init


def _train(spec, steps, ckpt_dir=None, die_at=None, restore=False,
           seed=0, every=5):
    mesh = M.make_debug_mesh(1)
    opt_cfg = OptConfig(lr=1e-3, warmup=10)
    _, jit_for, _ = build_train_step(spec, mesh, opt_cfg, donate=False)
    with jax.set_mesh(mesh):
        params = api.init(jax.random.key(seed), spec)
        opt_state = opt_init(params, opt_cfg)
    data = SyntheticLM(DataConfig(vocab=spec.cfg.vocab, seq_len=32,
                                  global_batch=4, seed=seed))
    start = 0
    mgr = CheckpointManager(ckpt_dir, every=every) if ckpt_dir else None
    if mgr and restore:
        restored, start = mgr.resume({"p": params, "o": opt_state})
        if restored is not None:
            params = jax.tree.map(jnp.asarray, restored["p"])
            opt_state = jax.tree.map(jnp.asarray, restored["o"])
    b0 = data.batch(0)
    step = jit_for(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), b0))
    losses = []
    for s in range(start, steps):
        if die_at is not None and s == die_at:
            return params, losses  # simulate preemption
        params, opt_state, stats = step(params, opt_state, data.batch(s))
        losses.append(float(stats["loss"]))
        if mgr:
            mgr.maybe_save(s + 1, {"p": params, "o": opt_state})
    return params, losses


def test_training_reduces_loss():
    spec = configs.reduced(configs.get("smollm_360m"))
    _, losses = _train(spec, 60)
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 0.1, (first, last)


def test_crash_recovery_bit_exact(tmp_path):
    """Run A: 30 uninterrupted steps.  Run B: die at 17, restart from the
    checkpoint, continue to 30.  Same final parameters, bit for bit —
    checkpoint + stateless data pipeline = deterministic recovery."""
    spec = configs.reduced(configs.get("mamba2_130m"))
    pa, _ = _train(spec, 30, seed=3)
    ck = str(tmp_path / "ck")
    _train(spec, 30, ckpt_dir=ck, die_at=17, seed=3)
    pb, _ = _train(spec, 30, ckpt_dir=ck, restore=True, seed=3)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_greedy_decode_deterministic():
    spec = configs.reduced(configs.get("qwen3_0p6b"))
    mesh = M.make_debug_mesh(1)
    with jax.set_mesh(mesh):
        params = api.init(jax.random.key(0), spec)
        _, jit_for, _ = build_serve_step(spec, mesh, donate=False)
        B, T = 2, 16
        state = api.decode_state(spec, B, T)
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
        step, _ = jit_for(shapes, jax.ShapeDtypeStruct((B, 1), jnp.int32))

        def rollout():
            st = jax.tree.map(jnp.array, state)
            tok = jnp.zeros((B, 1), jnp.int32)
            toks = []
            for i in range(8):
                nxt, st = step(params, st, tok, jnp.asarray(i, jnp.int32))
                tok = nxt[:, None]
                toks.append(np.asarray(nxt))
            return np.stack(toks, 1)

        r1, r2 = rollout(), rollout()
    np.testing.assert_array_equal(r1, r2)
    assert r1.shape == (B, 8)


def test_overlay_plus_lm_coexist():
    """The paper's overlay and the LM stack share one process/runtime:
    run a SIMT kernel and an LM step back-to-back (integration)."""
    from repro.core import scheduler
    from repro.core.programs import ALL
    mod = ALL["transpose"]
    code = mod.build(32)
    g0 = mod.make_gmem(np.random.default_rng(0), 32)
    res = scheduler.run_grid(code, *mod.launch(32), g0)
    np.testing.assert_array_equal(res.gmem[mod.out_slice(32)],
                                  mod.oracle(g0, 32))
    spec = configs.reduced(configs.get("yi_6b"))
    params = api.init(jax.random.key(0), spec)
    loss = api.apply_train(params, spec,
                           {"tokens": jnp.zeros((2, 16), jnp.int32),
                            "labels": jnp.ones((2, 16), jnp.int32)})
    assert bool(jnp.isfinite(loss))
