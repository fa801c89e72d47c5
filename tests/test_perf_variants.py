"""The §Perf configuration variants must be *numerically equivalent*
to the baseline — sharding profiles and chunked algorithms change cost,
never semantics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import mesh as M
from repro.launch.steps import build_train_step
from repro.models import api, layers as L, transformer
from repro.optim import OptConfig, opt_init


def _loss_for(spec, profile):
    mesh = M.make_debug_mesh(1)
    opt_cfg = OptConfig(lr=0.0, weight_decay=0.0)  # lr 0: loss only
    _, jit_for, _ = build_train_step(spec, mesh, opt_cfg, donate=False,
                                     profile=profile)
    with jax.set_mesh(mesh):
        params = api.init(jax.random.key(0), spec)
        opt = opt_init(params, opt_cfg)
        batch = {"tokens": jnp.arange(2 * 32, dtype=jnp.int32)
                 .reshape(2, 32) % spec.cfg.vocab,
                 "labels": jnp.ones((2, 32), jnp.int32)}
        step = jit_for(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch))
        _, _, stats = step(params, opt, batch)
    return float(stats["loss"])


def test_seq_profile_matches_tp_profile():
    spec = configs.reduced(configs.get("qwen3_0p6b"))
    l_tp = _loss_for(spec, "tp")
    l_seq = _loss_for(spec, "seq")
    assert abs(l_tp - l_seq) < 5e-2, (l_tp, l_seq)


def test_loss_chunk_matches_unchunked():
    spec = configs.reduced(configs.get("smollm_360m"))
    cfg = spec.cfg
    params = transformer.init(jax.random.key(1), cfg)
    toks = jax.random.randint(jax.random.key(2), (2, 32), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.key(3), (2, 32), 0, cfg.vocab)
    l0 = transformer.loss(params, cfg, toks, labels)
    cfg_c = dataclasses.replace(cfg, loss_chunk=8)
    l1 = transformer.loss(params, cfg_c, toks, labels)
    np.testing.assert_allclose(float(l0), float(l1), rtol=2e-2)


def test_remat_variants_same_gradients():
    spec = configs.reduced(configs.get("yi_6b"))
    toks = jax.random.randint(jax.random.key(4), (2, 16), 0,
                              spec.cfg.vocab)
    batch = {"tokens": toks, "labels": toks}

    def grads_for(remat):
        s2 = dataclasses.replace(
            spec, cfg=dataclasses.replace(spec.cfg, remat=remat))
        params = api.init(jax.random.key(5), s2)
        return jax.grad(lambda p: api.apply_train(p, s2, batch))(params)

    g1 = grads_for("dots")
    g2 = grads_for("full")
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-3)


def test_chunked_attention_gradients_match_reference():
    q = jax.random.normal(jax.random.key(6), (1, 64, 4, 16))
    k = jax.random.normal(jax.random.key(7), (1, 64, 2, 16))
    v = jax.random.normal(jax.random.key(8), (1, 64, 2, 16))

    def f_ref(q):
        return (L.causal_attention(q, k, v) ** 2).sum()

    def f_chunk(q):
        return (L.chunked_attention(q, k, v, q_chunk=16) ** 2).sum()

    g1 = jax.grad(f_ref)(q)
    g2 = jax.grad(f_chunk)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("dispatch", ["onehot", "sort", "scatter"])
def test_moe_dispatch_variants_agree(dispatch):
    from repro.models import moe
    cfg = moe.MoEConfig(n_experts=4, top_k=2, d_model=16, d_ff=32,
                        capacity_factor=8.0, group_size=32,
                        dispatch=dispatch)
    p = moe.moe_init(jax.random.key(9), cfg)
    x = jax.random.normal(jax.random.key(10), (2, 32, 16), jnp.float32)
    base = moe.moe_apply_onehot(p, cfg, x)
    got = moe.moe_apply(p, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=3e-2, atol=3e-2)


def test_hlo_analyzer_scope_and_bf16_fields():
    from repro.launch import hloanalysis as H
    hlo = """
HloModule t

ENTRY %main (a: bf16[64,64]) -> f32[64,64] {
  %a = bf16[64,64]{1,0} parameter(0)
  %c = f32[64,64]{1,0} convert(%a)
  %ar = f32[64,64]{1,0} all-reduce(%c), to_apply=%s
  %d = f32[64,64]{1,0} dot(%ar, %ar), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/flashable_attn/dot"}
  ROOT %r = f32[64,64]{1,0} add(%d, %ar)
}
"""
    cost = H.analyze(hlo)
    assert cost.collective_bytes == 64 * 64 * 4
    assert cost.collective_bytes_bf16 == 64 * 64 * 2  # f32 normalized
    assert cost.scope_bytes > 0                       # tagged dot counted
    assert cost.flops >= 2 * 64 ** 3
