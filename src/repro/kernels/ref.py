"""Pure-jnp oracles of the LM stack's Pallas kernels (allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_ref(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True):
    """Oracle for kernels.flash_attention (fp32 softmax)."""
    BH, Sq, dh = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * dh ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
