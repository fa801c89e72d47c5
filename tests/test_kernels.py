"""The LM stack's Pallas kernels vs pure-jnp oracles, swept over
shapes/dtypes (interpret mode: the kernel body executes on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul


# --------------------------------------------------------------- matmul
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128),
                                   (384, 128, 256)])
def test_matmul_sweep(shape, dtype, rng):
    M, K, N = shape
    a = jnp.asarray(rng.standard_normal((M, K)), dtype)
    b = jnp.asarray(rng.standard_normal((K, N)), dtype)
    got = matmul(a, b, bm=128, bn=128, bk=128, interpret=True)
    exp = ref.matmul_ref(a, b)
    tol = 1e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


# ------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cfg", [
    dict(Sq=256, Sk=256, dh=64, causal=True),
    dict(Sq=256, Sk=256, dh=128, causal=True),
    dict(Sq=128, Sk=512, dh=64, causal=False),
    dict(Sq=512, Sk=512, dh=64, causal=True),
])
def test_flash_attention_sweep(cfg, dtype, rng):
    BH = 3
    q = jnp.asarray(rng.standard_normal((BH, cfg["Sq"], cfg["dh"])), dtype)
    k = jnp.asarray(rng.standard_normal((BH, cfg["Sk"], cfg["dh"])), dtype)
    v = jnp.asarray(rng.standard_normal((BH, cfg["Sk"], cfg["dh"])), dtype)
    got = flash_attention(q, k, v, causal=cfg["causal"], bq=128, bk=128,
                          interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=cfg["causal"])
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_streaming_softmax_extremes(rng):
    """Large logit ranges must not overflow the online softmax."""
    q = jnp.asarray(rng.standard_normal((1, 256, 64)) * 30, jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 64)) * 30, jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 64)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, bq=128, bk=128,
                          interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-2, atol=1e-2)
