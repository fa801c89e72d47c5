"""jit'd public wrappers around the Pallas kernels.

``INTERPRET`` defaults to True on CPU (kernel bodies execute in Python
via the Pallas interpreter — correctness path) and False on real TPU.
Model code calls these wrappers; swapping interpret/compiled is a
deployment flag, not a code change.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import matmul as _mm
from . import ref

INTERPRET = jax.default_backend() != "tpu"


def matmul(a, b, **kw):
    kw.setdefault("interpret", INTERPRET)
    return _mm.matmul(a, b, **kw)


def mha(q, k, v, *, causal=True, bq=256, bk=256, use_kernel=True):
    """(B, S, H, dh) GQA attention via the flash kernel.

    Folds (B, H) into the kernel's leading axis and repeats KV heads.
    Falls back to the jnp oracle when shapes don't tile (e.g. decode).
    """
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    Sk = k.shape[1]
    rep = H // K
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), rep, 1).reshape(B * H, Sk, dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), rep, 1).reshape(B * H, Sk, dh)
    tile_ok = Sq % min(256, Sq) == 0 and Sk % min(256, Sk) == 0 and Sq > 8
    if use_kernel and tile_ok:
        of = _fa.flash_attention(qf, kf, vf, causal=causal, bq=bq, bk=bk,
                                 interpret=INTERPRET)
    else:
        of = ref.flash_attention_ref(qf, kf, vf, causal=causal)
    return of.reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)
