"""Pure-jnp oracles for the BMU (best-matching-unit) search kernel: the
exact-f32 tier (``bmu_ref``, the bitwise contract) and the bf16 tolerance
tier (``bmu_bf16_ref``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bmu_ref(w: jnp.ndarray, s: jnp.ndarray):
    """w: (N, D) unit weights; s: (B, D) samples.

    Returns (idx (B,) int32, q2 (B,) float32): argmin_j |w_j - s_i|^2 and the
    squared distance (paper Eq. 1, squared — argmin-equivalent).
    """
    w = w.astype(jnp.float32)
    s = s.astype(jnp.float32)
    w2 = jnp.sum(w * w, axis=-1)
    s2 = jnp.sum(s * s, axis=-1)
    cross = jnp.matmul(s, w.T, precision=jax.lax.Precision.HIGHEST)
    q2 = s2[:, None] - 2.0 * cross + w2[None, :]
    idx = jnp.argmin(q2, axis=-1).astype(jnp.int32)
    best = jnp.take_along_axis(q2, idx[:, None], axis=-1)[:, 0]
    return idx, jnp.maximum(best, 0.0)


def bmu_bf16_ref(w: jnp.ndarray, s: jnp.ndarray):
    """bf16 tolerance tier: the cross term runs on bf16-cast inputs with f32
    accumulation (on TPU: half the MXU input traffic), the argmin ranks the
    approximate distances, and the winner's distance is re-computed with one
    exact-f32 gather ("polish") so the returned q2 carries full-precision
    magnitude even when the *ranking* was approximate.

    Contract (tested in ``tests/test_kernels_properties.py``; documented in
    DESIGN.md §11): not bitwise vs ``bmu_ref`` — index agreement and a q2
    ULP bound instead. Outputs keep the exact tier's dtypes (i32 / f32).
    """
    w = w.astype(jnp.float32)
    s = s.astype(jnp.float32)
    w2 = jnp.sum(w * w, axis=-1)
    s2 = jnp.sum(s * s, axis=-1)
    cross = jax.lax.dot_general(
        s.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    q2a = s2[:, None] - 2.0 * cross + w2[None, :]
    idx = jnp.argmin(q2a, axis=-1).astype(jnp.int32)
    dw = w[idx] - s
    return idx, jnp.maximum(jnp.sum(dw * dw, axis=-1), 0.0)
