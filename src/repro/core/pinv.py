"""Iterative Moore-Penrose pseudoinverse (paper §7, eq. (11)).

The quartic Newton-Schulz-type iteration

    Z_{j+1} = 1/4 * Z_j (13 I - A Z_j (15 I - A Z_j (7 I - A Z_j)))

converges to ``A^+`` when the initial guess satisfies
``||A A^+ - A Z_0|| < 1``; the standard safe initializer is
``Z_0 = A^T / (||A||_1 ||A||_inf)`` (as in Nystromformer). Finite iteration
counts under-invert the small-eigenvalue tail, which the spectral-shifting
core exploits as a soft rank truncation (DESIGN.md §2.3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Matmul precision of the c x c core (pinv, shift, U, U @ BV). The core is
# tiny, and the Newton-Schulz iteration amplifies rounding: at the TPU's
# default precision (f32 matmuls rounded to bf16 passes) the core's error
# dominates the attention gradients. On the CPU this is the default anyway.
CORE_PRECISION = jax.lax.Precision.HIGHEST


def iterative_pinv(a: jnp.ndarray, num_iters: int = 6) -> jnp.ndarray:
    """Approximate pseudoinverse of ``a`` (..., c, c) via paper eq. (11)."""
    c = a.shape[-1]
    dtype = jnp.promote_types(a.dtype, jnp.float32)
    a32 = a.astype(dtype)
    eye = jnp.eye(c, dtype=dtype)
    abs_a = jnp.abs(a32)
    # ||A||_1 = max column abs-sum, ||A||_inf = max row abs-sum.
    norm_1 = jnp.max(jnp.sum(abs_a, axis=-2), axis=-1)[..., None, None]
    norm_inf = jnp.max(jnp.sum(abs_a, axis=-1), axis=-1)[..., None, None]
    z0 = jnp.swapaxes(a32, -1, -2) / jnp.maximum(norm_1 * norm_inf, 1e-30)

    def body(_, z):
        az = jnp.matmul(a32, z, precision=CORE_PRECISION)
        inner = 7.0 * eye - az
        inner = 15.0 * eye - jnp.matmul(az, inner, precision=CORE_PRECISION)
        inner = 13.0 * eye - jnp.matmul(az, inner, precision=CORE_PRECISION)
        return 0.25 * jnp.matmul(z, inner, precision=CORE_PRECISION)

    z = jax.lax.fori_loop(0, num_iters, body, z0)
    return z.astype(a.dtype)


def svd_pinv(
    a: jnp.ndarray, rank_tol: float = 1e-4
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Exact truncated pseudoinverse via SVD (CPU oracle path).

    Returns ``(pinv, kept_mask, singular_values)`` where ``kept_mask`` marks
    singular values above ``rank_tol * sigma_max`` (the effective rank used by
    the spectral-shift delta).
    """
    dtype = jnp.promote_types(a.dtype, jnp.float32)
    u, s, vt = jnp.linalg.svd(a.astype(dtype), full_matrices=False)
    cutoff = rank_tol * jnp.max(s, axis=-1, keepdims=True)
    keep = s > cutoff
    s_inv = jnp.where(keep, 1.0 / jnp.where(keep, s, 1.0), 0.0)
    pinv = jnp.einsum("...ji,...j,...kj->...ik", vt, s_inv, u)
    return pinv.astype(a.dtype), keep, s
