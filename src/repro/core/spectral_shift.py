"""Modified Spectral Shifting core (paper §4).

Given the landmark core ``A_s = L(Q~ K~^T / sqrt(d))`` (c x c), computes the
closed-form solution of paper eq. (3):

    delta_ss = ( tr(A_s) - tr(A_s^+ A_s^2) ) / ( c - rank(A_s) )
    U_ss     = A_s^+ - delta_ss (A_s^2)^+  =  A_s^+ (I - delta_ss A_s^+)

Two numerical paths (DESIGN.md §2.3):

* ``method="svd"`` — exact truncated pinv; rank = #(sigma > rank_tol*sigma_max),
  delta = mean of the *discarded* tail spectrum. This is Wang et al. (2016)'s
  truncated SS model and the CPU oracle.
* ``method="iterative"`` — paper eq. (11) pinv with finite iterations; the
  under-inverted tail acts as a soft truncation. Soft rank = tr(A Z*), the
  delta numerator/denominator are trace expressions of Z*. TPU fast path.

For a Lemma-1 spectrum (top-k + flat tail at theta) both paths give
delta -> theta, recovering the paper's exact-reconstruction regime.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.pinv import CORE_PRECISION, iterative_pinv, svd_pinv


class SSCore(NamedTuple):
    """Spectral-shift factors: ``S ~= F @ u @ B + delta * I_n``."""

    u: jnp.ndarray      # (..., c, c)  U_ss = Z (I - delta Z)
    delta: jnp.ndarray  # (..., 1, 1)  spectral shift
    z: jnp.ndarray      # (..., c, c)  the pseudoinverse estimate Z*


def _trace(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("...ii->...", x)


def ss_core(
    a_s: jnp.ndarray,
    *,
    method: str = "iterative",
    pinv_iters: int = 6,
    rank_tol: float = 1e-3,
    target_rank: int | None = None,
    use_shift: bool = True,
) -> SSCore:
    """Compute ``(U_ss, delta_ss)`` from the landmark core ``a_s`` (..., c, c).

    ``use_shift=False`` forces delta=0, which makes the SS model degenerate to
    the Nystrom prototype model exactly (useful for ablations/Theorem-1
    benchmarks).
    """
    c = a_s.shape[-1]
    dtype = jnp.promote_types(a_s.dtype, jnp.float32)
    a32 = a_s.astype(dtype)

    if method == "svd":
        if target_rank is not None:
            # Lemma-1 regime: keep exactly the top ``target_rank`` spectrum,
            # delta = mean of the flat tail.
            u_svd, s, vt = jnp.linalg.svd(a32, full_matrices=False)
            keep = jnp.arange(c) < target_rank
            s_inv = jnp.where(keep, 1.0 / jnp.where(s > 1e-30, s, 1.0), 0.0)
            z = jnp.einsum("...ji,...j,...kj->...ik", vt, s_inv, u_svd)
        else:
            z, keep, s = svd_pinv(a32, rank_tol=rank_tol)
        z = z.astype(dtype)
        rank = jnp.sum(keep, axis=-1).astype(dtype)
        # tr(A) - tr(A^+ A^2) = sum of discarded singular values (SPSD view).
        tail = jnp.sum(jnp.where(keep, 0.0, s), axis=-1)
        denom = jnp.maximum(c - rank, 1.0)
        delta = tail / denom
    elif method == "iterative":
        z = iterative_pinv(a32, num_iters=pinv_iters).astype(dtype)
        az = jnp.matmul(a32, z, precision=CORE_PRECISION)
        soft_rank = _trace(az)
        # tr(A^+ A^2) = tr(Z A A); numerator is the un-captured spectrum mass.
        tail = _trace(a32) - _trace(
            jnp.matmul(az, a32, precision=CORE_PRECISION)
        )
        denom = jnp.maximum(c - soft_rank, 1e-2)
        delta = jnp.maximum(tail, 0.0) / denom
    else:
        raise ValueError(f"unknown ss_core method: {method!r}")

    if not use_shift:
        delta = jnp.zeros_like(delta)
    delta = delta[..., None, None]
    u = jnp.matmul(z, jnp.eye(c, dtype=dtype) - delta * z,
                   precision=CORE_PRECISION)
    return SSCore(u=u.astype(a_s.dtype), delta=delta.astype(a_s.dtype), z=z.astype(a_s.dtype))
