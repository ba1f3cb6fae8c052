"""Attention implementations: exact softmax, Nystrom, and Spectral Shifting.

All functions take ``q`` (..., n_q, d), ``k``/``v`` (..., n_k, d) with
arbitrary shared leading batch/head dims and return (..., n_q, d_v).
Softmax always runs in fp32; outputs are cast back to the input dtype.

``spectral_shift_attention`` is the paper's contribution (eq. (10) plus the
``+ delta_ss I_n`` shifted-identity term, see DESIGN.md §2.2). With
``use_shift=False`` it reduces exactly to Nystromformer attention, which we
keep as the paper's main baseline.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.landmarks import segment_means, segment_of
from repro.core.pinv import CORE_PRECISION
from repro.core.spectral_shift import ss_core


@dataclasses.dataclass(frozen=True)
class SSConfig:
    """Hyper-parameters of the spectral-shifting approximation."""

    num_landmarks: int = 64
    pinv_iters: int = 6
    method: str = "iterative"        # "iterative" (TPU) | "svd" (oracle)
    rank_tol: float = 1e-3
    use_shift: bool = True           # False => exact Nystromformer
    include_shift_identity: bool = True  # the + delta_ss * V output term
    variant: str = "closed_form"     # "closed_form" | "eq10_literal"
    causal: bool = False             # segment-causal masking (beyond-paper)
    landmark_via_matmul: bool = False  # GEMM segment-means (sharded-seq safe)
    delta_scale: str = "paper"       # "paper" | "corrected" (x c/n; see below)
    # "corrected" (beyond-paper): the paper fits delta on the c x c landmark
    # core A = L(Q~K~^T), whose row-softmax normalizes over c columns — its
    # entries (and hence its tail eigenvalues) sit at the 1/c scale, while
    # the n x n attention matrix being approximated normalizes over n
    # columns (1/n scale). Applying the core-fitted delta directly (the
    # paper's eq. 10) overestimates the shift by ~n/c; scaling by c/n puts
    # the shifted identity on the right spectral scale. Validated in
    # benchmarks/bench_accuracy.py (accuracy_output_corrected rows).


def _softmax(scores: jnp.ndarray, mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    out = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    if mask is not None:
        out = jnp.where(mask, out, 0.0)
    return out / jnp.maximum(jnp.sum(out, axis=-1, keepdims=True), 1e-30)


def full_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    mask: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Exact O(n^2) softmax attention (paper §2.1)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    scores = jnp.einsum(
        "...qd,...kd->...qk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        n_q, n_k = q.shape[-2], k.shape[-2]
        # Queries are the last n_q positions of an n_k-long context.
        cmask = (
            jnp.arange(n_k)[None, :]
            <= (jnp.arange(n_q)[:, None] + (n_k - n_q))
        )
        mask = cmask if mask is None else jnp.logical_and(mask, cmask)
    attn = _softmax(scores, mask)
    return jnp.einsum("...qk,...kd->...qd", attn, v.astype(jnp.float32)).astype(q.dtype)


def chunked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    block: int = 1024,
    scale: Optional[float] = None,
    unroll: bool = False,
) -> jnp.ndarray:
    """Exact softmax attention, computed blockwise over keys with the online
    softmax recurrence (flash-attention memory profile, pure jnp). This is
    the memory-feasible 'full attention' baseline for 32k+ sequences — the
    O(n^2) FLOPs remain; only the O(n^2) score matrix is never materialized.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    n_q, n_k = q.shape[-2], k.shape[-2]
    block = min(block, n_k)
    pad = -n_k % block
    if pad:
        widths = [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)]
        k, v = jnp.pad(k, widths), jnp.pad(v, widths)
    nb = (n_k + pad) // block
    q32 = q.astype(jnp.float32)
    qpos = jnp.arange(n_q) + (n_k - n_q)  # decode convention

    kb = jnp.moveaxis(k.reshape(*k.shape[:-2], nb, block, d), -3, 0)
    vb = jnp.moveaxis(v.reshape(*v.shape[:-2], nb, block, v.shape[-1]), -3, 0)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        i, kblk, vblk = xs
        s = jnp.einsum("...qd,...kd->...qk", q32, kblk.astype(jnp.float32)) * scale
        kpos = i * block + jnp.arange(block)
        mask = kpos[None, :] < n_k
        if causal:
            mask = jnp.logical_and(mask, kpos[None, :] <= qpos[:, None])
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "...qk,...kd->...qd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l_new, acc), None

    lead = q.shape[:-2]
    m0 = jnp.full((*lead, n_q), -1e30, jnp.float32)
    l0 = jnp.zeros((*lead, n_q), jnp.float32)
    acc0 = jnp.zeros((*lead, n_q, v.shape[-1]), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (jnp.arange(nb), kb, vb),
        unroll=nb if unroll else 1,
    )
    return (acc / jnp.maximum(l_f, 1e-30)[..., None]).astype(q.dtype)


def _ss_factors(q, k, cfg: SSConfig, scale, q_landmarks=None, k_landmarks=None):
    """The three softmax factor matrices F (n_q,c), A (c,c), B (c,n_k)."""
    m = cfg.num_landmarks
    mm = cfg.landmark_via_matmul
    q_l = segment_means(q, m, via_matmul=mm) if q_landmarks is None else q_landmarks
    k_l = segment_means(k, m, via_matmul=mm) if k_landmarks is None else k_landmarks
    if q_l.shape[-2] != k_l.shape[-2]:
        raise ValueError(
            "spectral-shift attention needs matching landmark counts for Q~ "
            f"and K~, got {q_l.shape[-2]} vs {k_l.shape[-2]}. For decode "
            "(n_q=1) pass cached q_landmarks/k_landmarks explicitly."
        )
    f_mask = a_mask = b_mask = None
    if cfg.causal:
        n_q, n_k = q.shape[-2], k.shape[-2]
        c = k_l.shape[-2]
        qpos = jnp.arange(n_q) + (n_k - n_q)
        qseg = segment_of(qpos, n_k, m)[:, None]             # (n_q, 1)
        lseg = jnp.arange(c)[None, :]                        # (1, c)
        f_mask = lseg <= qseg                                # query i sees landmark seg <= its seg
        a_mask = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
        seg = -(-n_k // m)
        b_mask = jnp.arange(n_k)[None, :] < (jnp.arange(c)[:, None] + 1) * seg
    f = _softmax(jnp.einsum("...qd,...cd->...qc", q, k_l) * scale, f_mask)
    a = _softmax(
        jnp.einsum("...cd,...ed->...ce", q_l, k_l, precision=CORE_PRECISION)
        * scale,
        a_mask,
    )
    b = _softmax(jnp.einsum("...cd,...kd->...ck", q_l, k) * scale, b_mask)
    return f, a, b


def spectral_shift_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: SSConfig = SSConfig(),
    *,
    scale: Optional[float] = None,
    q_landmarks: Optional[jnp.ndarray] = None,
    k_landmarks: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Linear-time attention via Modified Spectral Shifting (paper eq. (10)).

    out = F @ U_ss @ (B @ V) [+ delta_ss * V]   with U_ss = Z*(I - delta Z*).

    Cost: O(n c d + n c^2 + c^3) — linear in n (paper §8).

    ``q_landmarks``/``k_landmarks`` override segment-mean landmark selection;
    serving passes the incrementally-maintained landmark state here so a
    single decode query still has a full (c x c) core.
    """
    if (
        q.shape[-2] <= cfg.num_landmarks
        and k.shape[-2] <= cfg.num_landmarks
        and q_landmarks is None
    ):
        return full_attention(q, k, v, causal=cfg.causal, scale=scale)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    f, a, b = _ss_factors(q, k, cfg, scale, q_landmarks, k_landmarks)
    core = ss_core(
        a,
        method=cfg.method,
        pinv_iters=cfg.pinv_iters,
        rank_tol=cfg.rank_tol,
        use_shift=cfg.use_shift,
    )
    if cfg.delta_scale == "corrected" and cfg.use_shift:
        # Beyond-paper: rescale the core-fitted shift to the n x n softmax
        # scale (core rows normalize over c entries, full rows over n).
        c_count = a.shape[-1]
        core = core._replace(
            delta=core.delta * (c_count / k.shape[-2]),
            u=jnp.matmul(
                core.z,
                jnp.eye(c_count, dtype=core.z.dtype)
                - (core.delta * (c_count / k.shape[-2])) * core.z,
                precision=CORE_PRECISION,
            ),
        )
    if cfg.variant == "eq10_literal":
        # Literal paper eq. (10): U = A^+ (I - delta A)  [typo'd form, kept
        # for faithfulness comparison — see DESIGN.md §2.1].
        c = a.shape[-1]
        u = jnp.matmul(core.z, jnp.eye(c, dtype=a.dtype) - core.delta * a,
                       precision=CORE_PRECISION)
    else:
        u = core.u
    if cfg.causal:
        # The causally-masked core A is lower-triangular, so its exact
        # (pseudo)inverse — and hence U — is lower-triangular too. The
        # finite Newton–Schulz iteration starts from A^T and is not exactly
        # triangular; project U back so no future landmark channel leaks
        # into past queries.
        c = a.shape[-1]
        tril = jnp.tril(jnp.ones((c, c), bool))
        u = jnp.where(tril, u, 0.0)
    v32 = v.astype(jnp.float32)
    bv = jnp.einsum("...ck,...kd->...cd", b, v32)           # (..., c, d_v)
    ubv = jnp.matmul(u.astype(jnp.float32), bv, precision=CORE_PRECISION)
    out = jnp.einsum("...qc,...cd->...qd", f, ubv)
    n_q, n_k = q.shape[-2], k.shape[-2]
    if cfg.include_shift_identity and n_q <= n_k:
        # + delta_ss * I_n maps to + delta_ss * V. Under the decode
        # convention (queries are the last n_q positions of the n_k context)
        # the diagonal picks out the trailing rows of V; for self-attention
        # (n_q == n_k) this is + delta_ss * V exactly.
        out = out + core.delta.astype(jnp.float32) * v32[..., n_k - n_q :, :]
    return out.astype(q.dtype)


def nystrom_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    num_landmarks: int = 64,
    pinv_iters: int = 6,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Nystromformer baseline (paper §2.4): F @ A^+ @ (B @ V)."""
    cfg = SSConfig(
        num_landmarks=num_landmarks,
        pinv_iters=pinv_iters,
        method="iterative",
        use_shift=False,
        include_shift_identity=False,
        causal=causal,
    )
    return spectral_shift_attention(q, k, v, cfg, scale=scale)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    impl: str = "full",
    *,
    causal: bool = False,
    ss_cfg: Optional[SSConfig] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Dispatch between attention implementations by name."""
    if impl == "full":
        return full_attention(q, k, v, causal=causal, scale=scale)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal, scale=scale)
    if impl == "nystrom":
        cfg = ss_cfg or SSConfig()
        return nystrom_attention(
            q, k, v, num_landmarks=cfg.num_landmarks,
            pinv_iters=cfg.pinv_iters, causal=causal, scale=scale,
        )
    if impl == "spectral_shift":
        cfg = ss_cfg or SSConfig()
        if causal and not cfg.causal:
            cfg = dataclasses.replace(cfg, causal=True)
        return spectral_shift_attention(q, k, v, cfg, scale=scale)
    raise ValueError(f"unknown attention impl: {impl!r}")
