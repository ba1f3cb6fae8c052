"""Training fwd+bwd benchmark: fused custom-VJP kernels vs the jnp path.

For each sequence length the same attention fwd+bwd cell (loss = sum(out^2),
grads w.r.t. q/k/v) runs through ``spectral_shift_attention`` (jnp reference
— materializes the (n, c) factor F and saves it for backward) and
``ss_attention_fused`` (Pallas custom-VJP — saves only the (c, 1) online-
softmax stats and recomputes the streams). Reported per cell:

    fwdbwd_ms     best wall-clock of a jitted value_and_grad call
    peak_temp_mb  XLA CompiledMemoryStats.temp_size_in_bytes of that program
    residual_mb   bytes of the saved VJP residuals (jax.vjp closure) — the
                  tensors that must live across fwd->bwd and set the
                  training memory profile

plus jnp/fused ratio rows. A model-level cell (reduced decoder via
``make_grad_step``) exercises the full dispatch wiring end to end.

On CPU the fused path runs the kernels in interpret mode — wall-clock and
XLA temp there measure interpreter overhead (dense block emulation), not
kernel behavior (the dispatch registry routes CPU to jnp for exactly this
reason). ``residual_mb`` is the backend-independent evidence of the memory
win: the custom VJP saves the (c, 1) online-softmax stats instead of the
(n, c) factor F. TPU is the compile target. ``REPRO_BENCH_SMOKE=1``
shrinks the sweep to one tiny cell for CI.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

from repro.core.attention import SSConfig, spectral_shift_attention
from repro.kernels.ops import ss_attention_fused
from repro.runtime import interpret_kernels


def _smoke() -> bool:
    return os.environ.get("REPRO_BENCH_SMOKE", "") == "1"


def _measure_ms(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))  # compile + warmup
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _peak_temp_mb(fn, args) -> float:
    try:
        stats = jax.jit(fn).lower(*args).compile().memory_analysis()
        return stats.temp_size_in_bytes / 2**20
    except Exception:
        return float("nan")


def _residual_mb(loss_fn, args) -> float:
    """Bytes saved across the fwd->bwd boundary (the vjp closure)."""
    _, vjp_fn = jax.vjp(loss_fn, *args)
    return sum(
        x.nbytes for x in jax.tree.leaves(vjp_fn) if hasattr(x, "nbytes")
    ) / 2**20


def _attention_cell(rows, n, c, d, causal, reps, interpret):
    b = 4  # flattened batch*heads
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, n, d)) * 0.5
    k = jax.random.normal(keys[1], (b, n, d)) * 0.5
    v = jax.random.normal(keys[2], (b, n, d))
    cfg = SSConfig(num_landmarks=c, causal=causal)

    losses = {
        "jnp": lambda q, k, v: jnp.sum(
            spectral_shift_attention(q, k, v, cfg) ** 2
        ),
        "fused": lambda q, k, v: jnp.sum(
            ss_attention_fused(q, k, v, cfg, interpret=interpret) ** 2
        ),
    }
    kind = "causal" if causal else "bidir"
    ms, res = {}, {}
    for name, loss in losses.items():
        case = f"n{n}_{kind}_{name}"
        fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
        ms[name] = _measure_ms(jax.jit(fn), (q, k, v), reps)
        res[name] = _residual_mb(loss, (q, k, v))
        rows.append(f"train_step,{case},fwdbwd_ms,{ms[name]:.2f}")
        rows.append(f"train_step,{case},peak_temp_mb,{_peak_temp_mb(fn, (q, k, v)):.2f}")
        rows.append(f"train_step,{case},residual_mb,{res[name]:.2f}")
    rows.append(
        f"train_step,n{n}_{kind},jnp_over_fused_time,"
        f"{ms['jnp'] / ms['fused']:.3f}"
    )
    rows.append(
        f"train_step,n{n}_{kind},jnp_over_fused_residual_mem,"
        f"{res['jnp'] / res['fused']:.3f}"
    )


def _model_cell(rows, seq_len, reps):
    """Full reduced-decoder fwd+bwd through the dispatch wiring."""
    import dataclasses

    from repro.configs.base import reduced
    from repro.configs.registry import get_config
    from repro.models.model import model_specs
    from repro.models.params import init_params
    from repro.train.train_step import make_grad_step

    base = reduced(get_config("qwen2-7b"), num_landmarks=32, remat="ss_stats")
    params = init_params(model_specs(base), jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, seq_len), 0, base.vocab_size
    )
    batch = {"tokens": tokens}
    for impl in ("spectral_shift", "spectral_shift_fused"):
        cfg = dataclasses.replace(base, attention_impl=impl)
        fn = jax.jit(make_grad_step(cfg))
        t = _measure_ms(fn, (params, batch), reps)
        rows.append(f"train_step,model_{impl}_n{seq_len},fwdbwd_ms,{t:.2f}")


def run(rows: list[str]) -> None:
    interpret = interpret_kernels()
    if _smoke():
        _attention_cell(rows, 512, 32, 64, False, reps=1, interpret=interpret)
        _model_cell(rows, 128, reps=1)
        return
    c, d, reps = 64, 64, 3
    for n in (1024, 4096, 16384):
        _attention_cell(rows, n, c, d, False, reps, interpret)
    _attention_cell(rows, 4096, c, d, True, reps, interpret)
    _model_cell(rows, 512, reps=2)
