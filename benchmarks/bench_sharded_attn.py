"""Context-parallel attention benchmark: shard_map fused kernels vs the
jnp-GSPMD route on a sequence-sharded mesh.

The measurement runs in a CPU subprocess (``JAX_PLATFORMS=cpu``) with
``--xla_force_host_platform_device_count=4`` (the same mechanism as the
multi-device tests), so it never contends with a parent that holds the
chip, and reports per cell:

    fwdbwd_ms    best wall-clock of a jitted value_and_grad call
    residual_mb  bytes of the saved VJP residuals (jax.vjp closure) — the
                 fused-sharded path saves the (c, dv)/(c, 1) landmark
                 summaries + online-softmax stats, the jnp path the (n, c)
                 softmax factors

plus jnp/sharded ratio rows. On CPU the kernels run in interpret mode, so
wall-clock measures interpreter overhead (the dispatch heuristic routes CPU
to jnp for exactly this reason); ``residual_mb`` is the backend-independent
evidence. TPU is the compile target. ``REPRO_BENCH_SMOKE=1`` shrinks the
sweep to one tiny cell for CI.
"""
from __future__ import annotations

import os
import subprocess
import sys

_SCRIPT = """
import time
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.attention import SSConfig, spectral_shift_attention
from repro.kernels.sharded import ss_attention_fused_sharded
from repro.runtime import interpret_kernels

SIZES = {sizes}
REPS = {reps}
mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
interpret = interpret_kernels()

def measure_ms(fn, args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3

def residual_mb(loss, args):
    _, vjp_fn = jax.vjp(loss, *args)
    return sum(x.nbytes for x in jax.tree.leaves(vjp_fn)
               if hasattr(x, "nbytes")) / 2**20

for n in SIZES:
    c, d, b = 32, 64, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, n, d)) * 0.5
    k = jax.random.normal(ks[1], (b, n, d)) * 0.5
    v = jax.random.normal(ks[2], (b, n, d))
    cfg = SSConfig(num_landmarks=c, causal=True, landmark_via_matmul=True)
    sh = NamedSharding(mesh, P(None, "data", None))
    args = tuple(jax.device_put(x, sh) for x in (q, k, v))

    losses = {{
        "jnp": lambda q, k, v: jnp.sum(
            spectral_shift_attention(q, k, v, cfg) ** 2),
        "sharded": lambda q, k, v: jnp.sum(ss_attention_fused_sharded(
            q, k, v, cfg, mesh=mesh, seq_axes=("data",),
            interpret=interpret) ** 2),
    }}
    ms, res = {{}}, {{}}
    for name, loss in losses.items():
        fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                     in_shardings=(sh, sh, sh))
        ms[name] = measure_ms(fn, args)
        res[name] = residual_mb(loss, args)
        print(f"sharded_attn,n{{n}}_sp4_{{name}},fwdbwd_ms,{{ms[name]:.2f}}")
        print(f"sharded_attn,n{{n}}_sp4_{{name}},residual_mb,{{res[name]:.2f}}")
    print(f"sharded_attn,n{{n}}_sp4,jnp_over_sharded_time,"
          f"{{ms['jnp'] / ms['sharded']:.3f}}")
    print(f"sharded_attn,n{{n}}_sp4,jnp_over_sharded_residual_mem,"
          f"{{res['jnp'] / res['sharded']:.3f}}")
"""


def _smoke() -> bool:
    return os.environ.get("REPRO_BENCH_SMOKE", "") == "1"


def run(rows: list[str]) -> None:
    sizes, reps = ((512,), 1) if _smoke() else ((2048, 8192), 3)
    env = dict(os.environ)
    # A CPU rehearsal: the parent (benchmarks.run) may already hold the chip.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep * bool(env.get("PYTHONPATH", "")) + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(sizes=sizes, reps=reps)],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded_attn subprocess failed:\n{proc.stderr[-2000:]}"
        )
    for line in proc.stdout.splitlines():
        if line.startswith("sharded_attn,"):
            rows.append(line)
