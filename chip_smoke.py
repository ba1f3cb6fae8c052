"""Bring-up smoke run: the serving and training paths on one TPU chip.

    python chip_smoke.py [--seed N]          # phases A-D, one chip
    python chip_smoke.py --four-chips        # phase E only, four chips

Everything runs in this one process, through the objects a user calls
(``ServeEngine``, ``Trainer``), with random weights drawn from ``--seed``:

  A  device: platform, kind, count, jax and libtpu versions.
  B  kernels at Qwen2-7B head widths (28 heads, n=4096, d=128, c=64, bf16),
     compiled by Mosaic: ``ss_attention_fused`` forward and grad,
     bidirectional and causal, against ``spectral_shift_attention`` in f32
     at the highest matmul precision; ``paged_row_stats_lanes`` over a
     serving-size block pool against ``recompute_stats``.
  C  serve: Qwen2-7B at its published widths, depth cut to 16 layers,
     bf16 weights; 8 greedy requests (prompts of 128-1536 tokens, 32 new
     tokens) through ss_fused prefill and the paged decode kernel, then
     the same batch again (no new compiles), then an engine on the gather
     decode route for comparison.
  D  train: paper-bert at full size, 3 Trainer steps at seq 4096, batch 8,
     on the fused kernels; step-1 loss against the jnp attention route.
  E  (``--four-chips`` only) 2 paper-bert steps at seq 8192, batch 4, with
     the sequence sharded over four chips, against the same steps on one
     chip.

Every compiled serve and train program is checked for ``tpu_custom_call``
(an interpreted kernel or the jnp route lowers to plain HLO). The script
exits non-zero, printing no result line, when JAX finds no TPU or any check
fails. A passing run ends with one JSON line:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime import enable_compile_cache, interpret_kernels  # noqa: E402

# tests/test_kernels.py's bound for bf16 kernels against an f32 oracle.
BF16_TOL = 3e-2
# tests/test_sharded_attn.py's bound on context-parallel vs one-device
# params after two steps.
SP_PARAM_TOL = 2e-4
# Step-1 loss, fused kernels vs the jnp route (relative).
LOSS_TOL = 1e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def require_kernel(jitted, *args) -> None:
    """The compiled program for ``args`` contains a Mosaic kernel. After a
    call with the same arguments this reuses the compiled executable."""
    text = jitted.lower(*args).compile().as_text()
    check("tpu_custom_call" in text,
          f"{getattr(jitted, '__name__', jitted)} has no tpu_custom_call")


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


# --------------------------------------------------------------------------
# A: device
# --------------------------------------------------------------------------
def phase_device() -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[A] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} jax={jax.__version__} "
          f"libtpu={_version('libtpu')}", flush=True)
    return dev


# --------------------------------------------------------------------------
# B: kernels against the jnp reference
# --------------------------------------------------------------------------
def phase_kernels(seed: int, *, heads=28, n=4096, d=128, c=64,
                  lanes=8, kv_heads=4, block=16, horizon=2048) -> None:
    from repro.core.attention import SSConfig, spectral_shift_attention
    from repro.kernels.ops import ss_attention_fused

    interpret = interpret_kernels()
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (1, heads, n, d)
    q = (jax.random.normal(ks[0], shape) * 0.5).astype(jnp.bfloat16)
    k = (jax.random.normal(ks[1], shape) * 0.5).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], shape).astype(jnp.bfloat16)
    w = jax.random.normal(ks[3], shape, jnp.float32)
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    for causal in (False, True):
        cfg = SSConfig(num_landmarks=c, causal=causal)

        def fused(q, k, v, cfg=cfg):
            return ss_attention_fused(q, k, v, cfg, interpret=interpret)

        def ref(q, k, v, cfg=cfg):
            return spectral_shift_attention(q, k, v, cfg)

        def loss(attn):
            # w is an argument: a captured array would be embedded in the
            # compiled program as a constant
            return lambda q, k, v, w: jnp.sum(
                attn(q, k, v).astype(jnp.float32) * w
            )

        fwd = jax.jit(fused)
        grad = jax.jit(jax.grad(loss(fused), argnums=(0, 1, 2)))
        out, grads = fwd(q, k, v), grad(q, k, v, w)
        require_kernel(fwd, q, k, v)
        require_kernel(grad, q, k, v, w)
        with jax.default_matmul_precision("highest"):
            out_ref = jax.jit(ref)(q32, k32, v32)
            grads_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(
                q32, k32, v32, w
            )
        errs = [rel_err(out, out_ref)] + [
            rel_err(g, r) for g, r in zip(grads, grads_ref)
        ]
        kind = "causal" if causal else "bidir"
        print(f"[B] ss_attention_fused {kind}: rel err fwd={errs[0]:.3e} "
              f"dq={errs[1]:.3e} dk={errs[2]:.3e} dv={errs[3]:.3e} "
              f"(bound {BF16_TOL})", flush=True)
        check(max(errs) <= BF16_TOL, f"ss_attention_fused {kind} vs jnp")

    _check_paged_row_stats(seed, lanes=lanes, kv_heads=kv_heads,
                           r=heads // kv_heads, d=d, block=block,
                           horizon=horizon, interpret=interpret)


def _check_paged_row_stats(seed, *, lanes, kv_heads, r, d, block, horizon,
                           interpret) -> None:
    """The gather-free decode kernel over a pool the size phase C's engine
    holds (``lanes * horizon / block`` blocks plus the zero block)."""
    from repro.kernels.paged_decode import paged_row_stats_lanes
    from repro.serve.decode_state import recompute_stats
    from repro.serve.paged import ZERO_BLOCK

    rng = np.random.default_rng(seed)
    n_slots = horizon // block
    num_blocks = lanes * n_slots + 1
    scale = d ** -0.5
    q = jnp.asarray(rng.normal(size=(lanes, kv_heads, r, d)), jnp.float32)
    k_pool = jnp.asarray(
        rng.normal(size=(kv_heads, num_blocks, block, d)) * 0.5, jnp.bfloat16
    ).at[:, ZERO_BLOCK].set(0)
    v_pool = jnp.asarray(
        rng.normal(size=(kv_heads, num_blocks, block, d)), jnp.bfloat16
    ).at[:, ZERO_BLOCK].set(0)
    kv_valid = rng.integers(1, horizon, size=lanes).astype(np.int32)
    perm = rng.permutation(np.arange(1, num_blocks)).astype(np.int32)
    tables = np.full((lanes, n_slots), ZERO_BLOCK, np.int32)
    for lane in range(lanes):
        used = -(-int(kv_valid[lane]) // block)
        tables[lane, :used] = perm[lane * n_slots: lane * n_slots + used]

    def stats(q, k_pool, v_pool, tables, kv_valid):
        return paged_row_stats_lanes(
            q, (k_pool,), v_pool, tables, kv_valid, scale=scale,
            block_size=block, interpret=interpret,
        )

    fn = jax.jit(stats)
    args = (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(kv_valid))
    m, l, acc = fn(*args)
    require_kernel(fn, *args)
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for lane in range(lanes):
            used = -(-int(kv_valid[lane]) // block)
            ids = jnp.asarray(tables[lane, :used])
            view = lambda pool: jnp.take(pool, ids, axis=1).reshape(  # noqa: E731
                1, kv_heads, used * block, d)
            m_r, l_r, acc_r = recompute_stats(
                q[lane][None], view(k_pool), view(v_pool),
                int(kv_valid[lane]) - 1, scale,
            )
            # anchor-invariant: log-mass and normalized BV rows
            worst = max(
                worst,
                rel_err(m[lane] + jnp.log(l[lane]), m_r[0] + jnp.log(l_r[0])),
                rel_err(acc[lane] / l[lane], acc_r[0] / l_r[0]),
            )
    print(f"[B] paged_row_stats_lanes: {lanes} lanes x {kv_heads} kv heads x "
          f"{r} rows, pool {num_blocks}x{block}: rel err {worst:.3e} "
          f"(bound {BF16_TOL})", flush=True)
    check(worst <= BF16_TOL, "paged_row_stats_lanes vs recompute_stats")


# --------------------------------------------------------------------------
# C: serve
# --------------------------------------------------------------------------
def serve_config():
    """Qwen2-7B at published widths; 16 of its 28 layers, bf16 weights."""
    from repro.configs.registry import get_config

    return dataclasses.replace(
        get_config("qwen2-7b"), num_layers=16, param_dtype="bfloat16",
    )


def serve_settings():
    from repro.configs.base import ServeConfig

    # prefill_bucket 512 bounds the prefill programs to three (prompts of
    # up to 1536 tokens); eos_id -1: random weights have no end token, so
    # every request decodes its full budget.
    return ServeConfig(
        max_lanes=8, max_seq=2048, block_size=16, prefill_impl="ss_fused",
        decode_impl="paged", prefill_bucket=512, telemetry=True, eos_id=-1,
    )


def _prompts(seed: int, vocab: int, count=8, lo=128, hi=1536) -> list:
    rng = np.random.default_rng(seed)
    lens = [lo, hi] + rng.integers(lo, hi + 1, size=count - 2).tolist()
    return [rng.integers(3, vocab, size=n).tolist() for n in lens]


def _watch_logits(engine) -> dict:
    """Count every logits row the engine samples from, and the non-finite
    ones among them (prefill and decode rows alike); keep each request's
    first two rows (its prefill row and its first decode step)."""
    seen = {"rows": 0, "nonfinite": 0, "first": {}}
    sample = engine._sample

    def checked(lane, lg):
        seen["rows"] += 1
        seen["nonfinite"] += int(not np.isfinite(lg).all())
        kept = seen["first"].setdefault(lane.req.uid, [])
        if len(kept) < 2:
            kept.append(np.array(lg, np.float32))
        return sample(lane, lg)

    engine._sample = checked
    return seen


def _run_batch(engine, prompts, uid0: int, new_tokens: int):
    """Submit the whole batch at once and drain it. Returns the outputs and
    host-clock timings: batch wall seconds, per-request time to first token
    and mean gap between later tokens."""
    from repro.serve.engine import Request

    stamps: dict = {}

    def on_token(uid, tok):
        stamps.setdefault(uid, []).append(time.perf_counter())

    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        engine.submit(Request(uid0 + i, p, max_new_tokens=new_tokens,
                              on_token=on_token))
    engine.run()
    wall = time.perf_counter() - t0
    uids = range(uid0, uid0 + len(prompts))
    check(all(engine.outcomes.get(u) == "finished" for u in uids),
          f"requests not finished: {[engine.outcomes.get(u) for u in uids]}")
    outs = [engine.finished[u] for u in uids]
    check(all(len(o) == new_tokens for o in outs), "short outputs")
    ttft = [stamps[u][0] - t0 for u in uids]
    itl = [(stamps[u][-1] - stamps[u][0]) / (new_tokens - 1) for u in uids]
    return outs, {"wall": wall, "ttft": ttft, "itl": itl}


def _peak_gb() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.2f} GB"


def _require_serve_kernels(engine, prompts) -> None:
    """The paged engine's prefill (each bucket served) and decode-tick
    programs contain Mosaic kernels."""
    for n_pad in sorted({engine._bucket * -(-len(p) // engine._bucket)
                         for p in prompts}):
        require_kernel(engine._prefill._jitted, engine.params,
                       jnp.zeros((1, n_pad), jnp.int32),
                       jnp.asarray(n_pad, jnp.int32))
    lanes = engine.max_lanes
    deepest = max(len(p) for p in prompts)
    nb = engine.kv.view_blocks_needed(np.asarray([deepest]), [0])
    require_kernel(
        engine._fused_step._jitted, engine.kv._storage, engine.params,
        jnp.asarray(engine.sched.tables())[:, :nb],
        jnp.zeros((lanes, 1, 1), jnp.int32), jnp.zeros(lanes, jnp.int32),
        jnp.zeros(lanes, bool),
    )


def phase_serve(seed: int, cfg=None, serve=None, new_tokens=32,
                prompts=None) -> None:
    from repro.models.model import model_specs
    from repro.models.params import init_params
    from repro.serve.engine import ServeEngine

    cfg = cfg or serve_config()
    serve = serve or serve_settings()
    t0 = time.perf_counter()
    params = init_params(model_specs(cfg), jax.random.PRNGKey(seed),
                         dtype=jnp.dtype(cfg.param_dtype))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[C] {cfg.name}: layers={cfg.num_layers} (published 28) "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} qkv_bias={cfg.qkv_bias} "
          f"weights={cfg.param_dtype} {nbytes / 1e9:.2f} GB; "
          f"lanes={serve.max_lanes} max_seq={serve.max_seq} "
          f"block={serve.block_size} prefill={serve.prefill_impl} "
          f"decode={serve.decode_impl} (init {time.perf_counter() - t0:.1f}s)",
          flush=True)
    prompts = prompts or _prompts(seed, cfg.vocab_size)

    engine = ServeEngine(cfg, params, serve=serve)
    seen = _watch_logits(engine)
    check(engine.stats()["decode_impl"] == "paged",
          f"decode route is {engine.stats()['decode_impl']}, not paged")
    first, cold = _run_batch(engine, prompts, 0, new_tokens)
    compiles = dict(engine.stats()["xla_compiles"])
    again, warm = _run_batch(engine, prompts, len(prompts), new_tokens)
    st = engine.stats()
    check(st["xla_compiles"] == compiles,
          f"second batch compiled again: {compiles} -> {st['xla_compiles']}")
    check(seen["rows"] >= 2 * len(prompts) * new_tokens
          and seen["nonfinite"] == 0,
          f"logits rows {seen['rows']}, non-finite {seen['nonfinite']}")
    _require_serve_kernels(engine, prompts)
    n_tok = len(prompts) * new_tokens
    print(f"[C] paged engine: compiles {compiles}; first batch "
          f"{cold['wall']:.1f}s (with compiles), second batch "
          f"{warm['wall']:.2f}s; device peak {_peak_gb()}", flush=True)
    print(f"[C] informative, second batch (host clock, 8 requests submitted "
          f"at once): {n_tok / warm['wall']:.1f} tok/s; ttft median "
          f"{np.median(warm['ttft']):.3f}s max {max(warm['ttft']):.3f}s; "
          f"mean gap between later tokens, median over requests "
          f"{np.median(warm['itl']) * 1e3:.1f} ms; batches identical: "
          f"{first == again}", flush=True)
    del engine
    gc.collect()

    gather = ServeEngine(cfg, params,
                         serve=dataclasses.replace(serve, decode_impl="gather"))
    seen_g = _watch_logits(gather)
    ref, _ = _run_batch(gather, prompts, 0, new_tokens)
    del gather
    gc.collect()
    check(seen_g["nonfinite"] == 0,
          f"gather engine: {seen_g['nonfinite']} non-finite logits rows")
    firsts = [a[0] == b[0] for a, b in zip(first, ref)]
    share = np.mean([x == y for a, b in zip(first, ref) for x, y in zip(a, b)])
    # both engines feed the same first token into their first decode step
    step_diff = max(
        rel_err(seen["first"][u][1], seen_g["first"][u][1])
        for u in range(len(prompts))
    )
    print(f"[C] informative: paged vs gather decode, {share:.3f} of tokens "
          f"match; first decode step logits differ by {step_diff:.3e} "
          f"(relative to the largest logit)", flush=True)
    check(all(firsts), f"first tokens differ from the gather engine: {firsts}")
    print(f"[C] first tokens match the gather engine ({sum(firsts)}/"
          f"{len(firsts)})", flush=True)


# --------------------------------------------------------------------------
# D: train
# --------------------------------------------------------------------------
def train_config(**overrides):
    from repro.configs.registry import get_config

    return dataclasses.replace(
        get_config("paper-bert"), attention_impl="spectral_shift_fused",
        **overrides,
    )


def _train(cfg, mesh, seq, batch, steps, seed, rule_overrides=None,
           kernel=True):
    from repro.configs.base import ShapeConfig, TrainConfig
    from repro.data.pipeline import make_global_batch
    from repro.distributed.sharding import sharding_rules
    from repro.train.trainer import Trainer

    shape = ShapeConfig(f"train_{seq}", seq, batch, "train")
    with tempfile.TemporaryDirectory() as ckpt:
        tr = Trainer(cfg, TrainConfig(checkpoint_dir=ckpt, seed=seed), shape,
                     mesh, rule_overrides=rule_overrides)
        hist = tr.run(steps, log_every=steps)
        if kernel:
            # traced under the same rules as Trainer.run, so this reuses
            # the step's compiled executable
            with tr.mesh, sharding_rules(tr.mesh, tr.rule_overrides):
                batch_arrs = make_global_batch(tr.data.batch(tr.step), tr.b_sh)
                require_kernel(tr.jitted, tr.params, tr.opt_state, batch_arrs)
        params = [np.asarray(x, np.float32) for x in jax.tree.leaves(tr.params)]
    losses = [h["loss"] for h in hist]
    check(all(np.isfinite(losses)), f"non-finite losses {losses}")
    return losses, params, [h["step_time_s"] for h in hist]


def phase_train(seed: int, cfg=None, seq=4096, batch=8, steps=3) -> None:
    from repro.launch.mesh import make_local_mesh

    cfg = cfg or train_config()
    mesh = make_local_mesh(1)
    losses, _, times = _train(cfg, mesh, seq, batch, steps, seed)
    ref, _, _ = _train(dataclasses.replace(cfg, attention_backend="jnp"),
                       mesh, seq, batch, 1, seed, kernel=False)
    rel = abs(losses[0] - ref[0]) / abs(ref[0])
    print(f"[D] {cfg.name} seq={seq} batch={batch} mesh={dict(mesh.shape)}: "
          f"losses {losses}; step-1 jnp loss {ref[0]:.6f}, rel diff "
          f"{rel:.3e} (bound {LOSS_TOL})", flush=True)
    print(f"[D] informative: step times {[round(t, 3) for t in times]}s "
          f"(step 1 compiles); device peak {_peak_gb()}", flush=True)
    check(rel <= LOSS_TOL, "fused step-1 loss vs jnp")


# --------------------------------------------------------------------------
# E: context-parallel training over four chips
# --------------------------------------------------------------------------
def phase_context_parallel(seed: int, cfg=None, seq=8192, batch=4,
                           steps=2) -> None:
    """Batch 4: the one-chip reference step at seq 8192 must fit one chip
    (its f32 logits over the 30,522-token vocabulary dominate)."""
    from jax.sharding import Mesh

    cfg = cfg or train_config()
    devs = jax.devices()
    check(len(devs) >= 4, f"four chips needed, found {len(devs)}")
    one = Mesh(np.array(devs[:1]).reshape(1, 1), ("data", "model"))
    four = Mesh(np.array(devs[:4]).reshape(1, 4), ("data", "model"))
    l1, p1, _ = _train(cfg, one, seq, batch, steps, seed)
    l4, p4, times = _train(cfg, four, seq, batch, steps, seed,
                           rule_overrides={"seq": "model"})
    worst = max(float(np.max(np.abs(a - b))) for a, b in zip(p1, p4))
    print(f"[E] {cfg.name} seq={seq} batch={batch}: one chip losses {l1}, "
          f"four chips (seq over 'model') losses {l4}; max param diff "
          f"{worst:.3e} (bound {SP_PARAM_TOL})", flush=True)
    print(f"[E] informative: four-chip step times "
          f"{[round(t, 3) for t in times]}s (step 1 compiles)", flush=True)
    check(worst <= SP_PARAM_TOL, "context-parallel params vs one chip")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase E, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found {platform!r}")
    print(f"[setup] compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    dev = phase_device()
    phases = ([phase_context_parallel] if args.four_chips
              else [phase_kernels, phase_serve, phase_train])
    for phase in phases:
        t = time.perf_counter()
        phase(args.seed)
        print(f"[time] {phase.__name__}: {time.perf_counter() - t:.1f}s",
              flush=True)
    print(f"[time] total {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
