"""Training cells: one ``Trainer``, seeded weights and data, one window.

Set-up (timed as ``setup_s``): the ``Trainer`` is built from the
configuration file on a one-chip mesh with the benchmark's data source
(token rows drawn from the seed), its weights replaced by the benchmark's
(``weights.make_weights``, GPT-2 style, in the configuration's parameter
dtype), then driven through its first three steps by ``dispatch`` — the
window's own call — which compiles the step. ``dispatch`` is one step as
``Trainer.run`` takes it (the trainer's data source, its batch placement,
its compiled step with its state), without ``Trainer.run``'s wait on every
step's metrics. The same object then runs in the window: steps are
dispatched about ``AHEAD_S`` seconds ahead of the one waited for, so the
chip stays fed while the host stands still, and each step's metrics are
waited for in order, a queue's depth late. When ``--seconds`` have passed
nothing more is sent; ``train_tokens_per_s`` counts the tokens of every
step sent over the time from the window's open to the return of the last
wait, so all of that work counts over all of that time, and no step is
cut at the close.

Correctness: once the window has closed and the trainer is freed, the
float32 reference that the configuration names (``ref_train``) takes the
same weights and the same three batches. Compared: each step's loss
(relative gap), the first gradient as the optimizer took it (from its
first moment after step 1, divided by ``1 - beta1``), and the parameters'
change over the three steps; the last two leaf by leaf, the gap between
the program's norm and the reference's over the larger of the reference's
norm of that leaf and of the median leaf, worst leaf. Leaves whose
reference gradient is under a thousandth of the median leaf's are left out
of both.
"""
from __future__ import annotations

import collections
import gc
import math
import shutil
import sys
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

import hostload
import peaks
import trace_reduce
import weights
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.data.pipeline import make_global_batch
from repro.distributed.sharding import sharding_rules
from repro.launch.mesh import make_local_mesh
from repro.models.model import model_specs
from repro.models.params import abstract_params
from repro.telemetry import Telemetry
from repro.train.trainer import Trainer

FIRST_STEPS = 3
# seconds of steps dispatched ahead of the one the window waits for
AHEAD_S = 4.0
# leaves whose reference gradient is below this share of the median leaf's
# move by round-off alone under Adam, and are not compared
QUIET_LEAF = 1e-3


def log(msg: str) -> None:
    print(f"[train] {msg}", file=sys.stderr, flush=True)


class SeededRows:
    """The benchmark's data: step ``i``'s batch is rows of token ids
    uniform over ``[1, vocab)`` drawn from (seed, i); no padding, every
    row different."""

    def __init__(self, seed: int, vocab: int, seq: int, batch: int):
        self.seed, self.vocab, self.seq, self.batch_size = seed, vocab, seq, batch

    def batch(self, step: int) -> dict:
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, self.seed >> 32,
                                     step])
        return {"tokens": rng.integers(1, self.vocab, (self.batch_size,
                                                       self.seq), np.int32)}


def host(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def leaf_norms(tree) -> list:
    return [float(np.linalg.norm(x)) for x in jax.tree.leaves(tree)]


def worst_leaf_gap(prog: list, ref: list, keep: list) -> float:
    """Largest ``|prog - ref| / max(ref, median ref)`` over kept leaves."""
    med = float(np.median([r for r, k in zip(ref, keep) if k]))
    return max(abs(p - r) / max(r, med, 1e-30)
               for p, r, k in zip(prog, ref, keep) if k)


def build(config: dict, seed: int, telemetry):
    cfg = ModelConfig(**config["model"])
    t = dict(config["train"])
    shape = ShapeConfig("bench", t.pop("seq_len"), t.pop("global_batch"),
                        "train")
    ckpt = tempfile.mkdtemp(prefix="bench_ckpt_")
    tcfg = TrainConfig(checkpoint_dir=ckpt, seed=seed & 0x7FFFFFFF, **t)
    data = SeededRows(seed, cfg.vocab_size, shape.seq_len, shape.global_batch)
    tr = Trainer(cfg, tcfg, shape, make_local_mesh(1), data=data,
                 telemetry=telemetry)
    shapes = abstract_params(model_specs(cfg), jnp.dtype(cfg.param_dtype))
    mine = weights.make_weights(shapes, seed, cfg.num_layers,
                                jnp.dtype(cfg.param_dtype))
    tr.params = jax.tree.map(lambda w, old: jax.device_put(w, old.sharding),
                             mine, tr.params)
    return tr, cfg, tcfg, shape, data


def readings(g_prog, d_prog, losses, g_ref, d_ref, ref_losses) -> dict:
    """The three numbers compared, from per-leaf norms and losses."""
    med = float(np.median(g_ref))
    keep = [g > QUIET_LEAF * med for g in g_ref]
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                            ref_losses)),
        "first_grad_gap": worst_leaf_gap(g_prog, g_ref, keep),
        "update_gap": worst_leaf_gap(d_prog, d_ref, keep),
        "leaves_compared": int(sum(keep)),
        "losses": [float(x) for x in losses],
        "ref_losses": [float(x) for x in ref_losses],
    }


def held(got: dict, limits: dict) -> dict:
    """Each number compared beside its limit."""
    return {name: {"value": got[name], "limit": limits[name],
                   "ok": got[name] <= limits[name]}
            for name in ("loss_gap", "first_grad_gap", "update_gap")}


def compare(ref, config, start, moment1, after, losses, data, beta1,
            control=False):
    """The first steps against the reference module ``ref``. With
    ``control``, also the readings of the reference computed with float8
    inputs in the program's place (the comparison's control)."""
    m, t = config["model"], config["train"]
    batches = [data.batch(i)["tokens"] for i in range(FIRST_STEPS)]

    def norms(start, g, after):
        return (leaf_norms(host(g)),
                leaf_norms(jax.tree.map(np.subtract, host(after), start)))

    ref_losses, ref_g, ref_after = ref.first_steps(start, batches, m, t)
    g_ref, d_ref = norms(start, ref_g, ref_after)
    g_prog = [n / (1 - beta1) for n in leaf_norms(moment1)]
    d_prog = leaf_norms(jax.tree.map(np.subtract, after, start))
    out = readings(g_prog, d_prog, losses, g_ref, d_ref, ref_losses)
    if control:
        low_losses, low_g, low_after = ref.first_steps(
            start, batches, m, t, quant="fp8")
        g_low, d_low = norms(start, low_g, low_after)
        out["control"] = readings(g_low, d_low, low_losses, g_ref, d_ref,
                                  ref_losses)
    return out


def dispatch(tr):
    """One step as ``Trainer.run`` takes it, without its wait: the batch
    from the trainer's data source, placed as the trainer places it,
    through the trainer's compiled step. Returns the step's metrics, still
    on the device. Called under the trainer's mesh and sharding rules."""
    with tr.telemetry.step_span("train_step", tr.step):
        batch = make_global_batch(tr.data.batch(tr.step), tr.b_sh)
        tr.params, tr.opt_state, metrics = tr.jitted(tr.params, tr.opt_state,
                                                     batch)
    tr.step += 1
    return metrics


def run(spec, *, seed: int, seconds: float, trace: bool, t_start: float,
        fault=None, control: bool = False):
    """``fault`` (tests and fault readings only) plants a fault under the
    timed path: called with the built trainer, before its first step."""
    config = spec["config"]
    telemetry = Telemetry(enabled=True, annotate=trace)
    tr, cfg, tcfg, shape, data = build(config, seed, telemetry)
    if fault is not None:
        fault(tr)
    start = host(tr.params)
    with tr.mesh, sharding_rules(tr.mesh, tr.rule_overrides):
        losses = [float(dispatch(tr)["loss"])]
        moment1 = host(tr.opt_state.m)
        for _ in range(FIRST_STEPS - 1):
            t_step = time.perf_counter()
            losses.append(float(dispatch(tr)["loss"]))
            t_step = time.perf_counter() - t_step
    after = host(tr.params)
    depth = max(2, math.ceil(AHEAD_S / t_step))
    tokens_per_step = shape.seq_len * shape.global_batch
    profile = trace_reduce.Profile(seconds) if trace else None
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s; losses {losses}; step {t_step:.4f}s, "
        f"{depth} dispatched ahead")

    steps = []
    pending = collections.deque()
    load = hostload.snapshot()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    if profile is not None:
        profile.open(t0)
    with tr.mesh, sharding_rules(tr.mesh, tr.rule_overrides):
        while time.perf_counter() < t_end:
            if profile is not None:
                profile.poll()
            pending.append(dispatch(tr))
            if len(pending) > depth:
                jax.block_until_ready(pending.popleft())
                steps.append(time.perf_counter())
        sent = len(steps) + len(pending)
        # time is up: nothing more is sent; every step sent is waited for
        while pending:
            jax.block_until_ready(pending.popleft())
            steps.append(time.perf_counter())
    memory_peak = int((jax.devices()[0].memory_stats() or {})
                      .get("peak_bytes_in_use", 0))
    t_close = steps[-1]
    e2e = {"train_tokens_per_s": len(steps) * tokens_per_step / (t_close - t0),
           "setup_s": setup_s}
    log(f"{len(steps)} steps in {t_close - t0:.3f}s ({sent} sent, "
        f"{sum(1 for t in steps if t <= t_end)} done by the close); {e2e}")
    gaps = np.diff([t0] + steps) * 1e3
    quartiles = (np.percentile(gaps, [0, 25, 50, 75, 100]).round(2).tolist()
                 if steps else [])
    log(f"step intervals (ms) min/p25/p50/p75/max {quartiles}; "
        f"host {hostload.since(load)}")
    host_spans = [(telemetry.tracer._origin + e["t"],
                   telemetry.tracer._origin + e["t"] + e["dur_s"], e["name"])
                  for e in telemetry.tracer.events]
    shutil.rmtree(tcfg.checkpoint_dir, ignore_errors=True)
    del tr, telemetry
    gc.collect()
    ctx = None
    if profile is not None:
        ctx = {"trace": profile.reduce(), "config": config,
               "window": profile.window, "host_spans": host_spans,
               "chip": peaks.peak(jax.devices()[0].device_kind),
               "step_bounds": [t0] + steps,
               "tokens_per_step": tokens_per_step,
               "chips": 1}

    t_ref = time.perf_counter()
    got = compare(spec["reference"], config, start, moment1, after, losses,
                  data, tcfg.beta1, control)
    log(f"reference {time.perf_counter() - t_ref:.1f}s: {got}")
    checks = held(got, config["limits"])
    out = {
        "scored": got,
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": len(steps) + FIRST_STEPS,
        "failed": 0,
        "end_to_end": e2e,
        "memory_peak_bytes": memory_peak,
        "checks": checks,
        "ctx": ctx,
    }
    if control:
        out["control_checks"] = held(got["control"], config["limits"])
    return out
