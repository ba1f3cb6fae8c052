"""Fault-tolerant training driver.

Wires together the substrate: sharded params/optimizer (distributed/
sharding.py), the jitted train step (train/train_step.py), the data pipeline
(data/pipeline.py), async checkpointing (checkpoint/checkpointer.py) and the
fault-tolerance control plane (distributed/fault_tolerance.py).

Lifecycle
---------
    trainer = Trainer(cfg, tcfg, shape, mesh=...)   # init or auto-restore
    trainer.run(num_steps)                          # step loop

Per step: build batch -> place sharded -> jitted step (donated state) ->
metrics; every ``checkpoint_every`` steps an async checkpoint is published
atomically. ``HeartbeatMonitor`` tracks per-host step times (this container
is single-host, so beats are synthesized for the mesh's logical hosts) and
a ``FailureInjector`` can kill hosts at chosen steps — the trainer then
checkpoints (if the failing step allows), re-plans the largest runnable mesh
(``ElasticPlan``: TP axis intact, DP shrunk to a power of two), rebuilds
shardings, restores the mesh-agnostic checkpoint onto the new mesh, re-jits
and continues. The elastic integration test exercises exactly this path.
"""
from __future__ import annotations

import logging
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.configs.registry import batch_specs
from repro.data.pipeline import SyntheticLM, make_global_batch
from repro.distributed.fault_tolerance import (
    ElasticPlan,
    FailureInjector,
    HeartbeatMonitor,
)
from repro.distributed.sharding import (
    apply_seq_sharding_config,
    sharding_rules,
    shardings_for,
)
from repro.models.model import model_specs
from repro.models.params import abstract_params, init_params, logical_axes
from repro.optim.adamw import AdamWState, adamw_init
from repro.optim.schedules import warmup_cosine
from repro.train.train_step import make_train_step

log = logging.getLogger("repro.trainer")


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainConfig,
        shape: ShapeConfig,
        mesh: Mesh,
        *,
        data=None,
        rule_overrides: Optional[dict] = None,
        monitor: Optional[HeartbeatMonitor] = None,
        injector: Optional[FailureInjector] = None,
        lr_fn: Optional[Callable] = None,
        telemetry=None,
    ):
        # Telemetry is caller-owned and optional; default = no-op pair, so
        # the step loop's spans/metrics cost nothing unless a Telemetry
        # object is passed in (launch scripts, tests, benchmarks).
        from repro.telemetry import Telemetry

        if telemetry is None:
            telemetry = Telemetry(enabled=False)
        self.telemetry = telemetry
        if telemetry.enabled:
            from repro.kernels import dispatch
            from repro.telemetry.metrics import LATENCY_BUCKETS

            dispatch.set_metrics(telemetry.metrics)
            telemetry.stamp_provenance(cfg, tcfg)
            r = telemetry.metrics
            self._step_hist = r.histogram(
                "train_step_seconds", help="wall time per optimizer step",
                buckets=LATENCY_BUCKETS)
            self._gauges = {
                name: r.gauge(f"train_{name}", help=f"last step's {name}")
                for name in ("loss", "ce", "grad_norm", "lr")
            }
        self.rule_overrides = rule_overrides or {}
        cfg = apply_seq_sharding_config(cfg, mesh, self.rule_overrides, log=log)
        self.cfg, self.tcfg, self.shape = cfg, tcfg, shape
        self.data = data or SyntheticLM(
            vocab_size=cfg.vocab_size,
            seq_len=shape.seq_len,
            global_batch=shape.global_batch,
            seed=tcfg.seed,
        )
        self.lr_fn = lr_fn or warmup_cosine(
            tcfg.learning_rate, tcfg.warmup_steps, tcfg.total_steps
        )
        self.ckpt = Checkpointer(tcfg.checkpoint_dir, keep=tcfg.keep_checkpoints)
        self.injector = injector
        self.step = 0
        self.metrics_history: list[dict] = []
        self._install_mesh(mesh, restore=True)
        hosts = [f"host{i}" for i in range(max(mesh.devices.size // 8, 1))]
        self.monitor = monitor or HeartbeatMonitor(hosts, timeout_s=600.0)

    # -- mesh / state installation -------------------------------------------
    def _install_mesh(self, mesh: Mesh, restore: bool) -> None:
        """(Re)build shardings + jitted step on ``mesh``; init or restore."""
        self.mesh = mesh
        cfg, tcfg = self.cfg, self.tcfg
        specs = model_specs(cfg)
        axes = logical_axes(specs)
        params_abs = abstract_params(specs, dtype=jnp.dtype(cfg.param_dtype))

        self._warm_attention_plans()
        with mesh, sharding_rules(mesh, self.rule_overrides):
            self.p_sh = shardings_for(mesh, axes, params_abs)
            bspecs, baxes = batch_specs(cfg, self.shape)
            self.b_sh = shardings_for(mesh, baxes, bspecs)
            self.o_sh = AdamWState(
                step=NamedSharding(mesh, P()), m=self.p_sh, v=self.p_sh
            )
            step_fn = make_train_step(cfg, tcfg, self.lr_fn)
            self.jitted = jax.jit(
                step_fn,
                in_shardings=(self.p_sh, self.o_sh, self.b_sh),
                out_shardings=(self.p_sh, self.o_sh, NamedSharding(mesh, P())),
                donate_argnums=(0, 1),
            )
            if self.telemetry.enabled:
                # xla_compiles_total{program="train_step"}: a steady run
                # compiles once; growth mid-run means a shape leak (batch /
                # mesh churn) — see telemetry/accounting.py.
                from repro.telemetry import accounting as acct

                acct.set_metrics(self.telemetry.metrics)
                acct.install_compile_listener()
                self.jitted = acct.XLAAccounting(
                    self.telemetry.metrics, self.telemetry.tracer
                ).wrap(self.jitted, "train_step")

            latest = self.ckpt.latest_step() if restore else None
            if latest is not None:
                log.info("restoring step %d onto mesh %s", latest, mesh.shape)
                target = {
                    "params": params_abs,
                    "opt": AdamWState(
                        step=jax.ShapeDtypeStruct((), jnp.int32),
                        m=abstract_params(specs, dtype=jnp.float32),
                        v=abstract_params(specs, dtype=jnp.float32),
                    ),
                }
                sh = {"params": self.p_sh, "opt": self.o_sh}
                state = self.ckpt.restore(latest, target, sh)
                self.params, self.opt_state = state["params"], state["opt"]
                self.step = latest
            else:
                key = jax.random.PRNGKey(tcfg.seed)
                init = jax.jit(
                    lambda k: init_params(
                        specs, k, dtype=jnp.dtype(cfg.param_dtype)
                    ),
                    out_shardings=self.p_sh,
                )
                self.params = init(key)
                opt = jax.jit(adamw_init, out_shardings=self.o_sh)
                self.opt_state = opt(self.params)

    def _warm_attention_plans(self) -> None:
        """Measured-autotune warmup: resolve the train-shape kernel plan
        before the step is jitted, so trace-time dispatch (models/
        attention.py) hits the registry instead of tuning mid-trace. A
        previously measured plan (in-memory or on disk, including the
        ``autotune_cache`` override) short-circuits re-measurement — this
        runs again on every elastic mesh re-install."""
        cfg = self.cfg
        if (not cfg.autotune
                or cfg.attention_impl != "spectral_shift_fused"
                or cfg.attention_backend != "auto"):
            # A forced backend never consults the registry — measuring
            # would be pure wasted startup time.
            return
        from repro.distributed.sharding import seq_axis_sharded

        if seq_axis_sharded(self.mesh, self.rule_overrides):
            # Context-parallel cells resolve a seq_shards key and route
            # through the shard_map driver; the single-device autotune
            # harness cannot reproduce that program, so leave the heuristic
            # (or a pre-registered sharded plan) in charge.
            log.info("sequence axis is sharded: skipping autotune warmup")
            return
        from repro.kernels import dispatch

        if cfg.autotune_cache:
            dispatch.set_cache_path(cfg.autotune_cache)
            dispatch.load_cache()
        key = dispatch.make_key(
            self.shape.seq_len, cfg.num_landmarks, cfg.resolved_head_dim,
            cfg.compute_dtype, cfg.is_decoder_only,
        )
        with self.telemetry.span("plan_resolution", n=key.n):
            plan = dispatch.get_plan(key)
            if plan.source == "heuristic":  # nothing measured for this shape
                plan = dispatch.autotune(
                    self.shape.seq_len,
                    cfg.num_landmarks,
                    cfg.resolved_head_dim,
                    dtype=cfg.compute_dtype,
                    causal=cfg.is_decoder_only,
                )
        log.info(
            "attention plan for n=%d (%s): impl=%s block_n=%d",
            self.shape.seq_len, plan.source, plan.impl, plan.block_n,
        )

    # -- checkpoint ----------------------------------------------------------
    def save(self, blocking: bool = False) -> None:
        self.ckpt.save(
            self.step,
            {"params": self.params, "opt": self.opt_state},
            blocking=blocking,
        )

    # -- failure handling ------------------------------------------------------
    def _handle_failure(self, dead: list[str]) -> None:
        """Simulated elastic recovery: drop dead hosts' chips, re-plan, restore."""
        log.warning("step %d: hosts failed: %s — elastic restart", self.step, dead)
        self.ckpt.wait()
        alive_hosts = [h for h in self.monitor.hosts if h not in dead]
        chips_per_host = max(self.mesh.devices.size // len(self.monitor.hosts), 1)
        alive_chips = chips_per_host * len(alive_hosts)
        model_par = self.mesh.shape.get("model", 1)
        plan = ElasticPlan.plan(
            alive_chips, model_par, max_data=self.mesh.shape.get("data", 1)
        )
        flat = sorted(self.mesh.devices.flat, key=lambda d: d.id)
        keep = np.array(flat[: plan.data * plan.model]).reshape(
            plan.data, plan.model
        )
        new_mesh = Mesh(keep, ("data", "model"))
        for h in dead:
            del self.monitor.hosts[h]
        # State on dead chips is lost: re-install from the last checkpoint.
        self._install_mesh(new_mesh, restore=True)

    # -- step loop -------------------------------------------------------------
    def run(self, num_steps: int, log_every: int = 10) -> list[dict]:
        cfg, tcfg = self.cfg, self.tcfg
        end = self.step + num_steps
        with self.mesh, sharding_rules(self.mesh, self.rule_overrides):
            while self.step < end:
                if self.injector:
                    dead = self.injector.failures_at(self.step)
                    if dead:
                        self._handle_failure(dead)
                t0 = time.perf_counter()
                with self.telemetry.step_span("train_step", self.step):
                    host_batch = self.data.batch(self.step)
                    batch = make_global_batch(host_batch, self.b_sh)
                    self.params, self.opt_state, metrics = self.jitted(
                        self.params, self.opt_state, batch
                    )
                    metrics = {
                        k: float(v)
                        for k, v in metrics.items() if jnp.ndim(v) == 0
                    }
                dt = time.perf_counter() - t0
                metrics["step"] = self.step
                metrics["step_time_s"] = dt
                self.metrics_history.append(metrics)
                if self.telemetry.enabled:
                    self._step_hist.observe(dt)
                    for name, g in self._gauges.items():
                        if name in metrics:
                            g.set(metrics[name])
                for h in self.monitor.hosts:
                    self.monitor.beat(h, dt)
                stragglers = self.monitor.stragglers()
                if stragglers:
                    log.warning("stragglers detected: %s", stragglers)
                self.step += 1
                if self.step % tcfg.checkpoint_every == 0:
                    self.save(blocking=False)
                if self.step % log_every == 0 or self.step == end:
                    log.info(
                        "step %d loss=%.4f ce=%.4f %.2fs",
                        self.step, metrics.get("loss", float("nan")),
                        metrics.get("ce", float("nan")), dt,
                    )
        self.ckpt.wait()
        return self.metrics_history
