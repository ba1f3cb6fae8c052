"""Serving cells: one engine, one seeded traffic mix, one measured window.

Set-up (timed as ``setup_s``): weights drawn on the device from the seed,
the engine built from the configuration file, then one warm-up request per
distinct prompt length of the mix, each run alone, so that every prefill
program and every piece the program compiles per prompt length exists, and
one per power of two between the shortest prompt and the longest sequence,
so that each decode program the window can reach exists too.

Window: ``--seconds`` of wall time, driven through ``ServeEngine.submit``
and ``ServeEngine.tick`` from this one thread. ``backlog`` keeps at least
twice ``max_lanes`` requests unfinished, so the queue never drains;
``open_loop`` submits each request when it is due and, once the window has
closed, keeps to the schedule until every request due inside the window
has its first token (or a minute has passed); a backlog, once closed,
goes on with what it holds until its finished requests hold a
correctness sample (or a minute has passed). Every token is stamped by
``Request.on_token`` as it is emitted; a stamp after the close counts in
no metric.

Correctness: once the window has closed and the engine is freed, a sample
drawn from the seed of the finished requests, the longest among them, is
scored by the float32 reference (``ref_qwen2``) over its prompt and served
tokens. The number compared is the widest gap by which a served token's
reference logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import sys
import time
from collections import Counter

import numpy as np

import jax
import jax.numpy as jnp

import hostload
import peaks
import ref_qwen2
import trace_reduce
import traffic_gen
import weights
from repro.configs.base import ModelConfig, ServeConfig
from repro.models.model import model_specs
from repro.models.params import abstract_params
from repro.serve.engine import Request, ServeEngine
from repro.telemetry import Telemetry

# Requests due in an open-loop window are followed this long past its close.
GRACE_S = 60.0
# The correctness sample: requests until this many served tokens.
SAMPLE_TOKENS = 256
WARM_UID0 = 1 << 40


def log(msg: str) -> None:
    print(f"[serve] {msg}", file=sys.stderr, flush=True)


class CompileWatch:
    """Counts JAX traces and backend compiles while armed (the window)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.armed = False
        self.counts: Counter = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in self.EVENTS:
            self.counts[event.rsplit("/", 1)[-1]] += 1


def build(config: dict):
    cfg = ModelConfig(**config["model"])
    serve = ServeConfig(**config["serve"])
    return cfg, serve


def dims_of(config: dict) -> ref_qwen2.Dims:
    return ref_qwen2.Dims.from_model(config["model"],
                                     config["serve"]["max_seq"])


def peak_bytes() -> int:
    return int((jax.devices()[0].memory_stats() or {})
               .get("peak_bytes_in_use", 0))


class Stamps:
    """Per-request emission times, from ``Request.on_token``."""

    def __init__(self):
        self.tokens: dict[int, list] = {}
        self.times: dict[int, list] = {}

    def __call__(self, uid: int, tok: int) -> None:
        self.tokens.setdefault(uid, []).append(tok)
        self.times.setdefault(uid, []).append(time.perf_counter())


def warm_lengths(traffic: dict, max_seq: int) -> list[int]:
    sizes = traffic_gen.prompt_sizes(traffic)
    lo = min(sizes)
    hi = min(max(sizes) + traffic["output"]["max"], max_seq - 1)
    p = 1
    while p < lo:
        p *= 2
    powers = []
    while p <= hi:
        powers.append(p)
        p *= 2
    return sorted(set(sizes) | set(powers))


def warm_up(engine, traffic, max_seq, seed, vocab) -> int:
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    lengths = warm_lengths(traffic, max_seq)
    for i, n in enumerate(lengths):
        prompt = rng.integers(traffic_gen.FIRST_TOKEN_ID, vocab,
                              size=n).tolist()
        engine.submit(Request(WARM_UID0 + i, prompt, max_new_tokens=2))
        engine.run()
    return len(lengths)


def drive(engine, reqs, traffic, seconds, stamps, lanes, profile=None):
    """The measured window, then an open loop's follow-up. Returns the
    window's bounds and per-request submit records. ``profile``
    (``trace_reduce.Profile``) is polled between ticks."""
    sent: dict[int, float] = {}
    due: dict[int, float] = {}
    outcomes = engine.outcomes

    def submit(r, t0):
        due[r.index] = t0 + r.due_s
        engine.submit(Request(r.index, r.prompt, max_new_tokens=r.max_new_tokens,
                              on_token=stamps))
        sent[r.index] = time.perf_counter()

    def unfinished():
        return sum(1 for uid in sent if uid not in outcomes)

    t0 = time.perf_counter()
    t_end = t0 + seconds
    poll = profile.poll if profile is not None else (lambda: None)
    if profile is not None:
        profile.open(t0)
    i = 0
    if traffic["kind"] == "backlog":
        while True:
            poll()
            while unfinished() < 2 * lanes and i < len(reqs):
                submit(reqs[i], t0)
                i += 1
            if time.perf_counter() >= t_end:
                break
            engine.tick()
        # past the close, with nothing new submitted, until the finished
        # requests hold a correctness sample's worth of tokens
        deadline = t_end + GRACE_S
        while (sum(len(engine.finished[u]) for u in sent
                   if u in engine.finished) < SAMPLE_TOKENS
               and unfinished() and time.perf_counter() < deadline):
            engine.tick()
        return t0, t_end, due, sent

    in_window = [r.index for r in reqs if r.due_s < seconds]
    deadline = t_end + GRACE_S
    while True:
        poll()
        now = time.perf_counter()
        while i < len(reqs) and t0 + reqs[i].due_s <= now:
            submit(reqs[i], t0)
            i += 1
        if now >= t_end and (now >= deadline or all(
                u in stamps.times or u in outcomes for u in in_window)):
            break
        if unfinished():
            engine.tick()
        elif i < len(reqs):
            time.sleep(max(0.0, t0 + reqs[i].due_s - time.perf_counter()))
        else:
            break
    return t0, t_end, due, sent


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile; an empty sample reads inf."""
    if len(values) == 0:
        return float("inf")
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(kind, reqs, t0, t_end, due, stamps, seconds) -> dict:
    out = {}
    times = stamps.times
    n_out = sum(1 for ts in times.values() for t in ts if t0 <= t <= t_end)
    out["output_tokens_per_s"] = n_out / seconds
    gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])
            if t0 <= a and b <= t_end]
    out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    if kind == "open_loop":
        ttft = [times[u][0] - due[u] if u in times else float("inf")
                for u in due if due[u] < t_end]
        out["ttft_p90_ms"] = percentile(ttft, 90) * 1e3
        out["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
        out["ttft_samples"] = len(ttft)
    return out


def pauses(stamps, t0, t_end, host_spans, top: int = 3) -> dict:
    """Where a run lost time, for its log: the longest stretches of the
    window in which no token was emitted, and the longest ``serve_tick``
    spans and gaps between them."""
    emitted = sorted(t for ts in stamps.times.values() for t in ts
                     if t0 <= t <= t_end)
    ticks = sorted((s, e) for s, e, n in host_spans
                   if n == "serve_tick" and t0 <= s and e <= t_end)

    def longest(values):
        return sorted((round(1e3 * float(v), 1) for v in values),
                      reverse=True)[:top]

    return {"between_tokens": longest(np.diff([t0] + emitted + [t_end])),
            "in_tick": longest(e - s for s, e in ticks),
            "between_ticks": longest(b[0] - a[1]
                                     for a, b in zip(ticks, ticks[1:]))}


def choose_sample(finished: dict, prompts: dict, seed: int) -> list[int]:
    """Drawn from the seed: the longest finished request, then others at
    random until ``SAMPLE_TOKENS`` served tokens."""
    uids = sorted(finished)
    if not uids:
        return []
    longest = max(uids, key=lambda u: len(prompts[u]) + len(finished[u]))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
    pick, total = [longest], len(finished[longest])
    for u in rng.permutation([u for u in uids if u != longest]).tolist():
        if total >= SAMPLE_TOKENS:
            break
        pick.append(u)
        total += len(finished[u])
    return pick


def score_sample(params, sample, prompts, finished, dims,
                 control=False) -> dict:
    """The widest served-token gap over the sample. With ``control``, also
    the gap of the tokens that the reference computed in float8 puts
    first, at the same positions (the comparison's control)."""
    out = {"gap": 0.0, "tokens": 0, "requests": len(sample)}
    if control:
        out["control_gap"] = 0.0
    for uid in sample:
        served = finished[uid]
        ref = ref_qwen2.score(params, prompts[uid], served, dims)
        out["gap"] = max(out["gap"],
                         float(ref_qwen2.served_gap(ref, served).max()))
        out["tokens"] += len(served)
        if control:
            low = ref_qwen2.score(params, prompts[uid], served, dims, "fp8")
            out["control_gap"] = max(out["control_gap"], float(
                ref_qwen2.served_gap(ref, low.argmax(-1)).max()))
    return out


def run(spec, *, seed: int, seconds: float, trace: bool, t_start: float,
        control: bool = False):
    config, traffic = spec["config"], spec["traffic"]
    cfg, serve = build(config)
    shapes = abstract_params(model_specs(cfg), jnp.dtype(cfg.param_dtype))
    params = weights.make_weights(shapes, seed, cfg.num_layers,
                                  jnp.dtype(cfg.param_dtype))
    telemetry = Telemetry(enabled=True, annotate=trace)
    engine = ServeEngine(cfg, params, serve=serve, telemetry=telemetry)
    reqs = traffic_gen.schedule(traffic, seed, cfg.vocab_size, seconds)
    n_warm = warm_up(engine, traffic, serve.max_seq, seed, cfg.vocab_size)
    watch = CompileWatch()
    compiles0 = dict(engine.stats()["xla_compiles"])
    stamps = Stamps()
    profile = trace_reduce.Profile(seconds) if trace else None
    setup_s = time.perf_counter() - t_start
    due_in = [r for r in reqs if r.due_s < seconds]
    log(f"set-up {setup_s:.3f}s ({n_warm} warm-up requests); "
        f"window {seconds}s, {len(reqs)} requests scheduled, "
        f"{len(due_in)} due in it with "
        f"{sum(len(r.prompt) for r in due_in)} prompt tokens")

    watch.armed = True
    load = hostload.snapshot()
    t0, t_end, due, sent = drive(engine, reqs, traffic, seconds, stamps,
                                 serve.max_lanes, profile)
    watch.armed = False
    compiles = dict(engine.stats()["xla_compiles"])
    memory_peak = peak_bytes()
    log(f"window compiles: program {compiles0} -> {compiles}; "
        f"jax events {dict(watch.counts)}; host {hostload.since(load)}")

    outcomes = dict(engine.outcomes)
    finished = dict(engine.finished)
    # the program's lifelines and spans, moved onto perf_counter
    origin = telemetry.tracer._origin
    admitted = {}
    for u in sent:
        line = telemetry.flight.lifeline(u)
        for ev in (line.events if line is not None else []):
            if ev["kind"] == "admit":
                admitted.setdefault(u, origin + ev["t"])
    host_spans = [(origin + e["t"], origin + e["t"] + e["dur_s"], e["name"])
                  for e in telemetry.tracer.events]
    log(f"longest pauses (ms) {pauses(stamps, t0, t_end, host_spans)}")
    del engine, telemetry
    gc.collect()

    ctx = None
    if profile is not None:
        t_read = time.perf_counter()
        ctx = {"trace": profile.reduce(), "window": profile.window}
        log(f"trace of {ctx['trace'].window_s:.3f}s read in "
            f"{time.perf_counter() - t_read:.1f}s")

    e2e = end_to_end(traffic["kind"], reqs, t0, t_end, due, stamps, seconds)
    e2e["setup_s"] = setup_s
    if traffic["kind"] == "open_loop":
        lateness = traffic_gen.lateness([due[u] for u in sent],
                                        [sent[u] for u in sent])
        log(f"ttft over {e2e['ttft_samples']} requests due in the window; "
            f"generator lateness {lateness}")
        attempted = [u for u in due if due[u] < t_end]
        failed = [u for u in attempted
                  if u not in stamps.times
                  or outcomes.get(u, "finished") != "finished"]
    else:
        attempted = [u for u in sent if sent[u] < t_end]
        failed = [u for u in attempted
                  if outcomes.get(u, "finished") != "finished"]
    log(f"{len(attempted)} attempted, {len(failed)} failed, "
        f"{len(finished)} finished; end to end {e2e}")

    prompts = {r.index: r.prompt for r in reqs}
    in_run = {u: finished[u] for u in finished if u in prompts}
    sample = choose_sample(in_run, prompts, seed)
    t_ref = time.perf_counter()
    scored = score_sample(params, sample, prompts, in_run, dims_of(config),
                          control)
    log(f"reference over {scored['requests']} requests, {scored['tokens']} "
        f"served tokens: {time.perf_counter() - t_ref:.1f}s")
    limit = config["limits"]["served_logit_gap"]
    checks = {
        "served_logit_gap": {"value": scored["gap"], "limit": limit,
                             "ok": scored["gap"] <= limit},
        "served_tokens_compared": {"value": scored["tokens"], "limit": 1,
                                   "ok": scored["tokens"] >= 1},
    }
    if ctx is not None:
        ctx.update({
            "config": config, "host_spans": host_spans,
            "chip": peaks.peak(jax.devices()[0].device_kind),
            "prompts": prompts, "due": due, "times": stamps.times,
            "admitted": admitted,
        })
    out = {
        "scored": scored,
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": len(attempted),
        "failed": len(failed),
        "end_to_end": e2e,
        "memory_peak_bytes": memory_peak,
        "checks": checks,
        "ctx": ctx,
    }
    if control:
        out["control_checks"] = {"served_logit_gap": {
            "value": scored["control_gap"], "limit": limit,
            "ok": scored["control_gap"] <= limit}}
    return out
