"""Observability layer: flight-recorder lifelines and bounds, Chrome
trace export validity, XLA recompile accounting, numerics probes, and the
perf-regression gate's tolerance policy."""
from __future__ import annotations

import dataclasses
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.regress import (
    DEFAULT_WALL_TOL,
    Policy,
    compare_cells,
    metric_policy,
)
from repro.configs.base import ServeConfig, reduced
from repro.configs.registry import get_config
from repro.models.model import model_specs
from repro.models.params import init_params
from repro.serve.engine import Request, ServeEngine
from repro.telemetry import (
    FlightRecorder,
    MetricsRegistry,
    NullFlightRecorder,
    NumericsProbe,
    Telemetry,
    XLAAccounting,
    chrome_trace,
    config_hash,
    git_sha,
    provenance,
    validate_trace,
    write_chrome_trace,
)


@pytest.fixture(scope="module")
def qwen():
    cfg = dataclasses.replace(
        reduced(get_config("qwen2-7b")),
        capacity_factor=100.0,
        decode_streaming="frozen",
    )
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, n, seed=0, lo=4, hi=24, max_new=8):
    rng = np.random.default_rng(seed)
    return [
        Request(
            u,
            rng.integers(3, cfg.vocab_size, int(rng.integers(lo, hi))).tolist(),
            max_new_tokens=max_new,
        )
        for u in range(n)
    ]


BASE = ServeConfig(max_lanes=2, max_seq=64, block_size=8, telemetry=True)


@pytest.fixture(scope="module")
def served(qwen):
    """One telemetry-on paged engine run shared by the lifeline/trace
    tests: 3 requests through admit -> prefill -> decode -> finish."""
    cfg, params = qwen
    eng = ServeEngine(cfg, params, serve=BASE)
    for r in _requests(cfg, 3, seed=1, lo=8, hi=20, max_new=6):
        eng.submit(r)
    out = eng.run()
    return eng, out


# ==========================================================================
# FlightRecorder bounds (unit)
# ==========================================================================
class TestFlightRecorder:
    def test_decode_runs_coalesce(self):
        fl = FlightRecorder()
        fl.record(7, "submit", prompt_len=5)
        for tick in range(10, 15):
            fl.record(7, "decode", tick=tick, pos=tick - 4)
        line = fl.lifeline(7)
        # five consecutive ticks -> ONE run event, O(1) steady-state memory
        assert line.kinds() == ["submit", "decode"]
        run = line.events[-1]
        assert (run["tick0"], run["tick1"]) == (10, 14)
        assert (run["pos0"], run["pos1"]) == (6, 10)
        assert run["n"] == 5
        # a scheduling gap breaks the run
        fl.record(7, "decode", tick=20, pos=11)
        assert line.kinds() == ["submit", "decode", "decode"]
        assert line.events[-1]["tick0"] == 20

    def test_ring_buffer_eviction_and_event_cap(self):
        reg = MetricsRegistry()
        fl = FlightRecorder(max_requests=4, max_events=8, registry=reg)
        for uid in range(10):
            fl.record(uid, "submit", prompt_len=1)
        # FIFO ring: only the newest 4 lifelines survive, evictions counted
        assert [ln.uid for ln in fl.lifelines()] == [6, 7, 8, 9]
        assert fl.summary()["evicted_requests"] == 6
        # per-lifeline cap: events beyond max_events drop and count instead
        # of growing (non-consecutive ticks so nothing coalesces)
        for tick in range(0, 40, 2):
            fl.record(9, "decode", tick=tick, pos=tick)
        line = fl.lifeline(9)
        assert len(line.events) == 8
        assert line.dropped == 20 - 7
        assert fl.summary()["dropped_events"] == line.dropped
        snap = reg.snapshot()
        assert snap["flight_events_dropped_total"]["value"] == line.dropped

    def test_counter_samples_bounded(self):
        fl = FlightRecorder(max_counter_samples=16)
        for i in range(100):
            fl.counter_sample("queue_depth", i)
        samples = fl.counters["queue_depth"]
        assert len(samples) == 16
        assert samples[-1][1] == 99.0

    def test_null_recorder_is_inert(self):
        fl = NullFlightRecorder()
        fl.record(1, "submit")
        fl.counter_sample("x", 1.0)
        assert not fl.enabled and fl.lifelines() == []
        assert fl.dump_jsonl(io.StringIO()) == 0


# ==========================================================================
# Engine lifelines + Chrome trace export
# ==========================================================================
class TestLifelines:
    def test_lifeline_complete(self, served):
        eng, _ = served
        for uid in range(3):
            kinds = eng.telemetry.flight.lifeline(uid).kinds()
            assert kinds[0] == "submit"
            assert kinds[-1] == "finish"
            i = {k: kinds.index(k) for k in
                 ("submit", "admit", "prefill_start", "prefill_end",
                  "decode")}
            assert (i["submit"] < i["admit"] < i["prefill_start"]
                    < i["prefill_end"] < i["decode"])

    def test_prefill_bucket_recorded(self, served):
        eng, _ = served
        events = eng.telemetry.flight.lifeline(0).events
        start = next(e for e in events if e["kind"] == "prefill_start")
        # the padding bucket is the shape that decides which XLA program ran
        assert start["bucket"] >= 8 and start["bucket"] % 8 == 0

    def test_trace_schema_valid(self, served, tmp_path):
        eng, _ = served
        path = tmp_path / "serve.json"
        n = write_chrome_trace(path, eng.telemetry, meta={"case": "test"})
        trace = json.loads(path.read_text())
        assert n == len(trace["traceEvents"]) > 0
        assert trace["metadata"]["trace_schema"] == "repro-chrome-trace-v1"
        assert trace["metadata"]["case"] == "test"
        # balanced B/E per track, monotonic timestamps — Perfetto's contract
        assert validate_trace(trace) == []
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"queued", "prefill", "decode"} <= names
        # one request track per lifeline on the requests pid
        req_tids = {e["tid"] for e in trace["traceEvents"]
                    if e["pid"] == 1 and e["ph"] == "B"}
        assert len(req_tids) == 3

    def test_counter_tracks_exported(self, served):
        eng, _ = served
        trace = chrome_trace(eng.telemetry)
        counters = {e["name"] for e in trace["traceEvents"]
                    if e["ph"] == "C"}
        assert {"queue_depth", "pool_blocks_used",
                "pool_fragmentation"} <= counters

    def test_jsonl_carries_flight_and_provenance(self, served, tmp_path):
        eng, _ = served
        path = tmp_path / "telemetry.jsonl"
        eng.telemetry.dump_jsonl(str(path))
        lines = [json.loads(x) for x in path.read_text().splitlines()]
        head = lines[0]
        assert head["kind"] == "meta"
        assert head["git_sha"] and head["jax"] == jax.__version__
        assert "config_hash" in head
        flights = [x for x in lines if x["kind"] == "flight"]
        assert len(flights) == 3
        assert flights[0]["events"][0]["kind"] == "submit"

    def test_preempted_lifeline_complete(self, qwen, tmp_path):
        """Pool pressure forces preemption; the victim's lifeline shows the
        full round-trip (admit -> preempt -> requeue -> re-prefill ->
        finish) and the trace still validates."""
        cfg, params = qwen
        serve = dataclasses.replace(BASE, max_lanes=3, num_blocks=12)
        eng = ServeEngine(cfg, params, serve=serve)
        for r in _requests(cfg, 4, seed=2, lo=20, hi=21, max_new=30):
            eng.submit(r)
        eng.run()
        assert eng.stats()["preemptions"] > 0
        victims = [ln for ln in eng.telemetry.flight.lifelines()
                   if "preempt" in ln.kinds()]
        assert victims
        kinds = victims[0].kinds()
        p = kinds.index("preempt")
        assert "admit" in kinds[:p]
        assert kinds[p + 1] == "requeue"
        rest = kinds[p + 2:]
        assert "prefill_start" in rest and rest[-1] == "finish"
        path = tmp_path / "preempt.json"
        write_chrome_trace(path, eng.telemetry)
        trace = json.loads(path.read_text())
        assert validate_trace(trace) == []
        instants = {e["name"] for e in trace["traceEvents"]
                    if e["ph"] == "i"}
        assert "preempt" in instants


# ==========================================================================
# XLA program accounting
# ==========================================================================
class TestAccounting:
    def test_recompile_detector(self):
        reg = MetricsRegistry()
        acct = XLAAccounting(reg)
        fn = jax.jit(lambda x: x * 2.0)
        wrapped = acct.wrap(fn, "toy")
        wrapped(jnp.ones(4))
        assert acct.compiles("toy") == 1
        # silent across 100 steady-state calls: same shape, no re-jit
        for _ in range(100):
            wrapped(jnp.ones(4))
        assert acct.compiles("toy") == 1
        # a forced re-jit (new input shape) fires exactly once
        wrapped(jnp.ones(8))
        assert acct.compiles("toy") == 2
        snap = reg.snapshot()
        assert snap["xla_compiles_total"]["program=toy"]["value"] == 2

    def test_wrap_without_probe_is_identity(self):
        def plain(x):
            return x

        acct = XLAAccounting(MetricsRegistry())
        assert acct.wrap(plain, "noprobe") is plain

    def test_engine_steady_state_compiles(self, served, qwen):
        """The served run's decode program compiled for its view buckets
        and then stayed flat: a fresh request over the same shapes adds
        ZERO compiles (the xla_compiles_total stability contract)."""
        eng, _ = served
        cfg, _ = qwen
        before = dict(eng.stats()["xla_compiles"])
        assert before["prefill"] >= 1 and before["decode_tick"] >= 1
        (req,) = _requests(cfg, 1, seed=1, lo=8, hi=20, max_new=6)
        eng.submit(Request(99, list(req.prompt), req.max_new_tokens))
        eng.run()
        assert eng.stats()["xla_compiles"] == before


# ==========================================================================
# Numerics probes
# ==========================================================================
class TestNumericsProbe:
    def test_catches_injected_inf_in_landmark_stats(self):
        reg = MetricsRegistry()
        probe = NumericsProbe(reg)
        m = np.zeros((2, 16), np.float32)  # (lanes, landmarks) m-stats shape
        assert probe.check("landmark_m", m) == 0
        assert probe.last_bad is None
        m[1, 3] = np.inf
        assert probe.check("landmark_m", m) == 1
        assert probe.last_bad == "landmark_m"
        l = np.ones((2, 16), np.float32)
        l[0, 0] = np.nan
        l[1, 5] = np.nan
        assert probe.check("landmark_l", l) == 2
        snap = reg.snapshot()
        assert snap["numerics_nonfinite_total"]["site=landmark_m"]["value"] == 1
        assert snap["numerics_nonfinite_total"]["site=landmark_l"]["value"] == 2
        assert snap["numerics_checks_total"]["value"] == 3

    def test_skips_integer_arrays(self):
        probe = NumericsProbe(MetricsRegistry())
        assert probe.check("tokens", np.arange(8)) == 0

    def test_engine_probe_runs_clean(self, qwen):
        """With the probe on every 2nd tick, a healthy run reports zero
        non-finite values in logits and (m, l) stats — the frozen decode
        state uses a finite NEG_INF sentinel by design."""
        cfg, params = qwen
        serve = dataclasses.replace(BASE, numerics_probe_every=2)
        eng = ServeEngine(cfg, params, serve=serve)
        for r in _requests(cfg, 2, seed=3, lo=8, hi=16, max_new=6):
            eng.submit(r)
        eng.run()
        snap = eng.telemetry.metrics.snapshot()
        assert snap["numerics_checks_total"]["value"] > 0
        assert "numerics_nonfinite_total" not in snap or all(
            s["value"] == 0
            for s in snap["numerics_nonfinite_total"].values()
        )


# ==========================================================================
# Provenance
# ==========================================================================
def test_provenance_stamp():
    sha = git_sha()
    assert sha == "unknown" or len(sha) == 40
    p = provenance(BASE)
    assert p["jax"] == jax.__version__
    assert len(p["config_hash"]) == 12
    # the hash tracks config content, not object identity
    assert config_hash(BASE) == config_hash(dataclasses.replace(BASE))
    assert config_hash(BASE) != config_hash(
        dataclasses.replace(BASE, max_lanes=7))


def test_git_sha_degrades_on_hung_git(monkeypatch):
    """A git that times out (TimeoutExpired) must degrade to $GITHUB_SHA /
    "unknown" like every other failure mode — provenance is never the
    reason an artifact fails to write."""
    import subprocess

    def hang(*a, **k):
        raise subprocess.TimeoutExpired(cmd=a[0], timeout=k.get("timeout", 10))

    monkeypatch.setattr(subprocess, "run", hang)
    monkeypatch.delenv("GITHUB_SHA", raising=False)
    git_sha.cache_clear()
    try:
        assert git_sha() == "unknown"
        monkeypatch.setenv("GITHUB_SHA", "f" * 40)
        git_sha.cache_clear()
        assert git_sha() == "f" * 40
    finally:
        git_sha.cache_clear()  # don't poison the per-process cache


# ==========================================================================
# Perf-regression gate
# ==========================================================================
class TestRegressGate:
    CELLS = {
        "paged|batched|prompt32": {
            "ttft_s": 0.02, "ttft_ticks": 1.0, "tok_per_s": 250.0,
            "hbm_bytes": 1.5e7, "note": "not a number-gated field",
        },
        "paged|batched|lanes4": {"tok_per_s": 400.0, "drift_err": 1e-4},
    }

    def test_policy_classification(self):
        assert metric_policy("ttft_s").direction == "lower"
        assert metric_policy("ttft_s").wall
        assert metric_policy("tok_per_s").direction == "higher"
        assert metric_policy("hbm_bytes") == Policy("both", 0.01, 0.5)
        assert not metric_policy("xla_cost_bytes").wall
        assert metric_policy("drift_err").direction == "lower"
        assert metric_policy("finished") is None  # informational
        # prefix-cache cells: warm TTFT gates like a latency, the speedup
        # rule still wins for ratios, and the hit rate is pinned
        assert metric_policy("ttft_warm_s") == Policy(
            "lower", DEFAULT_WALL_TOL, 2e-3, wall=True)
        assert metric_policy("ttft_warm_speedup").direction == "higher"
        assert metric_policy("prefix_hit_rate") == Policy("both", 0.01, 0.01)
        # chaos cell: seeded-schedule counters are pinned, the surviving
        # goodput fraction gates like a throughput
        assert metric_policy("chaos_injections") == Policy("both", 0.01, 0.5)
        assert metric_policy("quarantines") == Policy("both", 0.01, 0.5)
        assert metric_policy("goodput_frac") == Policy(
            "higher", DEFAULT_WALL_TOL, 0.0, wall=True)
        assert metric_policy("goodput_tok_per_s").direction == "higher"
        assert metric_policy("goodput_tok_per_s").wall

    def test_identical_cells_pass(self):
        violations, compared = compare_cells(
            "serve", self.CELLS, json.loads(json.dumps(self.CELLS)))
        assert violations == []
        assert compared == 6

    def test_doctored_regression_fails(self):
        doctored = json.loads(json.dumps(self.CELLS))
        cell = doctored["paged|batched|prompt32"]
        cell["ttft_s"] *= 2.0        # 2x slower: outside the 0.75 band
        cell["tok_per_s"] /= 2.0     # 2x less throughput
        cell["hbm_bytes"] *= 1.05    # structural drift beyond +-1%
        violations, _ = compare_cells("serve", doctored, self.CELLS)
        assert {v.metric for v in violations} == {
            "ttft_s", "tok_per_s", "hbm_bytes"}
        v = next(v for v in violations if v.metric == "ttft_s")
        assert "REGRESSION" in str(v) and "+100.0%" in str(v)

    def test_improvement_within_role_passes(self):
        better = json.loads(json.dumps(self.CELLS))
        better["paged|batched|prompt32"]["ttft_s"] *= 0.5  # faster is fine
        violations, _ = compare_cells("serve", better, self.CELLS)
        assert violations == []
        # ...but a structural metric moving EITHER way fails loudly
        better["paged|batched|prompt32"]["hbm_bytes"] *= 0.9
        violations, _ = compare_cells("serve", better, self.CELLS)
        assert [v.metric for v in violations] == ["hbm_bytes"]

    def test_host_mismatch_skips_wall_metrics(self):
        doctored = json.loads(json.dumps(self.CELLS))
        doctored["paged|batched|prompt32"]["ttft_s"] *= 10
        violations, compared = compare_cells(
            "serve", doctored, self.CELLS, host_match=False)
        assert violations == []
        assert compared == 3  # ttft_ticks, hbm_bytes, drift_err still gated

    def test_new_cells_and_metrics_skipped(self):
        fresh = {"brand|new|cell": {"ttft_s": 9.9},
                 "paged|batched|lanes4": {"tok_per_s": 400.0,
                                          "new_metric_s": 5.0}}
        violations, compared = compare_cells("serve", fresh, self.CELLS)
        assert violations == []
        assert compared == 1  # only the shared tok_per_s
