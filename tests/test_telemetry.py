"""Telemetry subsystem: histogram/percentile math, span nesting + JSONL
round-trip, the zero-overhead null path, scheduler p90/p99 views, the
drift-monitor-vs-bench_drift equivalence, and the JSONL dump contract the
CI artifact relies on."""
from __future__ import annotations

import dataclasses
import gc
import io
import json
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig, reduced
from repro.configs.registry import get_config
from repro.models.model import model_specs
from repro.models.params import init_params
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import Scheduler
from repro.telemetry import (
    DriftMonitor,
    MetricsRegistry,
    NullRegistry,
    NullTracer,
    Telemetry,
    Tracer,
    bv_row_residual,
    spectrum_mass,
)
from repro.telemetry.metrics import (
    LATENCY_BUCKETS,
    RATIO_BUCKETS,
    TICK_BUCKETS,
    Histogram,
    exp_buckets,
)


# ==========================================================================
# metrics.py
# ==========================================================================
def test_exp_buckets_shape():
    b = exp_buckets(1.0, 1000.0, per_decade=3)
    assert b[0] == 1.0 and b[-1] >= 1000.0
    assert np.allclose(np.diff(np.log10(b)), 1 / 3)
    with pytest.raises(ValueError):
        exp_buckets(0.0, 1.0)


def test_histogram_bucket_math():
    h = Histogram(bounds=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    # bucket i covers (bounds[i-1], bounds[i]]; overflow catches 100.0
    assert h.counts == [2, 1, 1, 0, 1]
    assert h.count == 5 and h.sum == pytest.approx(106.0)
    assert h.mean == pytest.approx(21.2)


def test_histogram_percentiles():
    h = Histogram(bounds=tuple(float(i) for i in range(1, 65)))
    assert h.percentile(50) is None  # empty
    for v in [1] * 50 + [10] * 40 + [60] * 10:
        h.observe(v)
    # percentile = upper bound of the bucket holding the target rank
    assert h.percentile(50) == 1.0
    assert h.percentile(90) == 10.0
    assert h.percentile(99) == 60.0
    # single-valued distributions are exact (the scheduler contract)
    h2 = Histogram(bounds=TICK_BUCKETS)
    for _ in range(7):
        h2.observe(30)
    assert h2.percentile(50) == 30.0 == h2.percentile(99)
    # overflow observations report the largest finite bound
    h3 = Histogram(bounds=(1.0, 2.0))
    h3.observe(99.0)
    assert h3.percentile(50) == 2.0


def test_registry_families_and_kinds():
    r = MetricsRegistry()
    c = r.counter("reqs_total", labels=("impl",))
    c.labels(impl="paged").inc(2)
    c.labels(impl="gather").inc()
    assert c.labels(impl="paged").value == 2.0
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    # idempotent re-registration returns the same family
    assert r.counter("reqs_total", labels=("impl",)) is c
    with pytest.raises(ValueError):
        r.gauge("reqs_total")  # kind mismatch
    r.gauge("depth", fn=lambda: 7.0)
    snap = r.snapshot()
    assert snap["reqs_total"]["impl=paged"]["value"] == 2.0
    assert snap["depth"]["value"] == 7.0


# ==========================================================================
# tracing.py
# ==========================================================================
def test_span_nesting_and_jsonl_roundtrip():
    r = MetricsRegistry()
    tr = Tracer(r)
    with tr.span("tick", lane=0):
        with tr.span("inner"):
            pass
    with tr.span("tick", lane=1):
        pass
    assert len(tr.events) == 3
    by_name = {e["name"]: e for e in tr.events}
    assert by_name["inner"]["depth"] == 1
    assert by_name["tick"]["depth"] == 0
    # inner closed first, so it records first; durations nest
    assert tr.events[0]["name"] == "inner"
    assert tr.events[1]["dur_s"] >= tr.events[0]["dur_s"]
    fh = io.StringIO()
    assert tr.dump_jsonl(fh) == 3
    lines = [json.loads(x) for x in fh.getvalue().splitlines()]
    assert all(l["kind"] == "span" for l in lines)
    assert lines[1]["labels"] == {"lane": 0}
    # spans feed the span_seconds histogram family
    fam = r.get("span_seconds")
    assert fam.labels(span="tick").count == 2


def test_tracer_bounded_buffer():
    tr = Tracer(max_events=2)
    for _ in range(4):
        with tr.span("x"):
            pass
    assert len(tr.events) == 2 and tr.dropped == 2
    assert tr.summary() == {"events": 2, "dropped": 2}


# ==========================================================================
# the disabled path
# ==========================================================================
def test_null_registry_emits_nothing():
    r = NullRegistry()
    c = r.counter("x")
    c.inc(5)
    h = r.histogram("h", buckets=(1.0,))
    h.observe(3)
    assert c.value == 0.0 and h.percentile(50) is None
    assert r.snapshot() == {} and list(r.iter_samples()) == []
    assert c.labels(anything="goes") is c
    nt = NullTracer()
    with nt.span("a"):
        pass
    assert nt.summary()["events"] == 0
    assert nt.dump_jsonl(io.StringIO()) == 0


def test_disabled_telemetry_dump_writes_nothing(tmp_path):
    t = Telemetry(enabled=False)
    with t.span("x"):
        pass
    p = tmp_path / "t.jsonl"
    assert t.dump_jsonl(p) == 0
    assert not p.exists()
    assert t.snapshot() == {"metrics": {}, "spans": {"events": 0, "dropped": 0}}


# ==========================================================================
# scheduler percentile views (satellite: p50-only fix + empty edge case)
# ==========================================================================
def _dummy(uid):
    return Request(uid, [5, 6, 7], max_new_tokens=4)


def test_scheduler_stats_empty():
    s = Scheduler(None, max_lanes=2, blocks_per_lane=4)
    st = s.stats()
    for k in ("ttft_ticks_p50", "ttft_ticks_p90", "ttft_ticks_p99",
              "latency_ticks_p50", "latency_ticks_p90", "latency_ticks_p99",
              "ttft_s_p50", "itl_s_p99"):
        assert st[k] is None, k
    assert st["admitted"] == 0 and st["queued"] == 0


def test_scheduler_percentiles_p90_p99():
    s = Scheduler(None, max_lanes=1, blocks_per_lane=4)
    s.requeue_cb = lambda lane: None
    # ten sequential requests with TTFTs 1..10 ticks
    for uid in range(10):
        s.tick_now = uid * 100
        s.submit(_dummy(uid))
        [(lane, _)] = s.admit()
        s.tick_now = uid * 100 + (uid + 1)  # first token after uid+1 ticks
        s.note_token(uid)
        s.note_token(uid)  # second token: exercises the ITL histogram
        s.release(lane)
    st = s.stats()
    assert st["ttft_ticks_p50"] == 5.0
    assert st["ttft_ticks_p90"] == 9.0
    assert st["ttft_ticks_p99"] == 10.0
    assert st["finished"] == 10
    assert st["itl_s_p50"] is not None
    fam = s.registry.get("serve_itl_seconds")
    assert fam.count == 10


# ==========================================================================
# drift monitor == bench_drift's offline formula (small case)
# ==========================================================================
def test_drift_probe_matches_offline_rebase_numbers():
    """Run the frozen-mode protocol with the decode_state primitives; at a
    segment boundary the monitor's pre-vs-post residual must equal the
    offline recompute-based drift (bench_drift's per-row formula) on the
    two rebased rows — the rebase IS the exact recompute."""
    from repro.serve.decode_state import (
        landmark_counts,
        landmark_means,
        rebase_rows,
        recompute_stats,
        segment_len,
        stream_append,
    )

    B, H, S, D, C = 1, 2, 32, 8, 8
    seg = segment_len(S, C)
    scale = D ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, S, D)) * 0.5
    k = q  # self-similar regime: non-trivial drift
    v = jax.random.normal(ks[2], (B, H, S, D))

    stats = (jnp.zeros((B, H, C, 1)), jnp.zeros((B, H, C, 1)),
             jnp.zeros((B, H, C, D)))
    q_sums = jnp.zeros((B, H, C, D))
    checked = 0
    for t in range(S):
        onehot = jax.nn.one_hot(t // seg, C, dtype=jnp.float32)
        q_sums = q_sums + onehot[:, None] * q[:, :, t][:, :, None, :]
        counts = landmark_counts(jnp.asarray(t), S, C)
        q_l = landmark_means(q_sums, counts)
        active = t // seg
        stats = stream_append(stats, q_l, k[:, :, t], v[:, :, t], scale,
                              row_mask=jnp.arange(C) <= active)
        if t > 0 and t % seg == 0:
            rows = [max(active - 1, 0), active]
            pre = tuple(np.asarray(x) for x in stats)
            stats = rebase_rows(stats, q_l, k, v, t, scale,
                                jnp.stack([rows[0], rows[1]]))
            post = tuple(np.asarray(x) for x in stats)
            monitor = bv_row_residual((pre[1], pre[2]), (post[1], post[2]),
                                      rows)
            # offline: bench_drift's _drift_at per-row formula against the
            # exact one-shot recompute, restricted to the rebased rows
            m_r, l_r, acc_r = recompute_stats(q_l, k, v, t, scale,
                                              row_valid=counts > 0)
            bv_f = pre[2] / np.maximum(pre[1], 1e-30)
            bv_e = np.asarray(acc_r) / np.maximum(np.asarray(l_r), 1e-30)
            per_row = np.linalg.norm(bv_f - bv_e, axis=-1) / np.maximum(
                np.linalg.norm(bv_e, axis=-1), 1e-30)
            offline = float(np.max(per_row[..., rows]))
            assert monitor == pytest.approx(offline, rel=1e-5)
            checked += 1
    assert checked >= 2
    # registry plumbing: observations land in the residual histogram
    r = MetricsRegistry()
    mon = DriftMonitor(r)
    mon.observe(0.01)
    mon.observe(0.02)
    hist = r.get("drift_rebase_residual")
    assert hist.count == 2 and r.get("drift_rebase_residual_last").value == 0.02


def test_spectrum_mass_extremes():
    C = 8
    m = np.zeros((1, C, 1))
    l = np.ones((1, C, 1))
    top1, eff = spectrum_mass(m, l, reached=C)  # perfectly even mass
    assert top1 == pytest.approx(1 / C)
    assert eff == pytest.approx(1.0)
    l1 = np.full((1, C, 1), 1e-12)
    l1[0, 3, 0] = 1.0  # all mass on one landmark
    top1, eff = spectrum_mass(m, l1, reached=C)
    assert top1 == pytest.approx(1.0, abs=1e-6)
    assert eff == pytest.approx(1 / C, rel=1e-3)


# ==========================================================================
# engine integration: JSONL contract + zero-overhead disabled path
# ==========================================================================
@pytest.fixture(scope="module")
def qwen():
    cfg = dataclasses.replace(
        reduced(get_config("qwen2-7b")), capacity_factor=100.0,
        decode_streaming="frozen",
    )
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _reqs(cfg, n, max_new=20):
    rng = np.random.default_rng(11)
    return [
        Request(u, rng.integers(3, cfg.vocab_size,
                                int(rng.integers(8, 20))).tolist(),
                max_new_tokens=max_new)
        for u in range(n)
    ]


CORE_FAMILIES = (
    "serve_ttft_ticks", "serve_latency_ticks", "serve_ttft_seconds",
    "serve_itl_seconds", "serve_admitted_total", "serve_tokens_total",
    "serve_ticks_total", "serve_rebases_total", "span_seconds",
    "pool_utilization", "pool_fragmentation",
    "autotune_plan_resolutions_total", "drift_rebase_residual",
    "spectrum_mass_top1_ema",
)


def test_engine_telemetry_jsonl_contract(qwen, tmp_path):
    """The CI artifact contract: an enabled frozen-mode run dumps JSONL
    that parses and contains every core metric family plus per-tick spans.
    Guards against silent metric renames."""
    cfg, params = qwen
    serve = ServeConfig(max_lanes=2, max_seq=64, block_size=8, telemetry=True)
    eng = ServeEngine(cfg, params, serve=serve)
    for r in _reqs(cfg, 3):
        eng.submit(r)
    eng.run()
    st = eng.stats()
    assert st["rebases"] > 0
    assert st["telemetry"]["events"] > 0
    path = tmp_path / "telemetry.jsonl"
    n = eng.telemetry.dump_jsonl(path, meta={"bench": "test"})
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert len(lines) == n
    assert lines[0]["kind"] == "meta"
    names = {l["name"] for l in lines if l["kind"] == "metric"}
    for fam in CORE_FAMILIES:
        assert fam in names, f"core metric family {fam} missing from dump"
    spans = [l for l in lines if l["kind"] == "span"]
    assert {"serve_tick", "decode_dispatch", "device_sync"} <= {
        s["name"] for s in spans
    }
    # TTFT/ITL histograms expose p50/p99 in the dump
    ttft = next(l for l in lines
                if l["kind"] == "metric" and l["name"] == "serve_ttft_ticks")
    assert ttft["count"] > 0 and ttft["p50"] is not None and "p99" in ttft
    drift = next(l for l in lines
                 if l["kind"] == "metric"
                 and l["name"] == "drift_rebase_residual")
    assert drift["count"] == st["rebases"]


def test_engine_disabled_identical_and_clean(qwen):
    """telemetry=False: greedy outputs token-identical to an enabled run,
    no telemetry keys in stats(), percentile views still populated."""
    cfg, params = qwen
    reqs = _reqs(cfg, 2, max_new=10)
    on = ServeConfig(max_lanes=2, max_seq=64, block_size=8, telemetry=True)
    off = dataclasses.replace(on, telemetry=False)
    out_on = out_off = None
    for serve in (on, off):
        eng = ServeEngine(cfg, params, serve=serve)
        for r in reqs:
            eng.submit(Request(r.uid, list(r.prompt), r.max_new_tokens))
        out = eng.run()
        if serve.telemetry:
            out_on = out
        else:
            out_off = out
            st = eng.stats()
            assert "telemetry" not in st
            assert eng.telemetry.metrics.snapshot() == {}
            assert isinstance(eng.telemetry.metrics, NullRegistry)
            # satellite-1 views work without the telemetry knob
            assert st["ttft_ticks_p99"] is not None
            assert st["latency_ticks_p90"] is not None
    assert out_on == out_off


def test_dropped_engine_is_freed(qwen):
    """An engine with telemetry on installs its registry process-wide (plan
    resolution and compile counters), and that registry's gauges reach back
    into the engine. Dropping the engine must still free it, with its
    weights and KV pools, rather than leave it pinned by the global."""
    cfg, params = qwen
    serve = ServeConfig(max_lanes=2, max_seq=64, block_size=8, telemetry=True)
    eng = ServeEngine(cfg, params, serve=serve)
    for r in _reqs(cfg, 1, max_new=2):
        eng.submit(r)
    eng.run()
    ref = weakref.ref(eng)
    del eng
    gc.collect()
    assert ref() is None
