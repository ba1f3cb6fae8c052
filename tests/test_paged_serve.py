"""Paged serving subsystem: block allocator invariants, paged-vs-dense
decode equivalence, batched-prefill-vs-token-replay equivalence, and the
preemption round-trip."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig, reduced
from repro.configs.registry import get_config
from repro.models.model import model_specs
from repro.models.params import init_params
from repro.serve.decode import decode_step
from repro.serve.engine import Request, ServeEngine
from repro.serve.kv_cache import cache_specs
from repro.serve.paged import ZERO_BLOCK, BlockAllocator, PagedKVCache
from repro.serve.prefill import batched_prefill


@pytest.fixture(scope="module")
def qwen():
    cfg = dataclasses.replace(
        reduced(get_config("qwen2-7b")), capacity_factor=100.0
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


def _run(cfg, params, reqs, serve, stagger=0):
    eng = ServeEngine(cfg, params, serve=serve)
    for r in reqs[: len(reqs) - stagger]:
        eng.submit(Request(r.uid, list(r.prompt), r.max_new_tokens))
    if stagger:
        for _ in range(4):
            eng.tick()
        for r in reqs[len(reqs) - stagger:]:
            eng.submit(Request(r.uid, list(r.prompt), r.max_new_tokens))
    out = eng.run()
    return out, eng


BASE = ServeConfig(max_lanes=2, max_seq=64, block_size=8)
DENSE = dataclasses.replace(BASE, paged=False, batched_prefill=False)


# ==========================================================================
# BlockAllocator
# ==========================================================================
class TestBlockAllocator:
    def test_alloc_free_reuse(self):
        a = BlockAllocator(9, 8)  # 8 usable (block 0 reserved)
        got = a.alloc(1, 3)
        assert got is not None and len(got) == 3
        assert ZERO_BLOCK not in got
        assert a.num_free == 5
        assert a.alloc(2, 6) is None  # over budget: no state change
        assert a.num_free == 5 and 2 not in a.tables
        freed = a.free(1)
        assert sorted(freed) == sorted(got)
        assert a.num_free == 8
        # freed blocks come back (LIFO) and are never double-issued
        again = a.alloc(3, 8)
        assert sorted(again) == list(range(1, 9))
        assert a.alloc(4, 1) is None

    def test_tables_are_per_request(self):
        a = BlockAllocator(9, 4)
        a.alloc(7, 2)
        a.alloc(8, 2)
        assert set(a.tables[7]).isdisjoint(a.tables[8])
        a.alloc(7, 1)
        assert len(a.tables[7]) == 3  # growth appends

    def test_stats_and_utilization(self):
        a = BlockAllocator(9, 4)
        a.alloc(1, 4)
        st = a.stats()
        assert st["blocks_used"] == 4 and st["blocks_free"] == 4
        assert st["utilization"] == pytest.approx(0.5)

    def test_defragment_compacts_and_remaps(self):
        a = BlockAllocator(17, 8)
        a.alloc(1, 3)
        a.alloc(2, 4)
        a.alloc(3, 2)
        a.free(2)  # hole in the middle
        mapping = a.defragment()
        live = sorted(b for t in a.tables.values() for b in t)
        assert live == list(range(1, 6))  # compact prefix, block 0 untouched
        assert ZERO_BLOCK not in mapping and ZERO_BLOCK not in mapping.values()
        assert a.num_free == 16 - 5


# ==========================================================================
# Paged storage
# ==========================================================================
def test_paged_gather_matches_dense_roundtrip(qwen):
    """write_prefill -> gather_views reconstructs exactly the dense cache
    batched_prefill produced (modulo zero-padding past the prompt)."""
    cfg, params = qwen
    serve = BASE
    kv = PagedKVCache(cfg, serve)
    alloc = BlockAllocator(serve.resolved_num_blocks, serve.block_size)
    rng = np.random.default_rng(0)
    n = 19
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :n] = rng.integers(3, cfg.vocab_size, n)
    _, pcache = batched_prefill(
        params, cfg, jnp.asarray(tokens), jnp.asarray(n, jnp.int32),
        seq_max=serve.max_seq,
    )
    alloc.alloc(0, alloc.blocks_for_tokens(n))
    tables = np.full((serve.max_lanes, serve.blocks_per_lane), ZERO_BLOCK,
                     np.int32)
    row = alloc.tables[0]
    tables[0, : len(row)] = row
    kv.write_prefill(0, pcache, tables[0], n_tokens=n)
    view = kv.gather_views(tables)

    k_dense = np.asarray(pcache["layers"][0]["k"] if isinstance(
        pcache["layers"], list) else pcache["layers"]["k"][0])
    k_view = np.asarray(view["layers"][0]["k"][0] if isinstance(
        view["layers"], list) else view["layers"]["k"][0][0])
    np.testing.assert_allclose(k_view[..., :32, :], k_dense, atol=0)
    assert np.all(k_view[..., 32:, :] == 0)  # unallocated -> zero block
    assert int(view["pos"][0]) == n


# ==========================================================================
# Engine equivalence
# ==========================================================================
def test_paged_vs_dense_token_identical(qwen):
    """Mixed batch, staggered arrivals: the paged/batched-prefill engine
    produces token-identical greedy outputs to the seed-style dense engine."""
    cfg, params = qwen
    reqs = _requests(cfg, 6, seed=1)
    ref, _ = _run(cfg, params, reqs, DENSE, stagger=3)
    out, eng = _run(cfg, params, reqs, BASE, stagger=3)
    assert ref == out
    st = eng.stats()
    assert st["finished"] == 6
    assert st["mode"] == "paged+batched-prefill"


def test_batched_prefill_matches_token_replay(qwen):
    """Cache state + next-token logits after batched prefill equal those
    after feeding the prompt token-by-token through decode_step."""
    cfg, params = qwen
    s_max = 64
    rng = np.random.default_rng(3)
    n = 21
    prompt = rng.integers(3, cfg.vocab_size, n)

    cache = init_params(cache_specs(cfg, 1, s_max), jax.random.PRNGKey(1))
    step = jax.jit(lambda c, t: decode_step(params, cfg, c, t))
    for i in range(n):
        replay_logits, cache = step(
            cache, jnp.asarray(prompt[None, i: i + 1], jnp.int32)
        )

    n_pad = 32
    tokens = np.zeros((1, n_pad), np.int32)
    tokens[0, :n] = prompt
    logits, pcache = batched_prefill(
        params, cfg, jnp.asarray(tokens), jnp.asarray(n, jnp.int32),
        seq_max=s_max,
    )
    np.testing.assert_allclose(
        np.asarray(logits[0, n - 1], np.float32),
        np.asarray(replay_logits[0, 0], np.float32), atol=2e-4, rtol=2e-4,
    )
    assert int(pcache["pos"]) == n == int(cache["pos"])
    ref_l, new_l = cache["layers"], pcache["layers"]
    get = (lambda t, k: t[k]) if not isinstance(ref_l, list) else (
        lambda t, k: jnp.stack([la[k] for la in t])
    )
    # Layer 0 is a pure accumulation path (no upstream attention): cumsum
    # must match sequential _lmk_add to fp epsilon.
    for key in ("q_lmk", "k_lmk"):
        np.testing.assert_allclose(
            np.asarray(get(new_l, key))[0], np.asarray(get(ref_l, key))[0],
            atol=1e-4, rtol=1e-4,
        )
    np.testing.assert_array_equal(
        np.asarray(get(new_l, "k"))[0],
        np.asarray(get(ref_l, "k"))[0][..., :n_pad, :],
    )
    # Deeper layers inherit fp-reassociation noise amplified through the
    # layer-0 pseudoinverse (vmapped vs sequential attention); greedy
    # outputs stay identical (test_paged_vs_dense_token_identical).
    for key in ("q_lmk", "k_lmk"):
        np.testing.assert_allclose(
            np.asarray(get(new_l, key)), np.asarray(get(ref_l, key)),
            atol=5e-2, rtol=5e-2,
        )
    np.testing.assert_allclose(
        np.asarray(get(new_l, "k")),
        np.asarray(get(ref_l, "k"))[..., :n_pad, :], atol=5e-2, rtol=5e-2,
    )


def test_preemption_roundtrip_identical(qwen):
    """A pool too small for all lanes forces preemption; the preempted
    request restarts from scratch and still finishes with identical
    greedy output."""
    cfg, params = qwen
    reqs = _requests(cfg, 4, seed=2, lo=20, hi=21, max_new=30)
    serve = dataclasses.replace(BASE, max_lanes=3, num_blocks=12)
    ref, _ = _run(cfg, params, reqs, dataclasses.replace(
        DENSE, max_lanes=3))
    out, eng = _run(cfg, params, reqs, serve)
    st = eng.stats()
    assert st["preemptions"] > 0, "pool should have forced preemption"
    assert st["finished"] == 4
    assert ref == out
    assert st["kv"]["blocks_used"] == 0  # everything released at the end


def test_scheduler_metrics_and_ttft(qwen):
    """Batched prefill: first token lands one tick after admission, and the
    engine surfaces latency/utilization counters."""
    cfg, params = qwen
    reqs = _requests(cfg, 1, seed=4, lo=30, hi=31, max_new=4)
    _, eng = _run(cfg, params, reqs, BASE)
    st = eng.stats()
    assert st["ttft_ticks_p50"] == 1.0  # one tick: prefill + first sample
    assert st["new_tokens"] == 4
    _, eng_d = _run(cfg, params, reqs, DENSE)
    # token replay pays one tick per prompt token before the first sample
    assert eng_d.stats()["ttft_ticks_p50"] == float(len(reqs[0].prompt))


def test_ssm_family_falls_back_dense():
    """xLSTM has no sequence-shaped cache: the engine runs lane-dense with
    no allocator, and outputs match the seed configuration."""
    cfg = reduced(get_config("xlstm-350m"))
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    reqs = _requests(cfg, 3, seed=5)
    ref, _ = _run(cfg, params, reqs, DENSE)
    out, eng = _run(cfg, params, reqs, BASE)
    assert ref == out
    assert eng.stats()["mode"] == "dense+replay-prefill"
    assert "kv" not in eng.stats()


def test_defragment_mid_stream_preserves_outputs(qwen):
    """engine.defragment() between ticks permutes pool storage + tables
    consistently: in-flight requests finish with unchanged output."""
    cfg, params = qwen
    reqs = _requests(cfg, 4, seed=8, max_new=12)
    ref, _ = _run(cfg, params, reqs, DENSE)
    eng = ServeEngine(cfg, params, serve=BASE)
    for r in reqs:
        eng.submit(Request(r.uid, list(r.prompt), r.max_new_tokens))
    moved_total = 0
    for _ in range(60):
        if eng.sched.idle:
            break
        eng.tick()
        moved_total += eng.defragment()  # compact while requests in flight
    out = eng.run()
    assert ref == out
    # retirements between staggered requests leave holes, so compaction
    # must actually have moved something for this test to mean anything
    assert moved_total > 0


def test_ss_fused_prefill_runs(qwen):
    """The Pallas-kernel prefill path (approximate prompt attention) serves
    a batch end-to-end and leaves exact landmark state behind."""
    cfg, params = qwen
    reqs = _requests(cfg, 3, seed=6)
    serve = dataclasses.replace(BASE, prefill_impl="ss_fused")
    out, eng = _run(cfg, params, reqs, serve)
    assert eng.stats()["finished"] == 3
    assert all(len(v) > 0 for v in out.values())


# ==========================================================================
# Bucketed ss_fused prefill (key-validity masked kernels)
# ==========================================================================
def test_ss_fused_prefill_padding_invariant(qwen):
    """Bucket-padded ss_fused prefill == unpadded ss_fused prefill: the
    dynamic kv_valid bound keeps padded zero-keys out of the softmax, so
    logits at valid positions and the cache state are identical."""
    cfg, params = qwen
    s_max = 64
    rng = np.random.default_rng(11)
    n = 21  # > num_landmarks (16): the masked fused path
    prompt = rng.integers(3, cfg.vocab_size, n)

    def run(n_pad):
        tokens = np.zeros((1, n_pad), np.int32)
        tokens[0, :n] = prompt
        return batched_prefill(
            params, cfg, jnp.asarray(tokens), jnp.asarray(n, jnp.int32),
            seq_max=s_max, prefill_impl="ss_fused",
        )

    logits_u, cache_u = run(n)       # unpadded reference
    logits_p, cache_p = run(32)      # bucket-padded
    np.testing.assert_allclose(
        np.asarray(logits_p[0, :n], np.float32),
        np.asarray(logits_u[0], np.float32), atol=1e-4, rtol=1e-4,
    )
    assert int(np.argmax(logits_p[0, n - 1])) == int(np.argmax(logits_u[0, n - 1]))
    get = (lambda t, k: jnp.stack([la[k] for la in t])) if isinstance(
        cache_u["layers"], list) else (lambda t, k: t[k])
    for key in ("q_lmk", "k_lmk"):
        np.testing.assert_allclose(
            np.asarray(get(cache_p["layers"], key), np.float32),
            np.asarray(get(cache_u["layers"], key), np.float32),
            atol=1e-4, rtol=1e-4,
        )
    np.testing.assert_allclose(
        np.asarray(get(cache_p["layers"], "k"))[..., :n, :],
        np.asarray(get(cache_u["layers"], "k"))[..., :n, :],
        atol=1e-4, rtol=1e-4,
    )


def test_ss_fused_bucket_size_token_identical(qwen):
    """Greedy engine outputs are invariant to the bucket size in ss_fused
    mode — padding is invisible end to end (prompts > num_landmarks so the
    masked kernels, not the degenerate exact path, are exercised)."""
    cfg, params = qwen
    reqs = _requests(cfg, 4, seed=9, lo=18, hi=30)
    outs = []
    for bucket in (8, 32):
        serve = dataclasses.replace(
            BASE, prefill_impl="ss_fused", prefill_bucket=bucket)
        out, eng = _run(cfg, params, reqs, serve)
        assert eng.stats()["finished"] == 4
        outs.append(out)
    assert outs[0] == outs[1]


def test_ss_fused_degenerate_prompt_unpadded(qwen):
    """Prompts of <= num_landmarks tokens take the exact-attention path and
    still serve correctly (the engine slices them to exact length)."""
    cfg, params = qwen
    reqs = _requests(cfg, 3, seed=10, lo=4, hi=16)  # all <= 16 landmarks
    serve = dataclasses.replace(BASE, prefill_impl="ss_fused")
    out, eng = _run(cfg, params, reqs, serve)
    assert eng.stats()["finished"] == 3
    assert all(len(v) > 0 for v in out.values())


def test_engine_warms_decode_plan(qwen):
    """ServeEngine resolves the decode-shape dispatch key at construction
    and surfaces the plan in stats()."""
    from repro.kernels import dispatch

    cfg, params = qwen
    eng = ServeEngine(cfg, params, serve=BASE)
    assert eng.decode_plan.impl in ("jnp", "fused", "interpret", "sharded")
    key = dispatch.make_key(
        BASE.max_seq, cfg.num_landmarks, cfg.resolved_head_dim,
        cfg.compute_dtype, True, family="decode",
    )
    assert key.family == "decode"
    # The heuristic decode plan routes to the jnp decode math.
    assert eng.decode_plan.impl == "jnp"
    assert eng.stats()["decode_plan"].startswith("jnp/")


def test_ss_fused_degenerate_padded_prompt_exact(qwen):
    """Regression: a bucket-padded window of <= num_landmarks tokens takes
    the exact path WITH the key-validity mask applied — padded zero-keys
    must not shift the logits or the next token."""
    cfg, params = qwen
    rng = np.random.default_rng(13)
    n = 5  # << num_landmarks (16)
    prompt = rng.integers(3, cfg.vocab_size, n)

    def run(n_pad):
        tokens = np.zeros((1, n_pad), np.int32)
        tokens[0, :n] = prompt
        return batched_prefill(
            params, cfg, jnp.asarray(tokens), jnp.asarray(n, jnp.int32),
            seq_max=64, prefill_impl="ss_fused",
        )

    logits_u, _ = run(n)
    logits_p, _ = run(8)
    np.testing.assert_allclose(
        np.asarray(logits_p[0, :n], np.float32),
        np.asarray(logits_u[0], np.float32), atol=1e-4, rtol=1e-4,
    )
    assert int(np.argmax(logits_p[0, n - 1])) == int(np.argmax(logits_u[0, n - 1]))


def test_engine_honors_autotune_cache_override(qwen, tmp_path):
    """Regression: ServeEngine's dispatch warm-up loads plans from
    ModelConfig.autotune_cache, like the Trainer does."""
    from repro.kernels import dispatch

    cfg, params = qwen
    cache = tmp_path / "tuned.json"
    key = dispatch.make_key(
        BASE.max_seq, cfg.num_landmarks, cfg.resolved_head_dim,
        cfg.compute_dtype, True, family="decode",
    )
    dispatch.clear_registry()
    dispatch.register_plan(
        key, dispatch.Plan(impl="jnp", block_n=64, source="autotuned"))
    dispatch.save_cache(str(cache))
    dispatch.clear_registry()
    try:
        eng = ServeEngine(
            dataclasses.replace(cfg, autotune_cache=str(cache)), params,
            serve=BASE,
        )
        assert eng.decode_plan.block_n == 64
        assert eng.decode_plan.source == "cache"
    finally:
        dispatch.clear_registry()  # drop the process-wide cache override


# ==========================================================================
# Streaming decode state (serve/decode_state.py)
# ==========================================================================
def _stats_logmass(lc):
    """Anchor-invariant total softmax mass per row: log(l) + m."""
    return np.log(np.maximum(np.asarray(lc["bv_l"], np.float64), 1e-300)) \
        + np.asarray(lc["bv_m"], np.float64)


def _layer0(cache):
    layers = cache["layers"]
    if isinstance(layers, list):
        return layers[0]
    return jax.tree.map(lambda a: a[0], layers)


class TestStreamingDecode:
    def test_exact_token_identical_dense_and_paged(self, qwen):
        """decode_streaming="exact" produces greedy outputs token-identical
        to the legacy recompute path, on both engines."""
        cfg, params = qwen
        reqs = _requests(cfg, 5, seed=21)
        outs = {}
        for mode in ("recompute", "exact"):
            mcfg = dataclasses.replace(cfg, decode_streaming=mode)
            ref, _ = _run(mcfg, params, reqs, DENSE, stagger=2)
            out, eng = _run(mcfg, params, reqs, BASE, stagger=2)
            assert ref == out, f"paged != dense under {mode}"
            assert eng.stats()["decode_streaming"] == mode
            outs[mode] = ref
        assert outs["recompute"] == outs["exact"]

    def test_exact_stats_match_recompute_invariant(self, qwen):
        """After token-by-token decode in exact mode, every reached row's
        (m, l, acc) equals the one-shot exact recompute (same softmax, fp
        reassociation only); unreached rows hold the zero state."""
        from repro.models.attention import _broadcast_kv
        from repro.serve.decode_state import (
            landmark_counts, landmark_means, recompute_stats, segment_len,
        )

        cfg, params = qwen
        s_max = 64
        rng = np.random.default_rng(22)
        n = 23
        prompt = rng.integers(3, cfg.vocab_size, n)
        cache = init_params(cache_specs(cfg, 1, s_max), jax.random.PRNGKey(1))
        step = jax.jit(lambda c, t: decode_step(params, cfg, c, t,
                                                seq_max=s_max))
        for i in range(n):
            _, cache = step(cache, jnp.asarray(prompt[None, i:i+1], jnp.int32))
        lc = _layer0(cache)
        pos = n - 1
        c = cfg.num_landmarks
        counts = landmark_counts(jnp.asarray(pos), s_max, c)
        q_l = landmark_means(lc["q_lmk"], counts)
        kb = _broadcast_kv(lc["k"], cfg.num_heads)
        vb = _broadcast_kv(lc["v"], cfg.num_heads)
        m, l, acc = recompute_stats(
            q_l, kb, vb, pos, cfg.resolved_head_dim ** -0.5,
            row_valid=counts > 0,
        )
        active = pos // segment_len(s_max, c)
        bv_ref = np.asarray(acc / jnp.maximum(l, 1e-30))
        bv_got = np.asarray(lc["bv_acc"] / jnp.maximum(lc["bv_l"], 1e-30))
        np.testing.assert_allclose(
            bv_got[..., : active + 1, :], bv_ref[..., : active + 1, :],
            atol=2e-4, rtol=2e-4,
        )
        # anchor-invariant mass agrees on reached rows
        mass_ref = np.log(np.maximum(np.asarray(l, np.float64), 1e-300)) \
            + np.asarray(m, np.float64)
        np.testing.assert_allclose(
            _stats_logmass(lc)[..., : active + 1, :],
            mass_ref[..., : active + 1, :], atol=1e-4, rtol=1e-4,
        )
        # unreached rows: exact zero state
        for name in ("bv_m", "bv_l", "bv_acc"):
            assert np.all(np.asarray(lc[name])[..., active + 1:, :] == 0)

    def test_prefill_rebuilds_streaming_state(self, qwen):
        """The preemption-recompute path (batched prefill on re-admission)
        rebuilds the same streaming stats token-by-token decode had
        accumulated: normalized BV and total mass agree row-for-row."""
        cfg, params = qwen
        s_max = 64
        rng = np.random.default_rng(23)
        n = 21
        prompt = rng.integers(3, cfg.vocab_size, n)
        cache = init_params(cache_specs(cfg, 1, s_max), jax.random.PRNGKey(1))
        step = jax.jit(lambda c, t: decode_step(params, cfg, c, t,
                                                seq_max=s_max))
        for i in range(n):
            _, cache = step(cache, jnp.asarray(prompt[None, i:i+1], jnp.int32))
        tokens = np.zeros((1, 32), np.int32)
        tokens[0, :n] = prompt
        _, pcache = batched_prefill(
            params, cfg, jnp.asarray(tokens), jnp.asarray(n, jnp.int32),
            seq_max=s_max,
        )
        lc_d, lc_p = _layer0(cache), _layer0(pcache)
        bv_d = np.asarray(lc_d["bv_acc"] / jnp.maximum(lc_d["bv_l"], 1e-30))
        bv_p = np.asarray(lc_p["bv_acc"] / jnp.maximum(lc_p["bv_l"], 1e-30))
        np.testing.assert_allclose(bv_p, bv_d, atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(
            _stats_logmass(lc_p), _stats_logmass(lc_d), atol=1e-4, rtol=1e-4,
        )

    def test_preemption_roundtrip_streaming_engine(self, qwen):
        """Pool pressure forces preemption; the preempted request recomputes
        through prefill and the streaming state survives the round trip. In
        exact mode that means token-identity with the dense reference; in
        frozen mode (approximate by design, and prefill-path dependent) the
        preempting run must be deterministic and complete."""
        cfg, params = qwen
        serve = dataclasses.replace(BASE, max_lanes=3, num_blocks=12)

        mcfg = dataclasses.replace(cfg, decode_streaming="exact")
        reqs = _requests(mcfg, 4, seed=24, lo=20, hi=21, max_new=30)
        ref, _ = _run(mcfg, params, reqs,
                      dataclasses.replace(DENSE, max_lanes=3))
        out, eng = _run(mcfg, params, reqs, serve)
        assert eng.stats()["preemptions"] > 0
        assert ref == out

        fcfg = dataclasses.replace(cfg, decode_streaming="frozen")
        out1, eng1 = _run(fcfg, params, reqs, serve)
        out2, eng2 = _run(fcfg, params, reqs, serve)
        assert eng1.stats()["preemptions"] > 0
        assert eng1.stats()["finished"] == 4
        assert out1 == out2

    def test_frozen_boundary_rebase_correctness(self, qwen):
        """Frozen mode with boundary rebases: every frozen row's stats equal
        the exact recompute (the drift its active phase accumulated is
        cleared by the lazy rebase); only the active row may drift."""
        from repro.models.attention import _broadcast_kv
        from repro.serve.decode_state import (
            landmark_counts, landmark_means, make_rebase_fn,
            recompute_stats, segment_len,
        )

        cfg, params = qwen
        mcfg = dataclasses.replace(cfg, decode_streaming="frozen")
        s_max = 48
        c = mcfg.num_landmarks
        seg = segment_len(s_max, c)  # 3 tokens per segment
        rng = np.random.default_rng(25)
        n = 20
        prompt = rng.integers(3, mcfg.vocab_size, n)
        cache = init_params(cache_specs(mcfg, 1, s_max), jax.random.PRNGKey(1))
        step = jax.jit(lambda ca, t: decode_step(params, mcfg, ca, t,
                                                 seq_max=s_max))
        rebase = jax.jit(make_rebase_fn(mcfg, s_max))
        for i in range(n):
            _, cache = step(cache, jnp.asarray(prompt[None, i:i+1], jnp.int32))
            if i > 0 and i % seg == 0:  # the engine's boundary trigger
                cache = rebase(cache, jnp.asarray(i))
        lc = _layer0(cache)
        pos = n - 1
        counts = landmark_counts(jnp.asarray(pos), s_max, c)
        q_l = landmark_means(lc["q_lmk"], counts)
        kb = _broadcast_kv(lc["k"], mcfg.num_heads)
        vb = _broadcast_kv(lc["v"], mcfg.num_heads)
        m, l, acc = recompute_stats(
            q_l, kb, vb, pos, mcfg.resolved_head_dim ** -0.5,
            row_valid=counts > 0,
        )
        active = pos // seg
        assert active >= 2, "test needs several frozen segments"
        bv_ref = np.asarray(acc / jnp.maximum(l, 1e-30))
        bv_got = np.asarray(lc["bv_acc"] / jnp.maximum(lc["bv_l"], 1e-30))
        np.testing.assert_allclose(  # frozen rows: exact after rebases
            bv_got[..., :active, :], bv_ref[..., :active, :],
            atol=2e-4, rtol=2e-4,
        )

    def test_frozen_engine_paged_matches_dense(self, qwen):
        """Frozen mode end to end: with the prefill strategy held fixed
        (frozen state is prefill-path dependent by design — batched prefill
        seeds exact stats, replay accumulates bounded drift), paged and
        dense storage agree token-for-token and rebases fire in both."""
        cfg, params = qwen
        mcfg = dataclasses.replace(cfg, decode_streaming="frozen")
        reqs = _requests(mcfg, 4, seed=26, max_new=16)
        dense_batched = dataclasses.replace(BASE, paged=False)
        ref, eng_d = _run(mcfg, params, reqs, dense_batched)
        out, eng_p = _run(mcfg, params, reqs, BASE)
        assert ref == out
        assert eng_p.stats()["rebases"] > 0
        assert eng_d.stats()["rebases"] > 0

    def test_ss_fused_prefill_stats_handoff(self, qwen):
        """ss_fused prefill hands the landmark_summary kernel's (m, l, BV)
        into the cache: equivalent to the jnp recompute on reached rows,
        zero elsewhere — and greedy decode continues identically from it."""
        from repro.models.attention import _broadcast_kv
        from repro.serve.decode_state import (
            landmark_counts, landmark_means, mask_stats_rows,
            recompute_stats, segment_len,
        )

        cfg, params = qwen
        s_max = 64
        rng = np.random.default_rng(27)
        n = 21  # > num_landmarks: the masked-kernel regime
        prompt = rng.integers(3, cfg.vocab_size, n)
        tokens = np.zeros((1, 32), np.int32)
        tokens[0, :n] = prompt
        _, pc = batched_prefill(
            params, cfg, jnp.asarray(tokens), jnp.asarray(n, jnp.int32),
            seq_max=s_max, prefill_impl="ss_fused",
        )
        lc = _layer0(pc)
        c = cfg.num_landmarks
        counts = landmark_counts(jnp.asarray(n - 1), s_max, c)
        q_l = landmark_means(lc["q_lmk"], counts)
        kb = _broadcast_kv(lc["k"], cfg.num_heads)
        vb = _broadcast_kv(lc["v"], cfg.num_heads)
        keep = jnp.arange(c) <= (n - 1) // segment_len(s_max, c)
        m, l, acc = mask_stats_rows(
            recompute_stats(q_l, kb, vb, n - 1,
                            cfg.resolved_head_dim ** -0.5),
            keep,
        )
        bv_ref = np.asarray(acc / jnp.maximum(l, 1e-30))
        bv_got = np.asarray(lc["bv_acc"] / jnp.maximum(lc["bv_l"], 1e-30))
        np.testing.assert_allclose(bv_got, bv_ref, atol=1e-4, rtol=1e-4)
        for name in ("bv_m", "bv_l", "bv_acc"):
            assert np.all(
                np.asarray(lc[name])[..., int(np.sum(keep)):, :] == 0
            )
        # greedy continuation from the kernel-seeded cache == from the
        # jnp-recomputed stats (exact mode overwrites only the active row)
        lc_fix = dict(lc, bv_m=m, bv_l=l, bv_acc=acc)
        if isinstance(pc["layers"], list):
            pc_fix = dict(pc, layers=[lc_fix] + pc["layers"][1:])
        else:
            pc_fix = dict(pc, layers=jax.tree.map(
                lambda full, one: full.at[0].set(one), pc["layers"], lc_fix))
        step = jax.jit(lambda ca, t: decode_step(params, cfg, ca, t,
                                                 seq_max=s_max))
        tok = jnp.asarray([[prompt[-1]]], jnp.int32)
        outs = []
        for start in (pc, pc_fix):
            ca, t = start, tok
            toks = []
            for _ in range(6):
                lg, ca = step(ca, t)
                t = jnp.argmax(lg[:, :, : cfg.vocab_size], -1).astype(jnp.int32)
                toks.append(int(t[0, 0]))
            outs.append(toks)
        assert outs[0] == outs[1]

    def test_stream_append_chain_equals_recompute(self):
        """decode_state unit: a chain of flash-appends from the zero state
        equals the one-shot exact stats (the algebra the whole subsystem
        rests on), including the zeros-as-empty anchor convention."""
        from repro.serve.decode_state import recompute_stats, stream_append

        rng = np.random.default_rng(28)
        B, H, c, d, S = 1, 2, 4, 8, 12
        q_l = jnp.asarray(rng.normal(size=(B, H, c, d)), jnp.float32)
        ks = jnp.asarray(rng.normal(size=(B, H, S, d)), jnp.float32)
        vs = jnp.asarray(rng.normal(size=(B, H, S, d)), jnp.float32)
        scale = 0.5
        stats = (jnp.zeros((B, H, c, 1)), jnp.zeros((B, H, c, 1)),
                 jnp.zeros((B, H, c, d)))
        for t in range(S):
            stats = stream_append(stats, q_l, ks[:, :, t], vs[:, :, t], scale)
        m_r, l_r, acc_r = recompute_stats(q_l, ks, vs, S - 1, scale)
        bv_stream = stats[2] / jnp.maximum(stats[1], 1e-30)
        bv_ref = acc_r / jnp.maximum(l_r, 1e-30)
        np.testing.assert_allclose(bv_stream, bv_ref, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(jnp.log(stats[1]) + stats[0]),
            np.asarray(jnp.log(l_r) + m_r), atol=1e-5, rtol=1e-5,
        )


# ==========================================================================
# Gather-free paged decode (ServeConfig.decode_impl="paged")
# ==========================================================================
PAGED_IMPL = dataclasses.replace(BASE, decode_impl="paged")


class TestPagedDecodeImpl:
    """The gather-free decode tick (block-table Pallas kernel + single-
    block scatter commit) against the gather route and the dense engine."""

    def test_token_identical_across_modes(self, qwen):
        """exact and frozen modes: paged impl == gather impl == dense
        engine on greedy, with staggered mixed batches (ragged prompt
        lengths exercise the ragged-last-block path)."""
        cfg, params = qwen
        reqs = _requests(cfg, 5, seed=31)
        for mode in ("exact", "frozen"):
            mcfg = dataclasses.replace(cfg, decode_streaming=mode)
            if mode == "exact":  # dense engine == paged storage invariant
                ref, _ = _run(mcfg, params, reqs, DENSE, stagger=2)
            else:
                # frozen is prefill-path dependent: hold batched prefill
                # fixed and take dense storage as the reference
                ref, _ = _run(mcfg, params, reqs,
                              dataclasses.replace(BASE, paged=False),
                              stagger=2)
            gat, _ = _run(mcfg, params, reqs, BASE, stagger=2)
            assert ref == gat, f"gather != dense reference under {mode}"
            out, eng = _run(mcfg, params, reqs, PAGED_IMPL, stagger=2)
            assert eng.stats()["decode_impl"] == "paged"
            assert gat == out, f"paged != gather under {mode}"

    def test_recompute_falls_back_to_gather(self, qwen):
        """decode_streaming="recompute" rebuilds the dense B matrix: the
        paged request falls back to the gather route (surfaced in stats)
        and stays token-identical."""
        cfg, params = qwen
        mcfg = dataclasses.replace(cfg, decode_streaming="recompute")
        reqs = _requests(mcfg, 3, seed=32)
        ref, _ = _run(mcfg, params, reqs, BASE)
        out, eng = _run(mcfg, params, reqs, PAGED_IMPL)
        assert eng.stats()["decode_impl"] == "gather"
        assert ref == out

    def test_full_attention_impl(self, qwen):
        """decode_attention_impl="full": the same kernel serves the exact-
        attention decode rows (acc / l), token-identical to the gather
        route."""
        cfg, params = qwen
        mcfg = dataclasses.replace(cfg, decode_attention_impl="full")
        reqs = _requests(mcfg, 3, seed=33)
        ref, _ = _run(mcfg, params, reqs, BASE)
        out, eng = _run(mcfg, params, reqs, PAGED_IMPL)
        assert eng.stats()["decode_impl"] == "paged"
        assert ref == out

    def test_preemption_requeue_roundtrip(self, qwen):
        """Pool pressure forces preemption under the paged impl; the
        preempted request recomputes through prefill and finishes with the
        dense engine's greedy output (exact mode)."""
        cfg, params = qwen
        reqs = _requests(cfg, 4, seed=34, lo=20, hi=21, max_new=30)
        serve = dataclasses.replace(
            PAGED_IMPL, max_lanes=3, num_blocks=12)
        ref, _ = _run(cfg, params, reqs,
                      dataclasses.replace(DENSE, max_lanes=3))
        out, eng = _run(cfg, params, reqs, serve)
        assert eng.stats()["preemptions"] > 0
        assert eng.stats()["decode_impl"] == "paged"
        assert ref == out

    def test_zero_block_stays_zero(self, qwen):
        """ZERO_BLOCK backs unallocated table slots; inactive-lane commits
        dump into it and are re-zeroed — after a full run every seq leaf's
        block 0 must be exactly zero."""
        cfg, params = qwen
        reqs = _requests(cfg, 4, seed=35)
        _, eng = _run(cfg, params, reqs, PAGED_IMPL)
        for arr, info in zip(eng.kv._storage, eng.kv.infos):
            if info.seq_axis is None:
                continue
            pre = (slice(None),) * info.seq_axis
            assert np.all(np.asarray(arr[(*pre, ZERO_BLOCK)]) == 0.0)

    def test_pages_counter_matches_positions_and_view(self, qwen):
        """``paged_decode_pages_total``: each paged tick adds, for every
        lane once, under ``streamed`` the pages its position reaches and
        under ``tail`` the rest of the view of the launch that decodes it
        (the first launch's for a lane none decodes) — also in the ticks
        where demoted lanes take a second launch."""
        cfg, params = qwen
        eng = ServeEngine(cfg, params, serve=dataclasses.replace(
            PAGED_IMPL, telemetry=True))
        for r in _requests(cfg, 3, seed=37, lo=6, hi=20, max_new=12):
            eng.submit(r)
        eng._ensure_exact_step()
        calls = []

        def spy(step):
            def fn(storage, tables, tokens, positions, active, nb_view):
                calls.append((np.asarray(positions), np.asarray(active),
                              nb_view))
                return step(storage, tables, tokens, positions, active,
                            nb_view)
            return fn

        eng._fused_step = spy(eng._fused_step)
        eng._exact_step = spy(eng._exact_step)
        fam = eng.telemetry.metrics.get("paged_decode_pages_total")
        bs, lanes = PAGED_IMPL.block_size, PAGED_IMPL.max_lanes
        want = {"streamed": 0, "tail": 0}
        launches = []
        for t in range(6):
            if t == 2:  # pin one decoding lane to the exact program
                lane = next(ln for ln in eng.lanes if not ln.free)
                eng._demoted.add(lane.req.uid)
            eng.tick()
            idle = lanes - sum(int(act.sum()) for _, act, _ in calls)
            for pos, act, nb_view in calls:
                streamed = int(sum(-(-int(p) // bs) for p in pos[act]))
                want["streamed"] += streamed
                want["tail"] += (int(act.sum()) + idle) * nb_view - streamed
                idle = 0
            launches.append(len(calls))
            calls.clear()
            got = {k: fam.labels(kind=k).value for k in want}
            assert got == want
        assert max(launches) == 2
        assert want["streamed"] > 0 and want["tail"] > 0

    def test_mla_paged_decode(self):
        """Absorbed MLA runs gather-free through the two-pool kernel
        (latent + rope), token-identical to the gather route."""
        cfg = dataclasses.replace(
            reduced(get_config("deepseek-v2-lite-16b")), capacity_factor=100.0
        )
        params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
        reqs = _requests(cfg, 3, seed=36)
        ref, _ = _run(cfg, params, reqs, BASE)
        out, eng = _run(cfg, params, reqs, PAGED_IMPL)
        assert eng.stats()["decode_impl"] == "paged"
        assert ref == out

    def test_mla_pools_stored_lane_padded(self):
        """The paged kernel's DMAs move whole 128-lane tiles, so every
        pool is stored lane-padded where it is allocated and the decode
        program never pads, and so never copies, a pool: no ``pad`` in
        it reads an operand with the pool's block axis."""
        cfg = reduced(get_config("deepseek-v2-lite-16b"))
        params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, params, serve=PAGED_IMPL)
        pools = [(info.spec.shape[-1], arr.shape[-1])
                 for arr, info in zip(eng.kv._storage, eng.kv.infos)
                 if info.seq_axis is not None]
        assert pools and all(s % 128 == 0 and s >= w for w, s in pools)
        assert any(s > w for w, s in pools)  # the rope keys are narrower
        lanes, nb = eng.max_lanes, eng.kv.num_blocks
        jaxpr = jax.make_jaxpr(eng._fused_step._jitted)(
            eng.kv._storage, params, jnp.asarray(eng.sched.tables()[:, :2]),
            jnp.zeros((lanes, 1, 1), jnp.int32),
            jnp.full((lanes,), 9, jnp.int32), jnp.ones(lanes, bool))

        def pads(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pad":
                    yield eqn.invars[0].aval.shape
                for v in eqn.params.values():
                    for sub in v if isinstance(v, (list, tuple)) else [v]:
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            yield from pads(sub)

        padded = list(pads(jaxpr.jaxpr))
        assert padded  # the query rows are padded to the pool widths
        assert not [shape for shape in padded if nb in shape], padded

    def test_hybrid_family_paged_decode(self):
        """Hybrid (attention + mamba) lanes: attention leaves page, SSM
        state stays dense; replay prefill feeds the paged tick from
        pos=0 (the kv_valid=0 empty-kernel edge)."""
        cfg = reduced(get_config("hymba-1.5b"))
        params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
        reqs = _requests(cfg, 2, seed=37, lo=4, hi=10, max_new=4)
        ref, _ = _run(cfg, params, reqs, BASE)
        out, eng = _run(cfg, params, reqs, PAGED_IMPL)
        assert eng.stats()["decode_impl"] == "paged"
        assert ref == out

    def test_defragment_mid_stream(self, qwen):
        """Block-table permutation (defragment) between ticks is invisible
        to the paged kernel route."""
        cfg, params = qwen
        reqs = _requests(cfg, 4, seed=38, max_new=12)
        ref, _ = _run(cfg, params, reqs, DENSE)
        eng = ServeEngine(cfg, params, serve=PAGED_IMPL)
        for r in reqs:
            eng.submit(Request(r.uid, list(r.prompt), r.max_new_tokens))
        moved = 0
        for _ in range(60):
            if eng.sched.idle:
                break
            eng.tick()
            moved += eng.defragment()
        out = eng.run()
        assert ref == out
        assert moved > 0


def test_engine_runs_measured_decode_autotune(qwen, tmp_path):
    """ModelConfig.autotune=True: ServeEngine's warm-up runs the measured
    decode sweep (gather vs paged across block_table) at the DEPLOYMENT's
    block size and registers the winner under the decode key."""
    import dataclasses as dc

    from repro.kernels import dispatch

    cfg, params = qwen
    cache = tmp_path / "tuned.json"
    dispatch.clear_registry()
    try:
        eng = ServeEngine(
            dc.replace(cfg, autotune=True, autotune_cache=str(cache)),
            params, serve=BASE,
        )
        assert eng.decode_plan.source == "autotuned"
        assert eng.decode_plan.impl in ("jnp", "paged")
        key = dispatch.make_key(
            BASE.max_seq, cfg.num_landmarks, cfg.resolved_head_dim,
            cfg.compute_dtype, True, family="decode",
        )
        got = dispatch.get_plan(key)  # registered: no re-sweep
        assert (got.impl, got.block_table) == (
            eng.decode_plan.impl, eng.decode_plan.block_table
        )
        assert cache.exists()  # winner persisted to the override path
    finally:
        dispatch.clear_registry()
