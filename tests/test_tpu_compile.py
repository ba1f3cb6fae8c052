"""Mosaic compiles of the main-path Pallas kernels at published widths.

Each test compiles (never runs) one kernel program for a described TPU
v5e:2x2 topology and checks that the compiled HLO holds the kernel as a
``tpu_custom_call`` — interpret mode and the jnp route lower to plain HLO,
so this fails if either sneaks in. Shapes are Qwen2-7B's attention widths
(28 query heads, 4 kv heads, head_dim 128), n=4096, c=64, bf16; the MLA
pool case uses DeepSeek-V2-Lite's latent/rope split (512 + 64).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file. JAX's persistent compilation cache is off around these
compiles (an entry written for a described chip cannot be read back here).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.attention import SSConfig
from repro.kernels.ops import landmark_summary_op, query_side_op
from repro.kernels.paged_decode import lane_width, paged_row_stats_lanes
from repro.kernels.sharded import ss_attention_fused_sharded
from repro.kernels.ss_attention import landmark_summary, query_side

HEADS, KV_HEADS, N, D, C = 28, 4, 4096, 128, 64
SCALE = D ** -0.5
BLOCK_N = 512
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_calls(fn, *args) -> int:
    """Compile ``fn`` for the argument shapes; count Mosaic kernel calls."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _kernel_names(fn, *args) -> list[tuple[str, str]]:
    """Compile ``fn``; for each Mosaic kernel call, in program order, its
    HLO instruction name and the kernel name in its op metadata
    (``.../<name>/pallas_call``, or ``.../jvp(<name>)/pallas_call`` and
    the like under a transformation)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    out = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        instr = re.search(r"%([\w.\-]+) = ", line).group(1)
        kernel = re.search(r'op_name="[^"]*/(?:\w+\()*(\w+)\)*/pallas_call"',
                           line).group(1)
        out.append((instr, kernel))
    return out


def _shape(sharding, *dims, dtype=BF16):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


@pytest.mark.parametrize("variant", ["bidirectional", "causal", "kv_valid"])
def test_landmark_summary_forward(one_chip, variant):
    q_l = _shape(one_chip, HEADS, C, D)
    kv = _shape(one_chip, HEADS, N, D)
    causal = variant == "causal"
    if variant == "kv_valid":
        fn = lambda q_l, k, v, n_valid: landmark_summary(  # noqa: E731
            q_l, k, v, scale=SCALE, block_n=BLOCK_N, interpret=False,
            kv_valid=n_valid,
        )
        args = (q_l, kv, kv, _shape(one_chip, dtype=jnp.int32))
    else:
        fn = lambda q_l, k, v: landmark_summary(  # noqa: E731
            q_l, k, v, scale=SCALE, block_n=BLOCK_N, causal=causal,
            interpret=False,
        )
        args = (q_l, kv, kv)
    assert _kernel_calls(fn, *args) >= 1


@pytest.mark.parametrize("causal", [False, True])
def test_query_side_forward(one_chip, causal):
    fn = lambda q, k_l, m_mat, v, delta: query_side(  # noqa: E731
        q, k_l, m_mat, v, delta, scale=SCALE, block_n=BLOCK_N, causal=causal,
        interpret=False,
    )
    args = (
        _shape(one_chip, HEADS, N, D), _shape(one_chip, HEADS, C, D),
        _shape(one_chip, HEADS, C, D), _shape(one_chip, HEADS, N, D),
        _shape(one_chip, HEADS, 1, 1, dtype=jnp.float32),
    )
    assert _kernel_calls(fn, *args) >= 1


@pytest.mark.parametrize("causal", [False, True])
def test_landmark_summary_backward(one_chip, causal):
    meta = (SCALE, BLOCK_N, 0, causal, False)

    def loss(q_l, k, v):
        return jnp.sum(landmark_summary_op(meta, q_l, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2))
    args = (_shape(one_chip, HEADS, C, D), _shape(one_chip, HEADS, N, D),
            _shape(one_chip, HEADS, N, D))
    assert _kernel_calls(fn, *args) >= 2  # forward + backward kernel


@pytest.mark.parametrize("causal", [False, True])
def test_query_side_backward(one_chip, causal):
    meta = (SCALE, BLOCK_N, causal, N, False)

    def loss(q, k_l, m_mat, v, delta):
        out = query_side_op(meta, q, k_l, m_mat, v, delta)
        return jnp.sum(out.astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2, 3))
    args = (
        _shape(one_chip, HEADS, N, D), _shape(one_chip, HEADS, C, D),
        _shape(one_chip, HEADS, C, D), _shape(one_chip, HEADS, N, D),
        _shape(one_chip, HEADS, 1, 1, dtype=jnp.float32),
    )
    # The forward output is dead in a grad-only program (its residuals are
    # the inputs), so the one kernel left is the backward.
    assert _kernel_calls(fn, *args) == 1


@pytest.mark.parametrize(
    "lanes,hkv,r,splits,dv,pool_dtype",
    [(8, KV_HEADS, HEADS // KV_HEADS, (D,), D, BF16),   # GQA: one key pool
     # MLA: latent + rope pools, the 64-wide rope keys stored 128 wide
     (8, 1, 16, (512, lane_width(64)), 512, BF16),
     # the serving deployment: 32 lanes over float32 pools
     (32, KV_HEADS, HEADS // KV_HEADS, (D,), D, jnp.float32)],
    ids=["one_pool", "two_pools_mla", "deployment_f32"],
)
def test_paged_row_stats_lanes(one_chip, lanes, hkv, r, splits, dv,
                               pool_dtype):
    block, horizon = 16, 2048                # 128 slots of 16
    num_blocks = lanes * horizon // block + 1

    def fn(q, *pools_table_valid):
        *k_pools, v_pool, table, kv_valid = pools_table_valid
        return paged_row_stats_lanes(
            q, tuple(k_pools), v_pool, table, kv_valid, scale=SCALE,
            block_size=block, interpret=False,
        )

    args = (
        _shape(one_chip, lanes, hkv, r, sum(splits), dtype=jnp.float32),
        *(_shape(one_chip, hkv, num_blocks, block, dp, dtype=pool_dtype)
          for dp in splits),
        _shape(one_chip, hkv, num_blocks, block, dv, dtype=pool_dtype),
        _shape(one_chip, lanes, horizon // block, dtype=jnp.int32),
        _shape(one_chip, lanes, dtype=jnp.int32),
    )
    assert _kernel_calls(fn, *args) >= 1


def test_sharded_fused_over_four_chips(topo):
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    seq = NamedSharding(mesh, P(None, None, "model", None))

    def fn(q, k, v):
        return ss_attention_fused_sharded(
            q, k, v, SSConfig(num_landmarks=C), mesh=mesh,
            seq_axes=("model",), block_n=BLOCK_N, interpret=False,
        )

    args = [_shape(seq, 1, HEADS, N, D) for _ in range(3)]
    assert _kernel_calls(fn, *args) >= 2  # landmark_summary + query_side


@pytest.mark.parametrize("kernel", [
    "landmark_summary", "query_side", "landmark_summary_bwd",
    "query_side_bwd", "paged_row_stats"])
def test_kernel_carries_its_name(one_chip, kernel):
    """Each kernel's ``pallas_call`` names it: the name is in the op
    metadata of its ``tpu_custom_call`` (and so in a device trace's
    ``tf_op``) and names the HLO instruction, whatever line calls it."""
    kv = _shape(one_chip, HEADS, N, D)
    lm = _shape(one_chip, HEADS, C, D)
    delta = _shape(one_chip, HEADS, 1, 1, dtype=jnp.float32)
    if kernel == "landmark_summary":
        fn = lambda q_l, k, v: landmark_summary(  # noqa: E731
            q_l, k, v, scale=SCALE, block_n=BLOCK_N, interpret=False)
        args, want = (lm, kv, kv), ["landmark_summary"]
    elif kernel == "query_side":
        fn = lambda q, k_l, m_mat, v, d: query_side(  # noqa: E731
            q, k_l, m_mat, v, d, scale=SCALE, block_n=BLOCK_N,
            interpret=False)
        args, want = (kv, lm, lm, kv, delta), ["query_side"]
    elif kernel == "landmark_summary_bwd":
        meta = (SCALE, BLOCK_N, 0, False, False)
        fn = jax.grad(lambda q_l, k, v: jnp.sum(landmark_summary_op(
            meta, q_l, k, v).astype(jnp.float32)), argnums=(0, 1, 2))
        args = (lm, kv, kv)
        want = ["landmark_summary", "landmark_summary_bwd"]
    elif kernel == "query_side_bwd":
        meta = (SCALE, BLOCK_N, False, N, False)
        fn = jax.grad(lambda q, k_l, m_mat, v, d: jnp.sum(query_side_op(
            meta, q, k_l, m_mat, v, d).astype(jnp.float32)),
            argnums=(0, 1, 2, 3))
        args, want = (kv, lm, lm, kv, delta), ["query_side_bwd"]
    else:
        lanes, block, horizon = 8, 16, 2048
        num_blocks = lanes * horizon // block + 1

        def fn(q, k_pool, v_pool, table, kv_valid):
            return paged_row_stats_lanes(
                q, (k_pool,), v_pool, table, kv_valid, scale=SCALE,
                block_size=block, interpret=False)

        pool = _shape(one_chip, KV_HEADS, num_blocks, block, D)
        args = (_shape(one_chip, lanes, KV_HEADS, HEADS // KV_HEADS, D,
                       dtype=jnp.float32), pool, pool,
                _shape(one_chip, lanes, horizon // block, dtype=jnp.int32),
                _shape(one_chip, lanes, dtype=jnp.int32))
        want = ["paged_row_stats"]
    calls = _kernel_names(fn, *args)
    assert sorted({kernel for _, kernel in calls}) == want
    for instr, kernel in calls:
        assert kernel in instr, (instr, kernel)
