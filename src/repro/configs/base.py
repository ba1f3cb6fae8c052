"""Model/run configuration dataclasses shared by every architecture."""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # identity
    name: str = "model"
    family: str = "dense"  # dense | moe | ssm | hybrid | audio | vlm

    # trunk
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 32000
    head_dim: int = 0            # 0 => d_model // num_heads
    qkv_bias: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    act: str = "swiglu"          # swiglu | gelu

    # attention approximation (the paper's technique)
    attention_impl: str = "full"             # training-time self-attention
    decode_attention_impl: str = "spectral_shift"  # KV-cache decode path
    encoder_attention_impl: str = "spectral_shift"  # bidirectional sites
    decode_streaming: str = "exact"    # spectral-shift decode state policy:
                                       # recompute = rebuild B/BV over the
                                       #   whole cache horizon every token
                                       #   (O(c*S*d)/token, the legacy path)
                                       # exact = stream (m, l, BV) stats in
                                       #   the cache; frozen landmark rows
                                       #   flash-append the new key, only the
                                       #   active segment's row is recomputed
                                       #   (O(S*d + c*d)/token, token-
                                       #   identical to recompute on greedy)
                                       # frozen = active row streams too and
                                       #   is rebased lazily at segment
                                       #   boundaries (amortized O(c*d)/token,
                                       #   bounded drift within a segment)
    num_landmarks: int = 64
    ss_method: str = "iterative"
    pinv_iters: int = 6
    include_shift_identity: bool = True
    landmark_via_matmul: bool = False  # GEMM segment-means: required for
                                       # sharded-seq (context-parallel) runs
    cast_params_once: bool = True      # bf16 working copy cast at step entry
                                       # (collectives move bf16, not fp32)
    attention_backend: str = "auto"    # kernel route for *_fused impls:
                                       # auto (dispatch registry) | fused |
                                       # jnp | interpret (forced)
    autotune: bool = False             # measured autotune for unseen shape
                                       # keys (kernels/dispatch.py); winners
                                       # persist to the on-disk cache
    autotune_cache: str = ""           # cache path override ("" = default
                                       # REPRO_AUTOTUNE_CACHE / <repo>/.autotune)
    seq_shard_fused: bool = True       # context-parallel cells keep the fused
                                       # Pallas path via the shard_map driver
                                       # (kernels/sharded.py); False restores
                                       # the legacy jnp-GSPMD downgrade in
                                       # apply_seq_sharding_config

    # MoE
    moe: bool = False
    moe_impl: str = "gspmd"      # gspmd (implicit) | ep (shard_map all-to-all)
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.001

    # MLA (DeepSeek-V2 style)
    mla: bool = False
    kv_lora_rank: int = 0
    rope_head_dim: int = 0

    # SSM / hybrid
    ssm_state: int = 0
    conv_width: int = 4
    slstm_every: int = 0         # xLSTM: every k-th block is sLSTM (0 = none)
    ssm_chunk: int = 256         # chunk length for chunk-parallel SSM scans

    # encoder-decoder (whisper)
    encoder_layers: int = 0
    cross_attention: bool = False
    encoder_seq_ratio: float = 1.0  # encoder length relative to shape seq_len

    # modality frontend stub
    frontend: str = "none"       # none | audio_frames | image_patches
    num_patches: int = 0         # vlm: image-patch count per example

    # numerics / execution
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    scan_layers: bool = True
    remat: str = "full"          # none | full | dots | ss_stats (save only
                                 # the fused-attention (m, l)/BV residuals) |
                                 # auto (per-backend default, REMAT_DEFAULTS)
    unroll_scans: bool = False   # probe mode: unroll chunk scans so XLA
                                 # cost_analysis sees every body (math-identical)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up to a multiple of 256 so TP-16 shards evenly."""
        return -(-self.vocab_size // 256) * 256

    @property
    def is_decoder_only(self) -> bool:
        return self.encoder_layers == 0


# Per-arch remat defaults for ``remat="auto"``, pinned from the measured
# study in results/remat_study.json (benchmarks/remat_study.py; reduced
# dense decoder scaled from the 4k/32k train cells). Measured: ``dots``
# carries the largest fwd->bwd footprint at every cell (+26-38% XLA temp vs
# full at 4k/32k on both routes); ``ss_stats`` matches ``full``'s footprint
# while additionally keeping only the tagged (m, l)/BV attention residuals
# on the kernel route (bench_train_step: ~2.1x smaller vjp residuals at
# 4k), which is the profile that matters on real accelerators — so
# TPU/GPU pin ``ss_stats``. On CPU the dispatch heuristic routes attention
# to jnp (no tagged residuals; ss_stats degenerates to recompute-all) and
# ``full`` is fastest-or-equal at every measured cell, so CPU pins
# ``full``.
REMAT_DEFAULTS: dict[str, str] = {
    "tpu": "ss_stats",
    "gpu": "ss_stats",
    "cpu": "full",
}


def resolve_remat(remat: str, backend: Optional[str] = None) -> str:
    """Map ``remat="auto"`` to the pinned per-arch default (identity for
    every explicit policy)."""
    if remat != "auto":
        return remat
    if backend is None:
        import jax

        backend = jax.default_backend()
    return REMAT_DEFAULTS.get(backend, "full")


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str                 # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                 # train | prefill | decode


SHAPE_PRESETS: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-engine knobs: paged KV cache + two-phase scheduler.

    ``paged=False, batched_prefill=False`` reproduces the seed engine exactly
    (dense per-lane caches, one prompt token per tick); the defaults give the
    vLLM-style engine (shared block pool, one-forward-pass prefill).
    """

    max_lanes: int = 4
    max_seq: int = 512
    block_size: int = 16          # tokens per KV block; must divide max_seq
    num_blocks: int = 0           # 0 => max_lanes * max_seq / block_size
    paged: bool = True            # block-paged pool vs dense per-lane caches
    batched_prefill: bool = True  # whole-prompt forward vs token replay
    prefill_bucket: int = 32      # prompts padded up to a bucket multiple
                                  # (bounds the number of prefill compiles);
                                  # rounded up to a block_size multiple
    prefill_impl: str = "replay"  # replay  = per-token decode math, exact
                                  # ss_fused = Pallas landmark_summary /
                                  #   query_side kernels, approximate prompt
                                  #   attention (landmark state still exact)
    decode_impl: str = "gather"   # decode-tick route over paged storage:
                                  # gather = assemble a transient dense
                                  #   per-lane K/V view each tick (legacy,
                                  #   O(S*d) HBM traffic; the only route for
                                  #   decode_streaming="recompute")
                                  # paged  = gather-free: the block-table
                                  #   Pallas kernel streams K/V straight
                                  #   from the pools and the new token
                                  #   commits via a single-block scatter
                                  #   (kernels/paged_decode.py; falls back
                                  #   to gather when unsupported)
    chunked_prefill: bool = False  # continuous batching: split prefill into
                                   # fixed-size chunks that ride inside the
                                   # decode tick (decode lanes advance every
                                   # tick, long prompts never stall them).
                                   # False reproduces the two-phase engine
                                   # exactly. Needs batched_prefill; falls
                                   # back to whole-prompt for families
                                   # without batched prefill (hybrid/ssm).
    prefill_chunk_tokens: int = 64  # chunk size (one static XLA program);
                                    # rounded up to a block_size multiple so
                                    # chunks commit whole blocks
    prefill_token_budget: int = 0   # max prompt tokens chunk-prefilled per
                                    # tick across all lanes; 0 = one chunk.
                                    # At least one chunk always runs when a
                                    # prefill is pending (no livelock).
    prefix_cache: bool = False    # content-hash prefix caching over the
                                  # block pool: prompts are hashed block by
                                  # block (chained hashes) and a matching
                                  # cached prefix maps its physical blocks
                                  # into the new request's table with
                                  # refcounts + copy-on-write. Implies the
                                  # continuous-batching (chunked) tick for
                                  # partial-hit resume; needs paged=True
                                  # (silently off for dense caches). False
                                  # reproduces the non-caching engine
                                  # byte for byte.
    prefix_cache_blocks: int = 0  # cap on pool blocks the prefix cache may
                                  # retain for finished requests (LRU-evicted
                                  # beyond it); 0 = bounded only by pool
                                  # pressure (allocation shortfalls evict)
    prefix_attach: str = "reseg"  # streaming-stat seeding on a cache hit:
                                  # reseg    = reuse the entry's stats stored
                                  #   at the canonical segmentation, running
                                  #   the O(c*d) re-segmentation program only
                                  #   if the lane's horizon segmentation
                                  #   differs (it never does within one
                                  #   engine, so a full hit is pure host
                                  #   work)
                                  # recompute = always re-derive the stats
                                  #   from the shared K/V blocks via the
                                  #   prefill handoff program (correctness
                                  #   fallback; token-identity-tested)
    eos_id: int = 2
    seed: int = 0
    telemetry: bool = False       # unified metrics/tracing/drift monitors
                                  # (src/repro/telemetry): off = no-op
                                  # registry + tracer on the hot path, no
                                  # extra device programs; the scheduler's
                                  # latency percentiles work either way
    numerics_probe_every: int = 0  # every N ticks, count NaN/Inf in decode
                                   # logits and the landmark (m, l) stats
                                   # (numerics_nonfinite_total{site=}); 0 =
                                   # off. Each probe forces a host sync, so
                                   # this is a cadence, not a boolean.
                                   # Requires telemetry=True to count.
    max_queue: int = 0            # admission-queue bound: a submit() that
                                  # would grow the waiting queue past this
                                  # is REJECTED (engine.submit returns
                                  # False, serve_rejected_total counts it,
                                  # the flight "reject" event carries a
                                  # retry_after_ticks hint). 0 = unbounded
                                  # (the pre-backpressure behavior).
    watchdog_ticks: int = 0       # no-progress watchdog: after N
                                  # consecutive ticks with work pending but
                                  # zero progress (no token, no chunk, no
                                  # prefill, no admission) the engine walks
                                  # the escalation ladder — reclaim parked
                                  # blocks, preempt the youngest lane, and
                                  # only as the last rung raise a
                                  # structured EngineStalled. 0 = off. A
                                  # healthy run never trips it, so any
                                  # value is output-identical to 0.
    numerics_guard: bool = False  # online non-finite defense for the
                                  # streaming decode state: after every
                                  # decode dispatch, check each active
                                  # lane's logits row and landmark
                                  # (m, l, acc) stats on the host;
                                  # corrupted stats under finite logits
                                  # quarantine the lane and rebuild its
                                  # stats exactly from cached K/V (the
                                  # prefix-attach reseed program);
                                  # corrupted logits replay-preempt the
                                  # lane (full recompute). Forces a host
                                  # sync per tick — a correctness posture,
                                  # not a fast path. Works without
                                  # telemetry (counters live on the
                                  # scheduler's always-real registry).
    numerics_demote_after: int = 2  # guard trips per request before a
                                    # frozen-mode lane is demoted to
                                    # decode_streaming="exact" for the rest
                                    # of its life (numerics_demotions_total
                                    # counts it); exact mode recomputes the
                                    # active row per tick, so a stats
                                    # corruptor can't keep re-poisoning the
                                    # drift window.

    @property
    def blocks_per_lane(self) -> int:
        return self.max_seq // self.block_size

    @property
    def resolved_num_blocks(self) -> int:
        # +1: block 0 is reserved as the permanently-zero block that backs
        # unallocated block-table slots.
        n = self.num_blocks or self.max_lanes * self.blocks_per_lane
        # One lane must always be able to hold a full sequence, or a lone
        # request could deadlock preempting itself forever.
        return max(n, self.blocks_per_lane) + 1

    def __post_init__(self):
        # Only the block-paged layout needs the divisibility; the dense
        # seed-compat mode accepts any max_seq, as the seed engine did.
        if self.paged and self.max_seq % self.block_size:
            raise ValueError(
                f"block_size {self.block_size} must divide max_seq "
                f"{self.max_seq} (or set paged=False)"
            )
        if self.prefill_impl not in ("replay", "ss_fused"):
            raise ValueError(f"unknown prefill_impl {self.prefill_impl!r}")
        if self.decode_impl not in ("gather", "paged"):
            raise ValueError(f"unknown decode_impl {self.decode_impl!r}")
        if self.numerics_probe_every < 0:
            raise ValueError(
                f"numerics_probe_every must be >= 0, "
                f"got {self.numerics_probe_every}"
            )
        if self.chunked_prefill and not self.batched_prefill:
            raise ValueError(
                "chunked_prefill=True requires batched_prefill=True (chunks "
                "are bucketed batched-prefill programs)"
            )
        if self.prefill_chunk_tokens <= 0:
            raise ValueError(
                f"prefill_chunk_tokens must be > 0, "
                f"got {self.prefill_chunk_tokens}"
            )
        if self.prefill_token_budget < 0:
            raise ValueError(
                f"prefill_token_budget must be >= 0, "
                f"got {self.prefill_token_budget}"
            )
        if self.prefix_attach not in ("reseg", "recompute"):
            raise ValueError(f"unknown prefix_attach {self.prefix_attach!r}")
        if self.prefix_cache_blocks < 0:
            raise ValueError(
                f"prefix_cache_blocks must be >= 0, "
                f"got {self.prefix_cache_blocks}"
            )
        if self.prefix_cache and not self.batched_prefill:
            raise ValueError(
                "prefix_cache=True requires batched_prefill=True (partial "
                "hits resume through chunked batched prefill)"
            )
        if self.max_queue < 0:
            raise ValueError(
                f"max_queue must be >= 0, got {self.max_queue}"
            )
        if self.watchdog_ticks < 0:
            raise ValueError(
                f"watchdog_ticks must be >= 0, got {self.watchdog_ticks}"
            )
        if self.numerics_demote_after < 1:
            raise ValueError(
                f"numerics_demote_after must be >= 1, "
                f"got {self.numerics_demote_after}"
            )


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Optimizer / trainer knobs (used by the real training driver)."""

    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    microbatches: int = 1        # grad-accumulation steps
    opt_state_dtype: str = "float32"
    grad_compression: Optional[str] = None  # None | "int8"
    checkpoint_every: int = 200
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    seed: int = 0


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """Shrink a config to smoke-test size, preserving its family shape
    (GQA ratios, MoE top-k, MLA ranks scale down proportionally)."""
    kv_ratio = max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))
    heads = 4
    small: dict = dict(
        num_layers=2,
        d_model=128,
        num_heads=heads,
        num_kv_heads=max(1, heads // kv_ratio),
        d_ff=256,
        vocab_size=512,
        head_dim=32 if cfg.head_dim else 0,
        num_landmarks=16,
        scan_layers=cfg.scan_layers,
        remat="none",
        compute_dtype="float32",
    )
    if cfg.moe:
        small.update(num_experts=8, num_shared_experts=min(cfg.num_shared_experts, 1),
                     top_k=min(cfg.top_k, 2), moe_d_ff=64)
    if cfg.mla:
        small.update(kv_lora_rank=32, rope_head_dim=16)
    if cfg.ssm_state:
        small.update(ssm_state=8)
    if cfg.encoder_layers:
        small.update(encoder_layers=2)
    if cfg.num_patches:
        small.update(num_patches=16)
    small.update(overrides)
    return dataclasses.replace(cfg, **small)
