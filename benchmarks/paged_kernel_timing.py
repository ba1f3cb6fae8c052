"""Time the paged decode kernel alone (``kernels/paged_decode.py``) on the
chip, at the serving deployments' geometries, and check it against a
float32 jnp recompute at the highest matmul precision.

    python benchmarks/paged_kernel_timing.py [--baseline PATH] [--reps N]

Cases (one JSON line each, ``PAGED_KERNEL <case> {...}``):

* ``gqa_*``: Qwen2-7B's decode launch — 32 lanes, 4 kv heads, 7 rows per
  head, d 128, 16-token pages, float32 pools — over a 128-slot view with
  lanes at 64-700 keys, every lane full, and one 8,000-key lane in a
  512-slot view.
* ``mla_*``: absorbed MLA (DeepSeek-V2-Lite: 1 head, latent 512, rope 64
  stored 128 wide, values the latents), 32 lanes of 16 rows, over a
  1,500-block pool and over a pool of ``--fill-blocks`` blocks (10 GiB of
  float32 at the default, one layer's pools filling most of a 16 GB
  chip). ``mla_*_pad_per_call`` times the same launch on a 64-wide rope
  pool that is zero-padded to 128 inside the timed call, the cost a pool
  stored at its model width would pay on every call.

``--baseline`` names another ``paged_decode.py`` (a parent commit's) whose
``paged_row_stats_lanes`` is timed on the same inputs, with the rope pool
at its model width. Timings are medians of ``--reps`` calls after one
warm call. On CPU the cases shrink and the kernels run in interpret
mode, which checks the script and says nothing of speed.
"""
from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_decode import paged_row_stats_lanes
from repro.serve.paged import ZERO_BLOCK, fit_lanes

BS = 16


def _load(path):
    spec = importlib.util.spec_from_file_location("baseline_paged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.paged_row_stats_lanes


@functools.partial(jax.jit, donate_argnums=(0,))
def _fill(pool, ids, vals):
    return pool.at[:, ids].set(vals)   # in place: the pool is donated


def _pool(rng, hkv, nb, width, ids, stored=None, dtype=jnp.float32):
    """A (hkv, nb, BS, stored) pool, zero but for random keys of width
    ``width`` in the blocks ``ids``: a chip-filling pool is never drawn
    whole."""
    stored = stored or width
    vals = jnp.asarray(rng.normal(size=(hkv, len(ids), BS, width)) * 0.5,
                       dtype)
    pool = jnp.zeros((hkv, nb, BS, stored), dtype)
    return _fill(pool, jnp.asarray(ids), fit_lanes(vals, stored))


def _tables(rng, kv, n_slots, nb):
    """Distinct random blocks for the pages each lane's ``kv`` reaches,
    ZERO_BLOCK past them."""
    need = [-(-int(k) // BS) for k in kv]
    ids = rng.choice(np.arange(1, nb), size=sum(need), replace=False)
    tables = np.full((len(kv), n_slots), ZERO_BLOCK, np.int32)
    off = 0
    for lane, n in enumerate(need):
        tables[lane, :n] = ids[off:off + n]
        off += n
    return tables, ids


def _time(fn, args, reps):
    out = jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3, out


def _reference(q, k_pools, v_pool, tables, kv, scale, widths, dv):
    """softmax(scale * q . K[0..kv-1]) V per lane, float32, highest
    precision, over the gathered pages."""
    lanes, hkv = q.shape[:2]
    tb = jnp.asarray(tables)

    def view(pool, width):   # gather the pages first: pools can be huge
        g = jnp.moveaxis(jnp.take(pool, tb, axis=1), 1, 0)
        return g.reshape(lanes, hkv, -1, g.shape[-1])[..., :width]

    with jax.default_matmul_precision("highest"):
        kg = jnp.concatenate(
            [view(p, w) for p, w in zip(k_pools, widths)], -1)
        vg = view(v_pool, dv)
        s = jnp.einsum("lhrd,lhkd->lhrk", q, kg) * scale
        keep = jnp.arange(s.shape[-1]) < jnp.asarray(kv)[:, None, None, None]
        s = jnp.where(keep, s, -jnp.inf)
        return (jnp.einsum("lhrk,lhkd->lhrd", jax.nn.softmax(s, -1), vg),
                jax.nn.logsumexp(s, -1))


def _errors(out, ref, dv):
    m, l, acc = out
    o_ref, lse_ref = ref
    o = acc[..., :dv] / l
    return {"rel_err_out": float(jnp.max(jnp.abs(o - o_ref))
                                 / jnp.max(jnp.abs(o_ref))),
            "abs_err_lse": float(jnp.max(jnp.abs((m + jnp.log(l))[..., 0]
                                                 - lse_ref)))}


def run_case(name, *, rng, lanes, hkv, r, widths, dv, n_slots, nb, kv,
             reps, interpret, baseline=None, pad_per_call=False):
    """One geometry: ``widths`` are the key pools' model widths; with two
    (MLA) the first, the latents, doubles as the value pool."""
    mla = len(widths) == 2
    tables, ids = _tables(rng, kv, n_slots, nb)
    stored = [-(-w // 128) * 128 for w in widths]
    scale = float(sum(widths)) ** -0.5
    q = jnp.asarray(rng.normal(size=(lanes, hkv, r, sum(widths))),
                    jnp.float32)
    q_stored = jnp.concatenate([
        fit_lanes(x, s) for x, s in zip(
            jnp.split(q, np.cumsum(widths)[:-1].tolist(), axis=-1), stored)
    ], -1)
    k_pools = [_pool(rng, hkv, nb, w, ids, s) for w, s in zip(widths, stored)]
    v_pool = k_pools[0] if mla else _pool(rng, hkv, nb, dv, ids)
    args = (jnp.asarray(tables), jnp.asarray(kv, jnp.int32))
    kw = dict(scale=scale, block_size=BS, interpret=interpret)
    res = {"lanes": lanes, "kv_heads": hkv, "rows": r, "widths": widths,
           "stored": stored, "view_slots": n_slots, "num_blocks": nb,
           "mean_keys": float(np.mean(kv)),
           "pool_gib": sum(p.nbytes for p in k_pools
                           + ([] if mla else [v_pool])) / 2**30}
    ref = _reference(q, k_pools, v_pool, tables, kv, scale, widths, dv)

    def launch(kernel, fit=lambda kp: kp):
        # MLA's latent pool enters once and serves as the value pool, as
        # in the decode program: a chip-filling pool is not passed twice
        if mla:
            return jax.jit(lambda q, kp, t, k: kernel(
                q, fit(kp), kp[0], t, k, **kw))
        return jax.jit(lambda q, kp, vp, t, k: kernel(
            q, fit(kp), vp, t, k, **kw))

    pools = lambda kp: (tuple(kp),) if mla else (tuple(kp), v_pool)  # noqa
    res["ms"], out = _time(launch(paged_row_stats_lanes),
                           (q_stored, *pools(k_pools), *args), reps)
    res.update(_errors(out, ref, dv))
    if pad_per_call or baseline is not None:
        # the same keys with the rope pool at its model width
        narrow = k_pools[:1] + [p[..., :w] for p, w in
                                zip(k_pools[1:], widths[1:])]
        del k_pools
    if pad_per_call:
        fn_pad = launch(paged_row_stats_lanes, lambda kp: tuple(
            fit_lanes(p, s) for p, s in zip(kp, stored)))
        res["pad_per_call_ms"], _ = _time(
            fn_pad, (q_stored, *pools(narrow), *args), reps)
    if baseline is not None:
        res["baseline_ms"], out_b = _time(
            launch(baseline), (q, *pools(narrow), *args), reps)
        res["baseline_errors"] = _errors(out_b, ref, dv)
    print("PAGED_KERNEL", name, json.dumps(res), flush=True)
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", help="another paged_decode.py to time")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--fill-blocks", type=int, default=262_144)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    rng = np.random.default_rng(args.seed)
    interpret = jax.default_backend() == "cpu"
    base = _load(args.baseline) if args.baseline else None
    lanes, reps = (32, args.reps) if not interpret else (3, 1)
    small, fill = (1500, args.fill_blocks) if not interpret else (80, 200)
    slots = 128 if not interpret else 8
    gqa = dict(lanes=lanes, hkv=4, r=7, widths=(128,), dv=128)
    mla = dict(lanes=lanes, hkv=1, r=16, widths=(512, 64), dv=512)
    short = rng.integers(64, 700 if not interpret else 120, lanes)
    common = dict(rng=rng, reps=reps, interpret=interpret, baseline=base)
    run_case("gqa_short", **gqa, **common, n_slots=slots, nb=small, kv=short)
    run_case("gqa_full", **gqa, **common, n_slots=slots,
             nb=lanes * slots + 1, kv=np.full(lanes, slots * BS))
    run_case("gqa_one_long", **gqa, **common, n_slots=4 * slots,
             nb=4 * slots + lanes * slots, kv=np.r_[4 * slots * BS - 192,
                                                    short[1:]])
    run_case("mla_short", **mla, **common, n_slots=slots, nb=small,
             kv=short, pad_per_call=True)
    run_case("mla_fill", **mla, **common, n_slots=slots, nb=fill,
             kv=short, pad_per_call=True)
    print("PAGED_KERNEL_DEVICE", jax.devices()[0].device_kind, flush=True)


if __name__ == "__main__":
    main()
