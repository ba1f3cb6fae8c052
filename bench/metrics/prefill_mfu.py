"""Model FLOP/s utilization of prefill (%): the operations every prompt
prefilled in the window requires (``counts.prefill_flops``) over the
device time inside the engine's ``prefill`` spans and the chip's peak.
Moves ``itl_p95_ms``: a prefill stalls every decoding lane."""
import layer_stats as ls


def read(ctx):
    m = ls.model(ctx)
    flops = sum(ls.counts.prefill_flops(m, n) for n in ls.prefills(ctx))
    busy = ctx["trace"].busy_within(ctx["trace"].span_intervals("prefill"))
    return ls.mfu(ctx, flops, busy)
