"""Device time inside the engine's ``prefill`` spans per 1000 prompt
tokens prefilled in the window (ms). Moves ``itl_p95_ms``: a prefill
stalls every decoding lane."""
import layer_stats as ls


def read(ctx):
    tokens = sum(ls.prefills(ctx))
    if tokens == 0:
        return None
    busy = ctx["trace"].busy_within(ctx["trace"].span_intervals("prefill"))
    return 1e6 * busy / tokens
