"""Mean wall time of the engine's ``serve_tick`` spans that lie in the
window (ms):
admission, prefill, decode dispatch, the device sync and sampling of one
tick. Moves ``output_tokens_per_s``."""
import layer_stats as ls


def read(ctx):
    ticks = ls.span_list(ctx, "serve_tick")
    if not ticks:
        return None
    return 1e3 * sum(e - s for s, e in ticks) / len(ticks)
