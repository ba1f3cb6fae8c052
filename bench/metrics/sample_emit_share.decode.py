"""Share of the window spent in the engine's ``sample_emit`` spans (%):
host-side sampling over every lane's logits and token emission. Moves
``output_tokens_per_s``."""
import layer_stats as ls


def read(ctx):
    t0, t_end = ctx["window"]
    sel = ls.spans(ctx, "sample_emit")
    if not sel:
        return None
    return 100.0 * sum(e - s for s, e in sel) / (t_end - t0)
