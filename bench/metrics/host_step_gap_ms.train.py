"""Device-idle time per training step in the window (ms): the window's
idle time over the steps done in it, the gap the host leaves between one
step's work and the next. Moves ``train_tokens_per_s``."""
import layer_stats as ls


def read(ctx):
    steps = ls.steps_in_window(ctx)
    if not steps:
        return None
    tr = ctx["trace"]
    return 1e3 * (tr.window_s - tr.busy_s) / steps
