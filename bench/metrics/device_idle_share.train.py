"""Share of the window in which no operation runs on the chip (%), from
the profiler's device trace. Moves ``train_tokens_per_s``."""


def read(ctx):
    return 100.0 * ctx["trace"].idle_share
