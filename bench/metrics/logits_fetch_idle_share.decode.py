"""Share of the window in which the chip idles inside the engine's
``logits_fetch`` spans (%): the copy of a tick's logits to the host and
their cast to float32, after ``device_wait`` has seen the device finish.
Device-idle time inside those spans over the window. Moves
``output_tokens_per_s``."""


def read(ctx):
    tr = ctx["trace"]
    fetch = tr.span_intervals("logits_fetch")
    if not fetch:
        return None
    span_s = sum(e - s for s, e in fetch) / 1e9
    return 100.0 * (span_s - tr.busy_within(fetch)) / tr.window_s
