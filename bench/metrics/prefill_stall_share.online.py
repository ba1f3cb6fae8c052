"""Share of the window in which the engine ran a whole-prompt prefill
while at least one other request was part way through decoding (%): the
time decoding lanes stood still. Moves ``itl_p95_ms``."""
import layer_stats as ls
import trace_reduce


def read(ctx):
    t0, t_end = ctx["window"]
    decoding = trace_reduce.union(
        [ts[0], ts[-1]] for ts in ctx["times"].values() if len(ts) > 1)
    stalled = trace_reduce.overlap(ls.spans(ctx, "prefill"), decoding)
    return 100.0 * stalled / (t_end - t0)
