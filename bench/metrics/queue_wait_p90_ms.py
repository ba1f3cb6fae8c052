"""90th percentile over the requests due in the window of the time from
when each was due to its admission into a lane (ms), from the program's
flight lifelines. Moves ``ttft_p90_ms``."""
import layer_stats as ls


def read(ctx):
    t0, t_end = ctx["window"]
    waits = [ctx["admitted"][u] - d for u, d in ctx["due"].items()
             if t0 <= d < t_end and u in ctx["admitted"]]
    p = ls.percentile(waits, 90)
    return None if p is None else 1e3 * p
