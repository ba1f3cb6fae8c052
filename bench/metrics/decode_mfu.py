"""Model FLOP/s utilization of decoding (%): the operations every token
emitted by a decode step in the window requires (``counts.
decode_token_flops`` at its position) over the window and the chip's
peak. Moves ``output_tokens_per_s``."""
import layer_stats as ls


def read(ctx):
    m = ls.model(ctx)
    max_seq = ctx["config"]["serve"]["max_seq"]
    flops = sum(ls.counts.decode_token_flops(m, pos, max_seq)
                for _, pos in ls.decode_steps(ctx))
    t0, t_end = ctx["window"]
    return ls.mfu(ctx, flops, t_end - t0)
