"""Model FLOP/s utilization of training (%): the forward and backward
operations every token of the steps done in the window requires
(``counts.train_token_flops``, recomputation not counted) over the window
and the peak of the chips used. Moves ``train_tokens_per_s``."""
import layer_stats as ls


def read(ctx):
    m = ls.model(ctx)
    seq = ctx["config"]["train"]["seq_len"]
    flops = ls.steps_in_window(ctx) * ctx["tokens_per_step"] * \
        ls.counts.train_token_flops(m, seq)
    t0, t_end = ctx["window"]
    return ls.mfu(ctx, flops, t_end - t0, ctx["chips"])
