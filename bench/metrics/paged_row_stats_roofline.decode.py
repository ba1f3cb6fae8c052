"""The paged decode kernel's share of its roofline (%): the work every
decode step in the window requires of it (each lane's active landmark
row of every head against the keys it has cached, in every layer;
``counts.paged_row_stats``) over the kernel's device time. Moves
``output_tokens_per_s``."""
import layer_stats as ls
import peaks

MODULES = ("kernels/paged_decode.py",)


def read(ctx):
    seconds = ctx["trace"].kernel_seconds(MODULES)
    m = ls.model(ctx)
    flops = bytes_ = 0.0
    for _, pos in ls.decode_steps(ctx):
        f, b = ls.counts.paged_row_stats(pos, m["num_heads"],
                                         m["num_kv_heads"], ls.head_dim(m))
        flops += f * m["num_layers"]
        bytes_ += b * m["num_layers"]
    if flops == 0:
        return None
    return peaks.roofline_share(flops, bytes_, seconds, ctx["chip"])
