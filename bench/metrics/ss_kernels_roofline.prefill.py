"""The spectral-shift prefill kernels' share of their roofline (%): the
work every prompt prefilled in the window requires of ``landmark_summary``
(twice per layer: the prompt's attention and the decode state's seed) and
``query_side`` (once per layer), from ``counts``, over the summed device
time of every Pallas kernel but the paged decode kernel. Moves ``ttft_p90_ms``."""
import layer_stats as ls
import peaks

# pallas_call appears in kernels/ss_attention.py, ss_attention_bwd.py and
# paged_decode.py alone; the trace names a kernel by the source line of the
# call that made it, which for the fused path is in kernels/ops.py. So every
# kernel of a serving step but the paged decode kernel is a spectral-shift
# kernel.
NOT_SS = ("kernels/paged_decode.py",)


def read(ctx):
    m = ls.model(ctx)
    h, hkv, d = m["num_heads"], m["num_kv_heads"], ls.head_dim(m)
    c = m["num_landmarks"]
    flops = bytes_ = 0.0
    for n in ls.prefills(ctx):
        if n <= c:
            continue
        f1, b1 = ls.counts.landmark_summary(n, h, hkv, d, c)
        f2, b2 = ls.counts.query_side(n, h, d, c)
        flops += m["num_layers"] * (2 * f1 + f2)
        bytes_ += m["num_layers"] * (2 * b1 + b2)
    if flops == 0:
        return None
    return peaks.roofline_share(flops, bytes_,
                                ctx["trace"].kernel_seconds(exclude=NOT_SS),
                                ctx["chip"])
