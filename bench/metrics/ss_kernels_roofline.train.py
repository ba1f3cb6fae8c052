"""The spectral-shift training kernels' share of their roofline (%): the
work every step done in the window requires of ``landmark_summary`` and
``query_side``, forward and backward, in every layer and row (``counts``;
recomputation not counted), over the summed device time of every Pallas
kernel of the step: the training step runs no other (``pallas_call``
appears in ``kernels/ss_attention.py``, ``ss_attention_bwd.py`` and
``paged_decode.py`` alone, and the step never decodes). Moves
``train_tokens_per_s``."""
import layer_stats as ls
import peaks


def read(ctx):
    m = ls.model(ctx)
    t = ctx["config"]["train"]
    n, rows = t["seq_len"], t["global_batch"]
    h, hkv, d = m["num_heads"], m["num_kv_heads"], ls.head_dim(m)
    c = m["num_landmarks"]
    parts = [ls.counts.landmark_summary(n, h, hkv, d, c),
             ls.counts.query_side(n, h, d, c),
             ls.counts.landmark_summary_bwd(n, h, hkv, d, c),
             ls.counts.query_side_bwd(n, h, d, c)]
    calls = ls.steps_in_window(ctx) * rows * m["num_layers"]
    flops = calls * sum(f for f, _ in parts)
    bytes_ = calls * sum(b for _, b in parts)
    if flops == 0:
        return None
    return peaks.roofline_share(flops, bytes_,
                                ctx["trace"].kernel_seconds(),
                                ctx["chip"])
