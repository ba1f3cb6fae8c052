"""Share of the window in which the chip runs the spectral-shift core
(%): the device operations whose ``tf_op`` holds the ``ss_core`` scope
(``core/spectral_shift.py``: the c x c core's iterative pseudo-inverse
and the shift) in the training step, forward and backward, as
the union of their intervals, over the window. None where no operation
carries the scope. Moves ``train_tokens_per_s``."""
import name_scopes


def read(ctx):
    tr = ctx["trace"]
    seconds = name_scopes.scope_seconds(tr, "ss_core")
    if seconds == 0:
        return None
    return 100.0 * seconds / tr.window_s
