"""Faults planted under a cell's timed path, to show that ``correct``
catches them (the tests under ``bench/tests``, and fault readings on the
chip through ``control.py --fault``). Never used by a benchmark run."""
from __future__ import annotations

import contextlib

import numpy as np


class _HalfRows:
    """A data source whose second half of every batch is padding (token
    0), which the loss masks out: the step's mean is over the rest."""

    def __init__(self, data):
        self.data = data

    def batch(self, step: int) -> dict:
        tokens = np.array(self.data.batch(step)["tokens"])
        tokens[tokens.shape[0] // 2:] = 0
        return {"tokens": tokens}


def half_batch(trainer) -> None:
    trainer.data = _HalfRows(trainer.data)


def unchanged_state(trainer) -> None:
    """Every step returns the parameters it was given."""
    import jax
    import jax.numpy as jnp

    step = trainer.jitted

    def frozen(params, opt_state, batch):
        kept = jax.tree.map(jnp.copy, params)
        _, opt_state, metrics = step(params, opt_state, batch)
        return kept, opt_state, metrics

    trainer.jitted = frozen


TRAIN = {"half_batch": half_batch, "unchanged_state": unchanged_state}


@contextlib.contextmanager
def altered_tokens(every: int = 7):
    """Every ``every``-th token the serving engine samples is replaced by
    the next id, where it is produced."""
    from repro.serve.engine import ServeEngine

    sample = ServeEngine._sample
    calls = {"n": 0}

    def altered(self, lane, lg):
        tok = sample(self, lane, lg)
        calls["n"] += 1
        return (tok + 1) % self.cfg.vocab_size if calls["n"] % every == 0 \
            else tok

    ServeEngine._sample = altered
    try:
        yield
    finally:
        ServeEngine._sample = sample
