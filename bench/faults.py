"""Faults planted under the timed path.

Each fault wraps the program's jitted train step the way a broken step would
behave; the benchmark's tests and ``calibrate.py`` drive a run through them
and expect ``correct`` to come out false.  (The control, the reference with
int8 products, is ``reference.int8_mm``.)
"""
from __future__ import annotations

import jax


def unchanged(jitted):
    """A step that computes its metrics but returns its state unchanged."""
    raw = jitted.__wrapped__

    def step(params, opt, batch):
        return params, opt, raw(params, opt, batch)[2]

    return jax.jit(step)


def half_batch(jitted):
    """Half of the batch left out and the mean taken over the rest: half the
    rows, or half of each row where the batch is a single row."""
    raw = jitted.__wrapped__

    def step(params, opt, batch):
        t = batch["tokens"]
        t = t[:t.shape[0] // 2] if t.shape[0] > 1 else t[:, :t.shape[1] // 2]
        return raw(params, opt, dict(batch, tokens=t))

    return jax.jit(step, donate_argnums=(0, 1))


FAULTS = {"unchanged": unchanged, "half_batch": half_batch}
