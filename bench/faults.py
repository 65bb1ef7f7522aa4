"""Faults planted in the program, to show that the comparison catches them.

Each takes the runner and breaks its timed path underneath, by wrapping the
runner's own calls on the instance:

* ``stale_step``: the dense step returns the replica and optimizer state it
  was given;
* ``half_batch``: the step sees only the first half of each batch, so the
  loss is the mean over that half and the other half's rows get no update;
* ``no_exchange``: the shadow round leaves the replicas and the centre as
  they were;
* ``shifted_grad``: the pooled gradient is handed to the sparse update one
  example out of place, so rows receive another bag's gradient;
* ``wrong_replica``: the runner hands the step the next trainer's replica
  in place of the trainer's own."""
from __future__ import annotations

import jax.numpy as jnp


def stale_step(runner):
    orig = runner._train_one

    def call(w, opt_state, tables, batch):
        _, _, loss, g = orig(w, opt_state, tables, batch)
        return w, opt_state, loss, g

    runner._train_one = call


def half_batch(runner):
    orig = runner._train_one

    def call(w, opt_state, tables, batch):
        n = batch["labels"].shape[0]
        half = {k: v[: n // 2] for k, v in batch.items()}
        w, opt_state, loss, g = orig(w, opt_state, tables, half)
        return w, opt_state, loss, jnp.concatenate([g, jnp.zeros_like(g)], axis=0)

    runner._train_one = call


def no_exchange(runner):
    def call(ws, state):
        return state, len(ws)

    runner._shadow_round = call


def shifted_grad(runner):
    orig = runner._train_one

    def call(w, opt_state, tables, batch):
        w, opt_state, loss, g = orig(w, opt_state, tables, batch)
        return w, opt_state, loss, jnp.roll(g, 1, axis=0)

    runner._train_one = call


def wrong_replica(runner):
    orig = runner._train_one

    def call(w, opt_state, tables, batch):
        ws = getattr(runner, "w", [])  # none yet while the runner warms up
        i = next((k for k, v in enumerate(ws) if v is w), None)
        return orig(w if i is None else ws[(i + 1) % len(ws)], opt_state, tables, batch)

    runner._train_one = call


FAULTS = {f.__name__: f for f in (stale_step, half_batch, no_exchange, shifted_grad,
                                  wrong_replica)}
