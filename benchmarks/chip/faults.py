"""Faults planted under the timed path of a training cell, to show that
the comparison that decides ``correct`` catches them.  Each is a
replacement for the program's ``make_train_step`` factory.

* ``unchanged_state``: the step computes as usual and returns the
  parameters and optimizer state it was given.
* ``half_batch``: the step leaves out the labels of the second half of
  the DP shards, so its loss is the mean over the rest.
"""
from __future__ import annotations

import jax.numpy as jnp


def unchanged_state(cfg, opt_cfg, **kw):
    from repro.training.train_step import make_train_step

    step = make_train_step(cfg, opt_cfg, **kw)

    def broken(params, opt_state, batch):
        _, _, metrics = step(params, opt_state, batch)
        return params, opt_state, metrics

    return broken


def half_batch(cfg, opt_cfg, **kw):
    from repro.training.train_step import make_train_step

    step = make_train_step(cfg, opt_cfg, **kw)

    def broken(params, opt_state, batch):
        labels = batch["llm_labels"]
        keep = jnp.arange(labels.shape[0])[:, None] < labels.shape[0] // 2
        return step(params, opt_state, {**batch, "llm_labels": jnp.where(keep, labels, -1)})

    return broken


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch}
