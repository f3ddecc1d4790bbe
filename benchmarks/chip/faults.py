"""Faults planted under the timed path, to show that ``correct`` catches
them: each is a context manager that breaks one thing in the program while
a driver sets up, runs and is checked. The one-chip cells have no exchange
between chips, so that fault has no entry here.

- ``state_unchanged``: a round returns the model it was given;
- ``half_batch``: each loss is taken over the first half of its rows only;
- ``answer_altered``: the round's answer, the new global model (the
  simulator's AirComp output, the server's updated parameters), is off by
  1%;
- ``decay_index``: the simulator's learning rate one step ahead on its
  schedule, η0·decay^(t+1) for η0·decay^t (0.2% at the paper's decay).
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _off_by_one_percent(tree):
    return jax.tree.map(lambda x: x * 1.01, tree)


# ---- the simulator's sweep ------------------------------------------------

@contextlib.contextmanager
def sweep_state_unchanged():
    from repro.core import sweep

    orig = sweep.make_param_round_fn

    def make(*a, **kw):
        round_fn = orig(*a, **kw)

        def broken(point, state, t):
            new, metrics = round_fn(point, state, t)
            return new._replace(w=state.w), metrics
        return broken

    with patched(sweep, "make_param_round_fn", make):
        yield


@contextlib.contextmanager
def sweep_half_batch():
    from repro.models import logreg

    orig = logreg.logistic_regression

    def make(*a, **kw):
        m = orig(*a, **kw)

        def loss(params, x, y):
            half = x.shape[0] // 2
            return m.loss(params, x[:half], y[:half])
        return m._replace(loss=loss)

    with patched(logreg, "logistic_regression", make):
        yield


@contextlib.contextmanager
def sweep_answer_altered():
    from repro.core import simulator

    orig = simulator.aircomp_aggregate_stack_tree

    def broken(*a, **kw):
        return _off_by_one_percent(orig(*a, **kw))

    with patched(simulator, "aircomp_aggregate_stack_tree", broken):
        yield


@contextlib.contextmanager
def sweep_decay_index():
    from repro.core import sweep

    orig = sweep.make_param_round_fn

    def make(*a, **kw):
        round_fn = orig(*a, **kw)

        def broken(point, state, t):
            ahead = dataclasses.replace(point,
                                        lr0=point.lr0 * point.lr_decay)
            return round_fn(ahead, state, t)
        return broken

    with patched(sweep, "make_param_round_fn", make):
        yield


# ---- the parameter server -------------------------------------------------

@contextlib.contextmanager
def server_state_unchanged():
    from repro.federated import rounds

    orig = rounds._make_gather_round

    def make(*a, **kw):
        round_fn = orig(*a, **kw)

        def broken(params, opt_state, batch, mask, idx, key):
            _, _, metrics = round_fn(params, opt_state, batch, mask, idx,
                                     key)
            return params, opt_state, metrics
        return broken

    with patched(rounds, "_make_gather_round", make):
        yield


@contextlib.contextmanager
def server_half_batch():
    from repro.federated import rounds

    orig = rounds._per_example_nll

    def broken(model, params, batch, ctx):
        half = jax.tree.map(lambda v: v[:v.shape[0] // 2], batch)
        per_ex = orig(model, params, half, ctx)
        return jnp.concatenate([per_ex, per_ex])

    with patched(rounds, "_per_example_nll", broken):
        yield


@contextlib.contextmanager
def server_answer_altered():
    from repro.federated import rounds

    orig = rounds._make_gather_round

    def make(*a, **kw):
        round_fn = orig(*a, **kw)

        def broken(*args):
            params, opt_state, metrics = round_fn(*args)
            return _off_by_one_percent(params), opt_state, metrics
        return broken

    with patched(rounds, "_make_gather_round", make):
        yield


FAULTS = {
    "sweep": {"state_unchanged": sweep_state_unchanged,
              "half_batch": sweep_half_batch,
              "answer_altered": sweep_answer_altered,
              "decay_index": sweep_decay_index},
    "server": {"state_unchanged": server_state_unchanged,
               "half_batch": server_half_batch,
               "answer_altered": server_answer_altered},
}
