"""Production FL tier: weighted-loss aggregation semantics, microbatching,
AWGN, server loop, partitioners."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import FLConfig
from repro.data.synthetic import make_lm_tokens
from repro.federated import (ParameterServer, client_weights, make_fl_round,
                             per_client_losses, sorted_label_shards)
from repro.models.api import build_model
from repro.optim import sgd


@pytest.fixture(scope="module")
def small_model():
    cfg = get_reduced("qwen2-0.5b").with_(dtype="float32", remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _fl_batch(cfg, key, n_clients=4, per_client=2, s=16):
    b = n_clients * per_client
    return {
        "tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "client_ids": jnp.repeat(jnp.arange(n_clients), per_client),
    }


def test_client_weights_scaling():
    mask = jnp.array([1.0, 0.0, 1.0, 0.0])
    cids = jnp.array([0, 0, 1, 2, 3, 3])
    w = client_weights(mask, cids, k=2.0)
    np.testing.assert_allclose(w, [2, 2, 0, 2, 0, 0])  # N/K = 2


def test_selection_mask_gates_gradient(small_model, key):
    """Unselected clients contribute NOTHING to the aggregated update."""
    cfg, model, params = small_model
    opt = sgd(0.1)
    batch = _fl_batch(cfg, key)
    rnd = jax.jit(make_fl_round(model, opt, 4, 2))
    mask_a = jnp.array([1.0, 1.0, 0.0, 0.0])
    p_a, _, _ = rnd(params, opt.init(params), batch, mask_a, key)
    # perturb an UNSELECTED client's data: update must not change
    batch2 = dict(batch)
    batch2["tokens"] = batch["tokens"].at[4:].set(0)  # clients 2,3 rows
    p_b, _, _ = rnd(params, opt.init(params), batch2, mask_a, key)
    for a, b in zip(jax.tree_util.tree_leaves(p_a),
                    jax.tree_util.tree_leaves(p_b), strict=True):
        np.testing.assert_allclose(a, b, atol=1e-7)


def test_microbatch_equivalence(small_model, key):
    cfg, model, params = small_model
    opt = sgd(0.1)
    batch = _fl_batch(cfg, key, n_clients=4, per_client=2)
    mask = jnp.array([1.0, 0.0, 1.0, 0.0])
    r1 = jax.jit(make_fl_round(model, opt, 4, 2, microbatches=1))
    r4 = jax.jit(make_fl_round(model, opt, 4, 2, microbatches=4))
    p1, _, m1 = r1(params, opt.init(params), batch, mask, key)
    p4, _, m4 = r4(params, opt.init(params), batch, mask, key)
    np.testing.assert_allclose(m1.loss, m4.loss, rtol=1e-5)
    np.testing.assert_allclose(m1.client_losses, m4.client_losses, rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p4), strict=True):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=2e-3)


def test_awgn_statistics(small_model, key):
    from repro.federated.rounds import add_awgn
    grads = {"big": jnp.zeros((16, 64, 64)), "small": jnp.zeros((7,))}
    noisy = add_awgn(grads, key, std=0.5)
    assert abs(float(jnp.std(noisy["big"])) - 0.5) < 0.02
    # scan path and direct path both seeded deterministically
    noisy2 = add_awgn(grads, key, std=0.5)
    np.testing.assert_allclose(noisy["big"], noisy2["big"])


# NOISE_BLOCK_ELEMS is patched to 256 elements: a 16-wide row gives blocks of
# 16 rows, a [20, 16] row (320 elements) one row a step
@pytest.mark.parametrize("shape,dtype", [
    ((37, 16), jnp.float32),      # 2 blocks of 16 rows + 5 left over
    ((48, 16), jnp.float32),      # 3 blocks, no remainder
    ((6, 16), jnp.float32),       # one block: no loop
    ((9, 20, 16), jnp.float32),   # a row above the budget: one row a step
    ((4, 100), jnp.float32),      # shape[0] <= 4: one draw for the leaf
    ((37, 16), jnp.bfloat16),
], ids=["remainder", "even", "one_block", "row_over_budget", "short_lead",
        "bf16"])
def test_awgn_block_stream_matches_rows(monkeypatch, key, shape, dtype):
    """The blocked draw gives the bits of one ``fold_in(leaf_key, i)`` draw
    per row (or one draw per short leaf), whatever the block size."""
    from repro.federated import rounds
    monkeypatch.setattr(rounds, "NOISE_BLOCK_ELEMS", 256)
    g = jax.random.normal(jax.random.PRNGKey(7), shape).astype(dtype)
    grads = {"a": jnp.zeros((3,)), "b": g}
    std = jnp.float32(0.5)

    def per_row(g, key, std):
        k_leaf = jax.random.split(key, 2)[1]
        if shape[0] <= 4:
            return g + std * jax.random.normal(k_leaf, shape, dtype)
        z = jax.vmap(lambda i: jax.random.normal(
            jax.random.fold_in(k_leaf, i), shape[1:], dtype))(
                jnp.arange(shape[0]))
        return g + std * z

    got = jax.jit(rounds.add_awgn)(grads, key, std)["b"]
    want = jax.jit(per_row)(g, key, std)
    assert got.dtype == want.dtype
    assert bool(jnp.array_equal(got, want))


def test_awgn_embedding_loop_steps_follow_budget(monkeypatch, key):
    """An embedding-shaped leaf [R, 896] loops over blocks of rows, never
    over its R rows one at a time."""
    from repro.federated import rounds
    budget, r = 1 << 16, 1000
    monkeypatch.setattr(rounds, "NOISE_BLOCK_ELEMS", budget)
    jaxpr = jax.make_jaxpr(rounds.add_awgn)(
        {"embed": jnp.zeros((r, 896))}, key, 1e-3)

    def loop_lengths(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                yield eqn.params["length"]
            assert eqn.primitive.name != "while"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from loop_lengths(sub)

    lengths = list(loop_lengths(jaxpr.jaxpr))
    assert lengths and max(lengths) <= r // (budget // 896)


def test_per_client_losses_segment_mean(small_model, key):
    cfg, model, params = small_model
    batch = _fl_batch(cfg, key, n_clients=4, per_client=2)
    losses = per_client_losses(model, params, batch, 4)
    assert losses.shape == (4,)
    assert bool(jnp.all(jnp.isfinite(losses)))
    # microbatched probe identical
    losses2 = per_client_losses(model, params, batch, 4, microbatches=2)
    np.testing.assert_allclose(losses, losses2, rtol=1e-5)


def test_server_loop_energy_and_lambda(small_model, key):
    cfg, model, _ = small_model
    fl = FLConfig(num_clients=4, clients_per_round=2, rounds=4,
                  method="ca_afl", energy_C=8.0, noise_std=0.0)
    ps = ParameterServer(model, sgd(0.05), fl, seed=0)
    state = ps.init_state(key)

    def batches():
        k = key
        while True:
            k = jax.random.fold_in(k, 1)
            yield _fl_batch(cfg, k)

    state = ps.run(state, batches(), rounds=4, log_fn=None)
    assert state.round == 4
    assert state.energy_joules > 0
    np.testing.assert_allclose(float(jnp.sum(state.lam)), 1.0, atol=1e-4)
    assert len(state.history) == 4
    assert all(np.isfinite(h["loss"]) for h in state.history)


def test_greedy_uses_less_energy_than_fedavg(small_model, key):
    """The Prop. 2 limit is the energy-optimal selection."""
    cfg, model, _ = small_model
    res = {}
    for method in ("greedy", "fedavg"):
        fl = FLConfig(num_clients=8, clients_per_round=3, rounds=6,
                      method=method, noise_std=0.0)
        ps = ParameterServer(model, sgd(0.01), fl, seed=1)
        state = ps.init_state(key)

        def batches():
            k = key
            while True:
                k = jax.random.fold_in(k, 2)
                yield _fl_batch(cfg, k, n_clients=8, per_client=1)

        res[method] = ps.run(state, batches(), rounds=6,
                             log_fn=None).energy_joules
    assert res["greedy"] < res["fedavg"]


def test_sorted_label_shards_heterogeneity():
    x = np.arange(100, dtype=np.float32)[:, None]
    y = np.repeat(np.arange(10), 10).astype(np.int32)
    rng = np.random.default_rng(0)
    perm = rng.permutation(100)
    xs, ys = sorted_label_shards(x[perm], y[perm], 10)
    assert xs.shape == (10, 10, 1)
    # each client sees exactly one label (maximal heterogeneity)
    for c in range(10):
        assert len(np.unique(ys[c])) == 1


def test_make_lm_tokens_heterogeneity():
    c = make_lm_tokens(4, 2000, vocab_size=100, heterogeneity=1.0, seed=0)
    assert c.shape == (4, 2000)
    # client unigram distributions differ strongly
    h0 = np.bincount(c[0], minlength=100) / 2000
    h1 = np.bincount(c[1], minlength=100) / 2000
    assert 0.5 * np.abs(h0 - h1).sum() > 0.3  # total variation
