"""Multi-device differential suite (ISSUE 4 sharding contract).

Runs only when the process sees a multi-device mesh — the CI multi-device
lane sets ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before
pytest starts (per conftest, the default lanes must keep seeing 1 device).

Pinned here:
  - sweep-cell sharding (``run_sweep(devices=...)``) is BIT-identical to the
    single-device sweep on every history leaf, including the padded-seed
    path — cells are independent, so no tolerance is tolerated;
  - population sharding (``run_simulation(mesh=...)``) keeps the O(N)
    control plane (masks, energy, availability) bit-identical across
    methods × {static, markov_fading, battery_constrained} and the model
    trajectories equal to the summation order of the eq. (10) psum;
  - the distributed local-then-global top-k equals dense ``lax.top_k``
    exactly, ties included;
  - a mesh of size 1 is a structural no-op.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import sharding, sweep
from repro.core.channel import SCENARIOS
from repro.core.simulator import run_simulation
from repro.data.synthetic import make_fmnist_like
from repro.federated.partition import sorted_label_shards
from repro.models.logreg import logistic_regression

pytestmark = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="multi-device suite: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

N, DIM = 16, 32
MODEL = logistic_regression(dim=DIM, num_classes=10)
# trajectories may differ from the dense reference only by the cross-shard
# summation order of the eq. (10) psum — ulps, amplified over a few rounds
SUM_ORDER_TOL = dict(rtol=2e-5, atol=2e-6)


@pytest.fixture(scope="module")
def shard_data():
    x, y, xt, yt = make_fmnist_like(num_train=640, num_test=320, dim=DIM,
                                    seed=0)
    xs, ys = sorted_label_shards(x, y, N)
    xts, yts = sorted_label_shards(xt, yt, N)
    return xs, ys, xts, yts


def _fl(method="ca_afl", rounds=6, **kw):
    return FLConfig(num_clients=N, clients_per_round=5, rounds=rounds,
                    batch_size=16, method=method, lr0=0.3, lr_decay=0.995,
                    ascent_lr=2e-2, **kw)


def _assert_bit_identical(h1, h2, fields=None):
    for f in fields or h1._fields:
        a, b = np.asarray(getattr(h1, f)), np.asarray(getattr(h2, f))
        np.testing.assert_array_equal(a, b, err_msg=f"field {f}")


# ---------------------------------------------------------------------------
# Sweep-cell sharding
# ---------------------------------------------------------------------------


def test_sharded_sweep_bit_identical(shard_data):
    specs = sweep.expand_grid(
        _fl(), variants={"ca": {}, "afl": {"method": "afl"}},
        scenarios=("default", "noisy_uplink"))
    seeds = tuple(range(jax.device_count() // 2))  # exercises seed padding
    r1 = sweep.run_sweep(MODEL, shard_data, specs, seeds=seeds)
    rd = sweep.run_sweep(MODEL, shard_data, specs, seeds=seeds,
                         devices=jax.device_count())
    assert rd.seeds == r1.seeds
    for lbl in r1.labels:
        _assert_bit_identical(r1.history(lbl), rd.history(lbl))


def test_sharded_sweep_bit_identical_divisible_seeds(shard_data):
    specs = [("run", _fl(temporal=True, rho_fading=0.9))]
    seeds = tuple(range(jax.device_count()))  # no padding
    r1 = sweep.run_sweep(MODEL, shard_data, specs, seeds=seeds)
    rd = sweep.run_sweep(MODEL, shard_data, specs, seeds=seeds,
                         devices="auto")
    _assert_bit_identical(r1.history("run"), rd.history("run"))


def test_sharded_sweep_devices_one_is_single_device_path(shard_data):
    # devices=1 must not even build a mesh: it is the exact default program
    specs = [("run", _fl(rounds=3))]
    r1 = sweep.run_sweep(MODEL, shard_data, specs, seeds=(0, 1))
    rd = sweep.run_sweep(MODEL, shard_data, specs, seeds=(0, 1), devices=1)
    _assert_bit_identical(r1.history("run"), rd.history("run"))


# ---------------------------------------------------------------------------
# Population sharding
# ---------------------------------------------------------------------------


POP_SCENARIOS = ("default", "markov_fading", "battery_constrained")


@pytest.mark.parametrize("scenario", POP_SCENARIOS)
@pytest.mark.parametrize("method", ["fedavg", "afl", "ca_afl", "greedy",
                                    "gca"])
def test_population_sharded_matches_dense(shard_data, method, scenario):
    fl = replace(_fl(method), **SCENARIOS[scenario])
    if scenario == "battery_constrained":
        # enough budget that *some* rounds transmit on N=16
        fl = replace(fl, battery_init=0.05)
    mesh = sharding.client_mesh(sharding.population_device_count(N))
    assert mesh.size > 1
    dense = run_simulation(MODEL, fl, shard_data, dense=True)
    shard = run_simulation(MODEL, fl, shard_data, mesh=mesh)
    # control plane: bit-identical (every [N] draw is replicated, selection
    # and the energy ledger read only replicated inputs)
    _assert_bit_identical(dense, shard,
                          fields=["num_scheduled", "energy", "avail_count",
                                  "min_battery"])
    # model-dependent metrics: equal to the psum's summation order
    for f in ["avg_acc", "worst_acc", "std_acc", "loss", "lam"]:
        np.testing.assert_allclose(
            np.asarray(getattr(dense, f)), np.asarray(getattr(shard, f)),
            err_msg=f"field {f}", **SUM_ORDER_TOL)


def test_population_sharded_eval_cadence(shard_data):
    fl = _fl(eval_every=3, rounds=7)
    mesh = sharding.client_mesh(sharding.population_device_count(N))
    dense = run_simulation(MODEL, fl, shard_data, dense=True)
    shard = run_simulation(MODEL, fl, shard_data, mesh=mesh)
    _assert_bit_identical(dense, shard, fields=["num_scheduled", "energy"])
    np.testing.assert_allclose(np.asarray(dense.avg_acc),
                               np.asarray(shard.avg_acc), **SUM_ORDER_TOL)
    # forward-fill structure survives sharding: non-eval rounds copy the
    # previous eval exactly
    acc = np.asarray(shard.avg_acc)
    for t in range(fl.rounds):
        if t % 3:
            assert acc[t] == acc[t - 1]


def test_population_mesh_of_one_is_noop(shard_data):
    fl = _fl()
    plain = run_simulation(MODEL, fl, shard_data, dense=True)
    m1 = run_simulation(MODEL, fl, shard_data, dense=True,
                        mesh=sharding.client_mesh(1))
    _assert_bit_identical(plain, m1)


def test_population_sharding_rejects_indivisible():
    fl = replace(_fl(), num_clients=N + 1)
    mesh = sharding.client_mesh(jax.device_count())
    with pytest.raises(ValueError, match="N % devices"):
        sharding.run_simulation_sharded(MODEL, fl, (None,) * 4, mesh)


def test_population_device_count_divides():
    assert sharding.population_device_count(16, 8) == 8
    assert sharding.population_device_count(12, 8) == 6
    assert sharding.population_device_count(7, 8) == 7
    assert sharding.population_device_count(13, 8) == 1


# ---------------------------------------------------------------------------
# Distributed top-k == dense lax.top_k (ties included)
# ---------------------------------------------------------------------------


def _run_distributed_top_k(scores, k):
    from jax.sharding import PartitionSpec as P

    mesh = sharding.client_mesh(
        sharding.population_device_count(scores.shape[0]))
    fn = jax.shard_map(
        lambda s: sharding.distributed_top_k(
            s, k, mesh.axis_names[0], n_global=scores.shape[0]),
        mesh=mesh, in_specs=P(mesh.axis_names[0]), out_specs=P(),
        check_vma=False)
    return jax.jit(fn)(scores)


@pytest.mark.parametrize("k", [1, 5, 16])
def test_distributed_top_k_matches_dense(k):
    for seed in range(5):
        scores = jax.random.normal(jax.random.PRNGKey(seed), (N,))
        mask, idx = _run_distributed_top_k(scores, k)
        _, didx = jax.lax.top_k(scores, k)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(didx))
        dmask = np.zeros(N, np.float32)
        dmask[np.asarray(didx)] = 1.0
        np.testing.assert_array_equal(np.asarray(mask), dmask)


@pytest.mark.parametrize("k", [3, 8])
def test_distributed_top_k_ties_pinned(k):
    for seed in range(5):
        # heavy quantization => many exact ties, incl. across shards
        raw = jax.random.normal(jax.random.PRNGKey(100 + seed), (N,))
        scores = jnp.round(raw * 2) / 2
        mask, idx = _run_distributed_top_k(scores, k)
        _, didx = jax.lax.top_k(scores, k)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(didx))


def test_distributed_top_k_with_neg_inf():
    scores = jnp.where(jnp.arange(N) % 3 == 0, -jnp.inf,
                       jnp.ones(N))  # tied finite scores + -inf holes
    mask, idx = _run_distributed_top_k(scores, 8)
    _, didx = jax.lax.top_k(scores, 8)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(didx))


# ---------------------------------------------------------------------------
# Production tier: sharded batch placement is semantics-free
# ---------------------------------------------------------------------------


def test_server_sharded_batch_matches_unsharded(shard_data):
    from repro.federated.server import ParameterServer
    from repro.models.logreg import logistic_regression_prod
    from repro.optim import sgd

    fl = _fl(rounds=3)
    model = logistic_regression_prod(DIM, 10)
    xs, ys = shard_data[0], shard_data[1]
    per = 8

    def batches():
        while True:
            xb = jnp.reshape(xs[:, :per], (N * per, DIM))
            yb = jnp.reshape(ys[:, :per], (N * per,))
            yield {"x": xb, "labels": yb,
                   "client_ids": jnp.repeat(jnp.arange(N), per)}

    mesh = sharding.client_mesh(sharding.population_device_count(N))
    out = {}
    for name, m in [("plain", None), ("sharded", mesh)]:
        ps = ParameterServer(model, sgd(0.3), fl, seed=0, mesh=m)
        state = ps.init_state(jax.random.PRNGKey(0))
        state = ps.run(state, batches(), rounds=3, log_fn=None)
        out[name] = state
    for a, b in zip(out["plain"].history, out["sharded"].history,
                    strict=True):
        assert a["num_scheduled"] == b["num_scheduled"]
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)
        np.testing.assert_allclose(a["energy_j"], b["energy_j"], rtol=1e-6)
    pa = jax.tree_util.tree_leaves(out["plain"].params)
    pb = jax.tree_util.tree_leaves(out["sharded"].params)
    for la, lb in zip(pa, pb, strict=True):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=2e-5, atol=2e-6)
