"""ISSUE 7 differential suite: the sharded control plane.

``control_plane="sharded"`` replaces the replicate-full-[N]-then-slice
discipline with per-client draws content-addressed by GLOBAL client id plus
a hierarchical (per-shard → group → global) exact-K top-k, so each device
materializes only N/D rows of channels, availability, scores, λ and
``ChanState``.

Pinned here:
  - the mesh-sharded program agrees with the unsharded reference (the SAME
    discipline at ``ids = arange(N)``) for every method ×
    {default, markov_fading, battery_constrained} and across the uplink
    transports. Per-client values are sharding-independent by construction
    (same fold_in streams, ownership-psum adds exact zeros, the tree top-k
    preserves dense tie-breaks); the two *compiled* programs differ only by
    XLA's shape-dependent FMA contraction — so discrete decisions
    (scheduled counts, availability) are asserted EXACTLY and continuous
    histories to a few ulps (``FMA_TOL``);
  - ``hierarchical_top_k`` equals dense ``lax.top_k`` — ties straddling
    shard boundaries, k > n_local, all-(-inf) shards, -inf-padded
    indivisible N, every tree fan-in;
  - the cross-tier contract (``ParameterServer`` vs simulator) holds under
    the sharded discipline (single-device, tier-1 lane);
  - an N=100k smoke on 8 forced host devices (slow lane).
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import sharding
from repro.core.channel import SCENARIOS
from repro.core.simulator import (_batch_indices_ids, init_sim_state,
                                  make_param_round_fn, run_simulation)
from repro.core.sweep import sweep_point_from_config
from repro.data.synthetic import make_fmnist_like
from repro.federated.partition import sorted_label_shards
from repro.models.logreg import logistic_regression
from repro.utils.tree import tree_size

multidev = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="multi-device suite: run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

N, DIM = 16, 32
MODEL = logistic_regression(dim=DIM, num_classes=10)
# Per-client values are identical by construction; the compiled unsharded
# and sharded programs differ only by XLA's shape-dependent instruction
# selection (FMA contraction of mul+add chains) — a few ulps on
# channel/energy values, never a decision flip at these seeds.
FMA_TOL = dict(rtol=2e-5, atol=2e-6)
EXACT_FIELDS = ("num_scheduled", "avail_count")


@pytest.fixture(scope="module")
def cs_data():
    x, y, xt, yt = make_fmnist_like(num_train=640, num_test=320, dim=DIM,
                                    seed=0)
    xs, ys = sorted_label_shards(x, y, N)
    xts, yts = sorted_label_shards(xt, yt, N)
    return xs, ys, xts, yts


def _fl(method="ca_afl", rounds=4, **kw):
    return FLConfig(num_clients=N, clients_per_round=5, rounds=rounds,
                    batch_size=16, method=method, lr0=0.3, lr_decay=0.995,
                    ascent_lr=2e-2, control_plane="sharded", **kw)


def _assert_agrees(ref, sh):
    for f in ref._fields:
        a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(sh, f))
        if f in EXACT_FIELDS:
            np.testing.assert_array_equal(a, b, err_msg=f"field {f}")
        else:
            np.testing.assert_allclose(b, a, err_msg=f"field {f}", **FMA_TOL)


# ---------------------------------------------------------------------------
# Unsharded sharded-discipline program (tier-1 lane, single device)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["fedavg", "afl", "ca_afl", "greedy",
                                    "gca"])
def test_sharded_discipline_runs(cs_data, method):
    h = run_simulation(MODEL, _fl(method), cs_data, seed=0)
    assert np.isfinite(np.asarray(h.avg_acc)).all()
    assert np.isfinite(np.asarray(h.lam)).all()
    assert h.lam.shape == (4, N)
    np.testing.assert_allclose(np.asarray(h.lam).sum(axis=1), 1.0, rtol=1e-5)
    if method != "gca":
        # static scenario: exact-K methods schedule exactly K every round
        np.testing.assert_array_equal(np.asarray(h.num_scheduled), 5.0)


@pytest.mark.parametrize("eval_every", [1, 2])
def test_eval_stats_psum_form_matches_unsharded(cs_data, eval_every):
    """ISSUE 9 regression: the sharded round's test-eval statistics are
    psum-of-local-rows (mean/min via psum/pmin, std via the two-pass
    centered variance) instead of the old all_gather + jnp.{mean,min,std} —
    the one remaining O(N) gather on the exact-K path. A size-1 clients
    mesh runs the psum-form program in the tier-1 lane; it must agree with
    the unsharded stack-form reference to summation-order ulps, on both the
    per-round and the cond-gated (eval_every > 1) eval programs."""
    fl = replace(_fl("ca_afl"), eval_every=eval_every)
    mesh = sharding.client_mesh(1)
    ref = run_simulation(MODEL, fl, cs_data, seed=0)
    sh = sharding.run_simulation_control_sharded(MODEL, fl, cs_data, mesh,
                                                 seed=0)
    _assert_agrees(ref, sh)


def test_sharded_discipline_deterministic(cs_data):
    h1 = run_simulation(MODEL, _fl(), cs_data, seed=3)
    h2 = run_simulation(MODEL, _fl(), cs_data, seed=3)
    for f in h1._fields:
        np.testing.assert_array_equal(np.asarray(getattr(h1, f)),
                                      np.asarray(getattr(h2, f)))
    h3 = run_simulation(MODEL, _fl(), cs_data, seed=4)
    assert not np.array_equal(np.asarray(h1.energy), np.asarray(h3.energy))


def test_batch_indices_content_addressed():
    key = jax.random.PRNGKey(11)
    ids = jnp.arange(12, dtype=jnp.int32)
    full = _batch_indices_ids(key, ids, 7, 5)
    # any slice of the population draws ITS rows bit-identically, and so
    # does a gather of an arbitrary winner subset — the property the
    # selected-K slot path relies on
    np.testing.assert_array_equal(
        np.asarray(_batch_indices_ids(key, ids[4:9], 7, 5)),
        np.asarray(full[4:9]))
    win = jnp.asarray([10, 0, 7], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(_batch_indices_ids(key, win, 7, 5)),
        np.asarray(full[win]))


def test_sharded_discipline_cross_tier():
    """One ``ParameterServer.step`` == one simulator round under the sharded
    discipline (same 7-way key split, now per-id streams on both tiers)."""
    from repro.federated.server import ParameterServer, ServerState
    from repro.models.logreg import logistic_regression_prod
    from repro.optim import sgd

    n, dim, cls, per = 6, 16, 10, 4
    key = jax.random.PRNGKey(7)
    xs = jax.random.normal(key, (n, 1, dim))
    ys = jax.random.randint(jax.random.fold_in(key, 1), (n, 1), 0, cls)
    for method in ("ca_afl", "greedy"):
        fl = FLConfig(num_clients=n, clients_per_round=3, rounds=1,
                      batch_size=per, local_steps=1, method=method, lr0=0.2,
                      ascent_lr=1e-2, energy_C=4.0, control_plane="sharded")
        sim_model = logistic_regression(dim, cls)
        point = sweep_point_from_config(fl)
        state = init_sim_state(sim_model, fl, jax.random.PRNGKey(0),
                               process=point.process)
        round_fn = make_param_round_fn(sim_model, fl, (xs, ys, xs, ys),
                                       tree_size(state.w), method)
        new_state, hist = jax.jit(lambda p, s: round_fn(p, s, 0))(point,
                                                                  state)

        prod_model = logistic_regression_prod(dim, cls)
        ps = ParameterServer(prod_model, sgd(fl.lr0), fl, seed=0)
        ps.key = state.key
        srv = ServerState(params=jax.tree.map(jnp.asarray, state.w),
                          opt_state=sgd(fl.lr0).init(state.w),
                          lam=state.lam)
        batch = {"x": jnp.repeat(xs[:, 0, :], per, axis=0),
                 "labels": jnp.repeat(ys[:, 0], per, axis=0),
                 "client_ids": jnp.repeat(jnp.arange(n), per)}
        srv = ps.step(srv, batch)

        assert srv.history[-1]["num_scheduled"] == int(hist.num_scheduled)
        np.testing.assert_allclose(srv.energy_joules, float(hist.energy),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(srv.lam),
                                   np.asarray(new_state.lam), atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(srv.params),
                        jax.tree_util.tree_leaves(new_state.w),
                        strict=True):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Mesh differential: sharded program == unsharded reference
# ---------------------------------------------------------------------------


POP_SCENARIOS = ("default", "markov_fading", "battery_constrained")


@multidev
@pytest.mark.parametrize("scenario", POP_SCENARIOS)
@pytest.mark.parametrize("method", ["fedavg", "afl", "ca_afl", "greedy",
                                    "gca"])
def test_control_sharded_matches_unsharded(cs_data, method, scenario):
    fl = replace(_fl(method), **SCENARIOS[scenario])
    if scenario == "battery_constrained":
        fl = replace(fl, battery_init=0.05)  # some rounds transmit at N=16
    mesh = sharding.client_mesh(sharding.population_device_count(N))
    assert mesh.size > 1
    ref = run_simulation(MODEL, fl, cs_data, seed=0)
    sh = run_simulation(MODEL, fl, cs_data, seed=0, mesh=mesh)
    _assert_agrees(ref, sh)


@multidev
@pytest.mark.parametrize("transport", ["quantized", "digital"])
@pytest.mark.parametrize("method", ["fedavg", "ca_afl", "gca"])
def test_control_sharded_matches_unsharded_transport(cs_data, method,
                                                     transport):
    # the transport axis crosses the two aggregation code paths: the
    # exact-K [K]-stack path (identical for all EXACT_K_METHODS) and GCA's
    # local-psum path — fedavg/ca_afl cover λ-free and λ-driven scoring
    fl = replace(_fl(method), transport=transport)
    mesh = sharding.client_mesh(sharding.population_device_count(N))
    ref = run_simulation(MODEL, fl, cs_data, seed=0)
    sh = run_simulation(MODEL, fl, cs_data, seed=0, mesh=mesh)
    _assert_agrees(ref, sh)


@multidev
@pytest.mark.parametrize("group_size", [1, 2, 4, 8])
def test_control_sharded_group_size(cs_data, group_size):
    # every tree fan-in (1 and 8 degenerate to the flat pass at D=8, 2 and 4
    # exercise both gather stages) selects identically
    fl = _fl()
    mesh = sharding.client_mesh(8)
    ref = run_simulation(MODEL, fl, cs_data, seed=0)
    sh = sharding.run_simulation_control_sharded(MODEL, fl, cs_data, mesh,
                                                 seed=0,
                                                 group_size=group_size)
    _assert_agrees(ref, sh)


@multidev
def test_control_sharded_lambda_stitching(cs_data):
    # λ history rows come back in global client order, not shard order
    fl = _fl("afl", rounds=3)
    mesh = sharding.client_mesh(8)
    ref = run_simulation(MODEL, fl, cs_data, seed=1)
    sh = run_simulation(MODEL, fl, cs_data, seed=1, mesh=mesh)
    assert sh.lam.shape == (3, N)
    np.testing.assert_allclose(np.asarray(sh.lam), np.asarray(ref.lam),
                               **FMA_TOL)


@multidev
def test_control_sharded_rejects_indivisible():
    fl = replace(_fl(), num_clients=N + 1)
    mesh = sharding.client_mesh(jax.device_count())
    with pytest.raises(ValueError, match="N % devices"):
        sharding.run_simulation_control_sharded(MODEL, fl, (None,) * 4, mesh)


@multidev
def test_control_sharded_rejects_replicated_config():
    fl = replace(_fl(), control_plane="replicated")
    mesh = sharding.client_mesh(jax.device_count())
    with pytest.raises(ValueError, match="control_plane"):
        sharding.run_simulation_control_sharded(MODEL, fl, (None,) * 4, mesh)


# ---------------------------------------------------------------------------
# hierarchical_top_k == dense lax.top_k (satellite 4)
# ---------------------------------------------------------------------------


def _run_hier_top_k(scores, k, group_size=None):
    from jax.sharding import PartitionSpec as P

    mesh = sharding.client_mesh(
        sharding.population_device_count(scores.shape[0]))
    ax = mesh.axis_names[0]
    n_shards = mesh.size

    def body(s):
        return sharding.hierarchical_top_k(s, k, ax, n_shards,
                                           group_size=group_size)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(ax), out_specs=P(),
                       check_vma=False)
    return np.asarray(jax.jit(fn)(scores))


def _dense_idx(scores, k):
    return np.asarray(jax.lax.top_k(scores, k)[1])


@multidev
@pytest.mark.property
@pytest.mark.parametrize("group_size", [None, 1, 2, 4, 8])
def test_hier_top_k_property_vs_dense(group_size):
    # random draws + heavy quantization (ties straddling shard boundaries)
    for seed in range(8):
        raw = jax.random.normal(jax.random.PRNGKey(seed), (N,))
        for scores in (raw, jnp.round(raw * 2) / 2):
            for k in (1, 3, 5, 13, 16):
                np.testing.assert_array_equal(
                    _run_hier_top_k(scores, k, group_size),
                    _dense_idx(scores, k),
                    err_msg=f"seed={seed} k={k} g={group_size}")


@multidev
def test_hier_top_k_k_exceeds_n_local():
    # k=13 > n_local=2 at D=8: stage-1 candidates cap at n_local and the
    # tree must still recover the exact global winner set
    assert N // jax.device_count() < 13
    scores = jax.random.normal(jax.random.PRNGKey(0), (N,))
    np.testing.assert_array_equal(_run_hier_top_k(scores, 13, 2),
                                  _dense_idx(scores, 13))


@multidev
@pytest.mark.parametrize("group_size", [None, 2])
def test_hier_top_k_all_neg_inf_shards(group_size):
    # entire shards at -inf (fully-unavailable populations) and the fully
    # -inf vector: ties resolve to the lowest global index, like dense
    n_local = N // sharding.population_device_count(N)
    shard_ids = jnp.arange(N) // n_local
    scores = jnp.where(shard_ids % 2 == 0, -jnp.inf, 1.0)
    for k in (3, 8, 12):
        np.testing.assert_array_equal(_run_hier_top_k(scores, k, group_size),
                                      _dense_idx(scores, k))
    all_inf = jnp.full((N,), -jnp.inf)
    np.testing.assert_array_equal(_run_hier_top_k(all_inf, 5, group_size),
                                  _dense_idx(all_inf, 5))


@multidev
def test_hier_top_k_indivisible_population_via_padding():
    # N=20 does not divide 8 shards: the documented recipe pads with -inf
    # rows to the next multiple; winners equal dense top-k on the padded
    # vector (and, for k <= the finite count, on the original)
    n_real, n_dev = 20, jax.device_count()
    n_pad = -(-n_real // n_dev) * n_dev
    raw = jax.random.normal(jax.random.PRNGKey(5), (n_real,))
    padded = jnp.concatenate([raw, jnp.full((n_pad - n_real,), -jnp.inf)])
    for k in (1, 7, 19):
        idx = _run_hier_top_k(padded, k)
        np.testing.assert_array_equal(idx, _dense_idx(padded, k))
        np.testing.assert_array_equal(idx, _dense_idx(raw, k))


# ---------------------------------------------------------------------------
# Large-N smoke (CI large-N lane: -m slow)
# ---------------------------------------------------------------------------


@multidev
@pytest.mark.slow
def test_control_sharded_large_population_smoke():
    """N=100k clients on the forced-8-device host: the O(N/D) control plane
    runs a few rounds end to end and λ stays a valid simplex."""
    n, dim = 100_000, 16
    fl = FLConfig(num_clients=n, clients_per_round=32, rounds=2,
                  batch_size=2, local_steps=1, num_subcarriers=1,
                  method="ca_afl", lr0=0.1, ascent_lr=1e-2,
                  control_plane="sharded", eval_every=2)
    model = logistic_regression(dim=dim, num_classes=4)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, 2, dim), jnp.float32)
    y = jax.random.randint(jax.random.fold_in(key, 1), (n, 2), 0, 4)
    mesh = sharding.client_mesh(jax.device_count())
    hist = run_simulation(model, fl, (x, y, x, y), seed=0, mesh=mesh)
    assert np.isfinite(np.asarray(hist.avg_acc)).all()
    assert np.asarray(hist.num_scheduled).max() <= 32
    np.testing.assert_allclose(np.asarray(hist.lam).sum(axis=1), 1.0,
                               rtol=1e-4)
    assert hist.lam.shape == (2, n)


# ---------------------------------------------------------------------------
# ISSUE 8: psum-bisection projection over randomized shard layouts
# ---------------------------------------------------------------------------


def _run_sharded_projection(v, n_dev):
    from jax.sharding import PartitionSpec as P

    mesh = sharding.client_mesh(n_dev)
    ax = mesh.axis_names[0]
    fn = jax.shard_map(
        lambda s: sharding.project_simplex_sharded(s, axis_name=ax),
        mesh=mesh, in_specs=P(ax), out_specs=P(ax), check_vma=False)
    return np.asarray(jax.jit(fn)(v))


@multidev
@pytest.mark.property
def test_projection_sharded_property_layouts():
    """project_simplex_sharded over randomized shard layouts: for every
    divisor-of-N device count the mesh result equals the unsharded result
    of the same program (psum order is the ONLY difference) and the sort
    reference, including duplicate scores and -inf rows."""
    from repro.core.dro import project_simplex

    max_dev = jax.device_count()
    for seed in range(6):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(2, 9)) * max_dev
        v = rng.normal(size=n).astype(np.float32) * 10
        if seed % 2:
            v = np.round(v)                      # duplicates at water level
        if seed >= 4:
            v[rng.integers(0, n, size=n // 4)] = -np.inf
        vj = jnp.asarray(v)
        ref = np.asarray(sharding.project_simplex_sharded(vj))
        for d in (2, 4, max_dev):
            if n % d:
                continue
            got = _run_sharded_projection(vj, d)
            np.testing.assert_allclose(got, ref, atol=2e-6,
                                       err_msg=f"seed={seed} d={d}")
        if np.isfinite(v).all():
            np.testing.assert_allclose(
                ref, np.asarray(project_simplex(vj)), atol=2e-6,
                err_msg=f"seed={seed} vs sort")


@multidev
@pytest.mark.property
def test_hier_top_k_property_random_layouts():
    """hierarchical_top_k == dense lax.top_k over randomized (population,
    group_size) layouts with duplicate and -inf scores — the handpicked
    edge cases generalized (ISSUE 8 satellite)."""
    max_dev = jax.device_count()
    for seed in range(6):
        rng = np.random.default_rng(1000 + seed)
        n = int(rng.integers(2, 9)) * max_dev
        raw = rng.normal(size=n).astype(np.float32)
        if seed % 2:
            raw = np.round(raw * 2) / 2
        if seed >= 4:
            raw[rng.integers(0, n, size=n // 3)] = -np.inf
        k = int(rng.integers(1, n + 1))
        g = int(rng.choice([1, 2, 4, max_dev]))
        scores = jnp.asarray(raw)
        np.testing.assert_array_equal(
            _run_hier_top_k(scores, k, g), _dense_idx(scores, k),
            err_msg=f"seed={seed} n={n} k={k} g={g}")


# ---------------------------------------------------------------------------
# ISSUE 8: run_sweep on the 2-D cells × clients mesh
# ---------------------------------------------------------------------------


@multidev
@pytest.mark.parametrize("transport", ["analog", "quantized"])
def test_sweep_2d_mesh_matches_single_device(cs_data, transport):
    """The differential contract extended across the 2-D grid: run_sweep on
    the cells × clients mesh == the 1-D cells mesh == single device, for
    3 methods × 2 scenarios (× 2 transports via the parametrize) — discrete
    fields exact, continuous to ulps."""
    from repro.core.sweep import expand_grid, run_sweep

    base = replace(_fl(rounds=3), transport=transport)
    specs = expand_grid(
        base,
        variants={"ca": {"method": "ca_afl"}, "af": {"method": "afl"},
                  "gr": {"method": "greedy"}},
        scenarios=("default", "heterogeneous_pathloss"))
    n_dev = jax.device_count()
    ref = run_sweep(MODEL, cs_data, specs, seeds=(0,))
    two_d = run_sweep(MODEL, cs_data, specs, seeds=(0,), devices=n_dev,
                      client_devices=max(d for d in (2, 4, n_dev)
                                         if n_dev % d == 0 and N % d == 0))
    one_d = run_sweep(MODEL, cs_data, specs, seeds=(0,), devices=n_dev,
                      client_devices=1)
    for lbl in ref.labels:
        for sweep_hist in (two_d, one_d):
            _assert_agrees(ref.history(lbl), sweep_hist.history(lbl))


@multidev
def test_sweep_2d_mesh_strided_lambda(cs_data):
    # the strided recorder composes with the 2-D mesh: snapshots stitch
    # back to global client order and match the dense rows on the cadence
    fl = replace(_fl(rounds=4), record_lambda_every=2)
    specs = [("s", fl)]
    from repro.core.sweep import run_sweep

    ref = run_sweep(MODEL, cs_data, specs, seeds=(0, 1))
    two_d = run_sweep(MODEL, cs_data, specs, seeds=(0, 1),
                      devices=jax.device_count(), client_devices=4)
    assert np.asarray(two_d.history("s").lam).shape == (2, 2, N)
    np.testing.assert_allclose(np.asarray(two_d.history("s").lam),
                               np.asarray(ref.history("s").lam), **FMA_TOL)


@multidev
def test_factor_client_devices():
    assert sharding.factor_client_devices(16, 8) == 8
    assert sharding.factor_client_devices(12, 8) == 4
    assert sharding.factor_client_devices(7, 8) == 1  # no divisor fits
    assert sharding.factor_client_devices(16, 8, 2) == 2  # explicit wins
    with pytest.raises(ValueError):
        sharding.factor_client_devices(16, 8, 3)  # 3 divides neither
    with pytest.raises(ValueError):
        sharding.factor_client_devices(15, 8, 5)  # 5 divides N, not devices


@multidev
@pytest.mark.slow
def test_sweep_2d_mesh_large_population_smoke():
    """N=50k × 2 sweep cells on the forced-8-device host factored as a
    (2 cells × 4 clients) mesh: the composed O(N/D) path runs end to end,
    the psum-bisection keeps λ a valid simplex, and the strided recorder
    bounds the history to ceil(T/E) rows."""
    from repro.core.sweep import run_sweep

    n, dim = 50_000, 16
    fl = FLConfig(num_clients=n, clients_per_round=32, rounds=2,
                  batch_size=2, local_steps=1, num_subcarriers=1,
                  method="ca_afl", lr0=0.1, ascent_lr=1e-2,
                  control_plane="sharded", eval_every=2,
                  record_lambda_every=2)
    model = logistic_regression(dim=dim, num_classes=4)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, 2, dim), jnp.float32)
    y = jax.random.randint(jax.random.fold_in(key, 1), (n, 2), 0, 4)
    res = run_sweep(model, (x, y, x, y), [("a", fl)], seeds=(0, 1),
                    devices=jax.device_count(), client_devices=4)
    hist = res.history("a")
    assert np.asarray(hist.lam).shape == (2, 1, n)  # ceil(2/2) = 1 snapshot
    np.testing.assert_allclose(np.asarray(hist.lam).sum(-1), 1.0, rtol=1e-4)
    assert np.isfinite(np.asarray(hist.avg_acc)).all()
    assert np.asarray(hist.num_scheduled).max() <= 32
    # the lone snapshot is round 0 (t % E == 0), so pin it against the
    # round-0 summary leaf, not the final round's
    np.testing.assert_allclose(np.asarray(hist.lam_ess)[:, 0],
                               1.0 / (np.asarray(hist.lam)[:, 0] ** 2)
                               .sum(-1), rtol=1e-4)
