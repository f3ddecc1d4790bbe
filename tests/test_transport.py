"""Uplink-transport layer suite (``repro.core.transport``).

Property tests (``property`` marker): the stochastic-rounding quantizer is
unbiased with error variance within the Δ²/4 bound; digital-OFDMA upload
energy is monotone in the payload bits and decreasing in SNR; the analog
deep-fade guard keeps an exactly-zero channel draw finite.

Differential pins: ``transport="analog"`` is bit-identical to the
pre-transport program across all 5 selection methods (its output is a
constant function of every transport knob, and the transport dispatch
delegates to the exact pre-existing calls); quantized at bits=32 matches
analog to f32 eps with the identical AWGN realization; digital aggregation
is the masked weighted mean with zero superposition noise; the sparse-K and
population-sharded paths equal the dense reference for every transport ×
{default, markov_fading, battery_constrained}; and a four-transport sweep
compiles one executable per scheme with every knob traced (the
error-feedback ``sparse`` scheme's invariants get their own suite,
``tests/test_sparse_transport.py``).
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import sharding, sweep, transport
from repro.core.aircomp import aircomp_aggregate_tree
from repro.core.channel import SCENARIOS
from repro.core.energy import round_energy, transmit_energy
from repro.core.simulator import run_simulation
from repro.core.transport import (TransportParams, digital_energy,
                                  digital_latency, quant_step, quantize_rows,
                                  transport_from_config, uplink_energy)
from repro.data.synthetic import make_fmnist_like
from repro.federated.partition import sorted_label_shards
from repro.kernels.aircomp.ops import quant_aircomp_flat
from repro.models.logreg import logistic_regression

N, DIM = 12, 32
MODEL = logistic_regression(dim=DIM, num_classes=10)
METHODS = ("fedavg", "afl", "ca_afl", "greedy", "gca")


@pytest.fixture(scope="module")
def tdata():
    x, y, xt, yt = make_fmnist_like(num_train=600, num_test=240, dim=DIM,
                                    seed=0)
    xs, ys = sorted_label_shards(x, y, N)
    xts, yts = sorted_label_shards(xt, yt, N)
    return xs, ys, xts, yts


def _fl(method="ca_afl", rounds=6, **kw):
    return FLConfig(num_clients=N, clients_per_round=5, rounds=rounds,
                    batch_size=16, method=method, lr0=0.3, lr_decay=0.995,
                    ascent_lr=2e-2, **kw)


def _hist_equal(a, b, msg="", **tol):
    for name in a._fields:
        if tol:
            np.testing.assert_allclose(
                np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
                err_msg=f"{msg}:{name}", **tol)
        else:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
                err_msg=f"{msg}:{name}")


# ---------------------------------------------------------------------------
# Quantizer properties: unbiasedness and the Δ²/4 variance bound
# ---------------------------------------------------------------------------


@pytest.mark.property
def test_quantizer_unbiased():
    """E[Q(x)] = x under stochastic rounding: the empirical mean over many
    independent rounding draws converges to the input at the CLT rate."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (3, 64))
    bits = 4.0
    trials = 4096
    cids = jnp.arange(3)

    def one(k):
        q, _ = quantize_rows(x, cids, k, bits)
        return q

    qs = jax.vmap(one)(jax.random.split(jax.random.PRNGKey(1), trials))
    step = np.asarray(quant_step(x, bits))           # [3]
    err = np.asarray(qs.mean(0)) - np.asarray(x)     # [3, 64]
    # CLT: |mean error| <~ 4 * sqrt(Δ²/4 / trials) per coordinate
    bound = 4.0 * step[:, None] / 2.0 / np.sqrt(trials)
    assert (np.abs(err) <= bound).mean() > 0.99
    assert np.abs(err).max() <= 8.0 * step.max() / 2.0 / np.sqrt(trials)


@pytest.mark.property
def test_quantizer_variance_bound():
    """Var[Q(x)] = Δ²·p(1−p) ≤ Δ²/4 per coordinate (stochastic rounding on a
    Δ-grid); the empirical variance stays within the bound plus CLT slack."""
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (2, 48)) * 3.0
    bits = 3.0
    trials = 4096
    cids = jnp.arange(2)
    qs = jax.vmap(lambda k: quantize_rows(x, cids, k, bits)[0])(
        jax.random.split(jax.random.PRNGKey(3), trials))
    step = np.asarray(quant_step(x, bits))
    var = np.asarray(qs).var(axis=0)                 # [2, 48]
    bound = (step[:, None] ** 2) / 4.0
    assert (var <= bound * 1.15).all()


@pytest.mark.property
def test_quantizer_error_bounded_and_zero_rows_exact():
    """Every realization lands on one of the two neighbouring grid points
    (|Q(x) − x| < Δ always), and an all-zero payload row passes through
    exactly (Δ = 0 disables the grid)."""
    bits = 4.0
    rows = jnp.stack([jnp.linspace(-1.0, 1.0, 16), jnp.zeros((16,))])
    step = quant_step(rows, bits)
    assert float(step[1]) == 0.0
    for trial in range(8):
        q, _ = quantize_rows(rows, jnp.arange(2), jax.random.PRNGKey(trial),
                             bits)
        assert np.abs(np.asarray(q[0]) - np.asarray(rows[0])).max() \
            < float(step[0])
        np.testing.assert_array_equal(np.asarray(q[1]), np.zeros((16,)))


# ---------------------------------------------------------------------------
# Digital energy properties + the analog deep-fade guard
# ---------------------------------------------------------------------------


@pytest.mark.property
def test_digital_energy_monotone_in_payload_and_snr():
    h = jnp.asarray([0.1, 0.5, 1.0, 2.5])
    tp = TransportParams(bits=8.0, tx_power=0.1, bandwidth=1e5, rx_noise=1e-2)
    e = np.asarray(digital_energy(h, 1000, tp))
    e2 = np.asarray(digital_energy(h, 2000, tp))
    assert (e2 > e).all()                        # monotone in model bits M·32
    np.testing.assert_allclose(e2, 2.0 * e, rtol=1e-6)   # airtime is linear
    assert (np.diff(e) < 0).all()                # decreasing in channel SNR
    e_less_noise = np.asarray(digital_energy(h, 1000,
                                             replace(tp, rx_noise=1e-3)))
    assert (e_less_noise < e).all()              # decreasing in SNR, N0 axis
    lat = np.asarray(digital_latency(h, 1000, tp))
    np.testing.assert_allclose(e, 0.1 * lat, rtol=1e-6)  # E = P · t
    # `bits` is the QUANTIZED scheme's knob: the digital PS decodes the full
    # f32 payload, so its bill must not shrink with bits (the free-lunch
    # regression — a b-bit price for a 32-bit delivery would make digital
    # cells dominate every Pareto comparison they appear in)
    np.testing.assert_array_equal(
        e, np.asarray(digital_energy(h, 1000, replace(tp, bits=1.0))))


@pytest.mark.property
def test_digital_energy_zero_knobs_stay_finite():
    """Regression: tx_power=0 gave rate 0 → 0·inf = NaN energy (and
    bandwidth=0 gave inf), poisoning the ledger and battery gating for all
    clients. The rate floor keeps degenerate traced knobs finite."""
    h = jnp.asarray([0.05, 1.0])
    tp = TransportParams(tx_power=0.0, bandwidth=1e5, rx_noise=1e-2)
    assert np.isfinite(np.asarray(digital_energy(h, 1000, tp))).all()
    tp = TransportParams(tx_power=0.1, bandwidth=0.0, rx_noise=1e-2)
    e = np.asarray(digital_energy(h, 1000, tp))
    assert np.isfinite(e).all() and (e > 0).all()


@pytest.mark.property
def test_digital_energy_zero_rx_noise_not_free():
    """Regression: rx_noise=0 made the Shannon SNR infinite, the rate
    infinite and the airtime zero — digital uploads billed at exactly 0 J,
    so digital cells dominated every Pareto front they appeared in. The
    noise clamp keeps the rate (hence the bill) finite and positive."""
    h = jnp.asarray([0.05, 1.0])
    tp = TransportParams(tx_power=0.1, bandwidth=1e5, rx_noise=0.0)
    e = np.asarray(digital_energy(h, 1000, tp))
    assert np.isfinite(e).all() and (e > 0).all()
    # a vanishing-but-positive noise must behave the same way (no knife edge)
    e_tiny = np.asarray(digital_energy(
        h, 1000, TransportParams(tx_power=0.1, bandwidth=1e5,
                                 rx_noise=1e-30)))
    assert np.isfinite(e_tiny).all() and (e_tiny > 0).all()


@pytest.mark.property
def test_quant_step_degenerate_bits_stay_finite(tdata):
    """Regression: bits=0 gave 2^0 − 1 = 0 grid levels → Δ = max|x|/0 = inf
    → NaN payloads after rounding. The level floor pins Δ finite on the
    whole degenerate edge, and a traced bits-grid sweep crossing 0/1 stays
    finite end-to-end (bits is a TRACED knob: one executable serves the
    grid, so one poisoned cell would share its program with healthy ones).
    The billed energy floors at the 1-bit payload — bits=0 must not upload
    for free."""
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 64))
    for bits in (0.0, 0.5, 1.0, 2.0):
        step = np.asarray(quant_step(x, bits))
        assert np.isfinite(step).all(), bits
        q, _ = quantize_rows(x, jnp.arange(3), jax.random.PRNGKey(1), bits)
        assert np.isfinite(np.asarray(q)).all(), bits
    fl = _fl("fedavg", rounds=3)
    specs = [(f"b{b}", replace(fl, transport="quantized", quant_bits=b))
             for b in (0.0, 1.0, 4.0, 32.0)]
    result = sweep.run_sweep(MODEL, tdata, specs, seeds=(3,))
    s = result.summary(window=2)
    for lbl in ("b0.0", "b1.0", "b4.0", "b32.0"):
        assert np.isfinite(s[lbl]["energy"]), lbl
        assert np.isfinite(s[lbl]["avg_acc"]), lbl
        assert s[lbl]["energy"] > 0.0, lbl
    # the bits=0 bill floors at exactly the 1-bit price
    np.testing.assert_allclose(s["b0.0"]["energy"], s["b1.0"]["energy"],
                               rtol=1e-6)
    assert s["b1.0"]["energy"] < s["b32.0"]["energy"]


@pytest.mark.property
def test_deep_fade_guard_zero_channel_draw():
    """Regression: an exactly-zero channel used to give inf/NaN upload energy
    (1/h²), poisoning battery depletion and greedy scores. Energy is now
    priced at max(h, floor) for every scheme."""
    h = jnp.asarray([0.0, 0.05, 1.0])
    e = np.asarray(transmit_energy(h, 7850, 0.5e-3, 1e-3))
    assert np.isfinite(e).all()
    assert e[0] == e[1]  # the zero draw prices exactly at the floor
    total = round_energy(h, jnp.ones((3,)), 7850, 0.5e-3, 1e-3)
    assert np.isfinite(float(total))
    scen = sweep.sweep_point_from_config(FLConfig()).scenario
    for scheme in transport.TRANSPORTS:
        tp = transport_from_config(replace(FLConfig(), transport=scheme))
        en = np.asarray(uplink_energy(scheme, tp, h, 7850, scen))
        assert np.isfinite(en).all(), scheme
    # a custom floor stays authoritative: clamping never overrides a LOWER
    # scenario floor (which would silently change that scenario's ledger)
    e_low = np.asarray(transmit_energy(jnp.asarray([0.01]), 100, 1.0, 1.0,
                                       floor=0.01))
    np.testing.assert_allclose(e_low, 1e6, rtol=1e-5)


# ---------------------------------------------------------------------------
# Fused quantize-aggregate kernel: Pallas (interpret) == jnp oracle
# ---------------------------------------------------------------------------


def test_quant_kernel_matches_reference():
    key = jax.random.PRNGKey(5)
    c, m = 7, 1536
    x = jax.random.normal(key, (c, m))
    w = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0, 1.0, 1.0])
    d = quant_step(x, 6.0)
    u = jax.random.uniform(jax.random.fold_in(key, 1), (c, m))
    z = jax.random.normal(jax.random.fold_in(key, 2), (m,))
    ref = quant_aircomp_flat(x, w, d, u, z, noise_std=0.3, k=5.0,
                             use_pallas=False)
    pal = quant_aircomp_flat(x, w, d, u, z, noise_std=0.3, k=5.0,
                             use_pallas=True)  # interpret mode off-TPU
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    # traced scalars: no recompile across noise_std/k values
    f = jax.jit(lambda ns, k: quant_aircomp_flat(
        x, w, d, u, z, noise_std=ns, k=k, use_pallas=True))
    np.testing.assert_allclose(np.asarray(f(0.3, 5.0)), np.asarray(pal),
                               rtol=1e-6)
    f(0.1, 3.0)  # same executable, different scalars


def test_sparse_kernel_matches_reference():
    """The fused compress-aggregate kernel: Pallas (interpret) == jnp oracle,
    with traced noise_std/k scalars sharing one executable."""
    from repro.kernels.aircomp.ops import sparse_aircomp_flat
    from repro.core.transport import sparse_thresholds

    key = jax.random.PRNGKey(9)
    c, m = 7, 1536
    x = jax.random.normal(key, (c, m))
    w = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0, 1.0, 1.0])
    thr = sparse_thresholds(x, 77)
    z = jax.random.normal(jax.random.fold_in(key, 2), (m,))
    ref = sparse_aircomp_flat(x, w, thr, z, noise_std=0.3, k=5.0,
                              use_pallas=False)
    pal = sparse_aircomp_flat(x, w, thr, z, noise_std=0.3, k=5.0,
                              use_pallas=True)  # interpret mode off-TPU
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    # an all-zero payload row thresholds at 0, keeps itself and adds zeros
    x0 = x.at[2].set(0.0)
    thr0 = sparse_thresholds(x0, 77)
    assert float(thr0[2]) == 0.0
    out0 = sparse_aircomp_flat(x0, w, thr0, z, noise_std=0.0, k=5.0,
                               use_pallas=True)
    assert np.isfinite(np.asarray(out0)).all()
    # traced scalars: no recompile across noise_std/k values
    f = jax.jit(lambda ns, k: sparse_aircomp_flat(
        x, w, thr, z, noise_std=ns, k=k, use_pallas=True))
    np.testing.assert_allclose(np.asarray(f(0.3, 5.0)), np.asarray(pal),
                               rtol=1e-6)
    f(0.1, 3.0)  # same executable, different scalars


@pytest.mark.parametrize("kind", ["analog", "quantized", "sparse"])
def test_kernels_match_reference_at_unaligned_width(kind):
    """M = 3,001 is no multiple of 128: the kernels zero-pad it to whole
    TILE_M tiles (three tiles, the last mostly padding) and slice the pad
    off. Pallas (interpret) must equal the jnp oracle column for column."""
    from repro.kernels.aircomp.ops import (aircomp_aggregate_flat,
                                           sparse_aircomp_flat)
    from repro.core.transport import sparse_thresholds

    key = jax.random.PRNGKey(11)
    c, m = 6, 3_001
    x = jax.random.normal(key, (c, m))
    w = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0, 1.0])
    z = jax.random.normal(jax.random.fold_in(key, 2), (m,))
    if kind == "analog":
        agg = lambda p: aircomp_aggregate_flat(x, w, z, noise_std=0.3, k=4.0,
                                               use_pallas=p)
    elif kind == "quantized":
        d = quant_step(x, 6.0)
        u = jax.random.uniform(jax.random.fold_in(key, 1), (c, m))
        agg = lambda p: quant_aircomp_flat(x, w, d, u, z, noise_std=0.3,
                                           k=4.0, use_pallas=p)
    else:
        thr = sparse_thresholds(x, 150)
        agg = lambda p: sparse_aircomp_flat(x, w, thr, z, noise_std=0.3,
                                            k=4.0, use_pallas=p)
    ref, pal = agg(False), agg(True)  # True: interpret mode off-TPU
    assert pal.shape == (m,)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Differential pins: analog bit-identity, bits=32 ≈ analog, digital == mean
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", METHODS)
def test_analog_is_invariant_to_transport_knobs(tdata, method):
    """The pre-PR pin: the analog program's output is a CONSTANT function of
    every transport knob (the pre-transport FLConfig had none, so any
    dependence would mean the analog path no longer compiles the pre-PR
    program). Masks, λ, energy and trajectories: bit-for-bit."""
    base = run_simulation(MODEL, _fl(method), tdata, seed=3)
    tweaked = run_simulation(
        MODEL, _fl(method, quant_bits=3.0, tx_power=9.9, ofdma_bandwidth=1.0,
                   rx_noise=123.0, sparse_density=0.5), tdata, seed=3)
    _hist_equal(base, tweaked, msg=f"analog-knobs:{method}")


def test_quantized_bits32_matches_analog(tdata):
    """At bits=32 the rounding grid is below f32 resolution and the energy
    scale factor bits/32 is exactly 1, so the quantized transport reproduces
    analog to f32 eps — with the IDENTICAL AWGN realization (same per-leaf
    streams)."""
    fl = _fl("ca_afl", noise_std=1e-3)
    ha = run_simulation(MODEL, fl, tdata, seed=3)
    hq = run_simulation(MODEL, replace(fl, transport="quantized",
                                       quant_bits=32.0), tdata, seed=3)
    eps = float(np.finfo(np.float32).eps)
    _hist_equal(ha, hq, msg="q32", rtol=64 * eps, atol=64 * eps)


def test_quantized_energy_scales_with_bits(tdata):
    """Quantized airtime (hence the ledger) is exactly bits/32 of analog.
    FedAvg's uniform draw is λ- and energy-independent, so both transports
    schedule the identical sets and the ledgers are directly comparable."""
    fl = _fl("fedavg")
    ha = run_simulation(MODEL, fl, tdata, seed=3)
    hq = run_simulation(MODEL, replace(fl, transport="quantized",
                                       quant_bits=8.0), tdata, seed=3)
    np.testing.assert_array_equal(np.asarray(hq.num_scheduled),
                                  np.asarray(ha.num_scheduled))
    np.testing.assert_allclose(np.asarray(hq.energy),
                               np.asarray(ha.energy) * (8.0 / 32.0),
                               rtol=1e-6)


def test_digital_aggregation_is_masked_weighted_mean():
    """The digital PS decodes each payload exactly: the aggregate is the
    plain masked weighted mean with NO superposition noise, regardless of
    the scenario's noise_std."""
    key = jax.random.PRNGKey(6)
    stack = {"w": jax.random.normal(key, (N, 5, 3)),
             "b": jax.random.normal(jax.random.fold_in(key, 1), (N, 3))}
    mask = (jax.random.uniform(jax.random.fold_in(key, 2), (N,)) > 0.5
            ).astype(jnp.float32)
    k = jnp.maximum(jnp.sum(mask), 1.0)
    # the simulator's digital branch: analog aggregation with a STATIC zero
    # noise_std — the AWGN draw is structurally elided
    agg = aircomp_aggregate_tree(stack, mask, jax.random.fold_in(key, 3),
                                 0.0, k)
    for name in ("w", "b"):
        manual = jnp.einsum("n...,n->...", stack[name], mask) / k
        np.testing.assert_allclose(np.asarray(agg[name]), np.asarray(manual),
                                   rtol=1e-6, atol=1e-7)


def test_digital_trajectories_equal_analog_sans_energy(tdata):
    """On a noise-free static scenario the digital round computes the exact
    same update as analog (weighted mean, no AWGN on either) — only the
    energy ledger differs (OFDMA rate/latency vs channel inversion)."""
    fl = _fl("ca_afl")
    ha = run_simulation(MODEL, fl, tdata, seed=3)
    hd = run_simulation(MODEL, replace(fl, transport="digital"), tdata,
                        seed=3)
    for name in ha._fields:
        if name == "energy":
            continue
        np.testing.assert_array_equal(np.asarray(getattr(ha, name)),
                                      np.asarray(getattr(hd, name)),
                                      err_msg=name)
    assert not np.allclose(np.asarray(ha.energy), np.asarray(hd.energy))


# ---------------------------------------------------------------------------
# Sparse-K == dense reference for every transport × scenario family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", ("default", "markov_fading",
                                      "battery_constrained"))
@pytest.mark.parametrize("transport_name", ("quantized", "digital", "sparse"))
def test_sparse_matches_dense_per_transport(tdata, transport_name, scenario):
    """The hot-path contract holds per transport: the selected-K gather
    round equals the dense [N, model] reference (control plane exact, model
    trajectory to summation order — quantized rows are content-addressed by
    client id, so the K gathered rows round bit-identically to dense).
    Analog is covered by tests/test_hotpath.py."""
    fl = replace(_fl("ca_afl", transport=transport_name, quant_bits=6.0),
                 **SCENARIOS[scenario])
    got = run_simulation(MODEL, fl, tdata, seed=3)
    ref = run_simulation(MODEL, fl, tdata, seed=3, dense=True)
    np.testing.assert_array_equal(np.asarray(got.num_scheduled),
                                  np.asarray(ref.num_scheduled))
    _hist_equal(got, ref, msg=f"{transport_name}@{scenario}",
                rtol=1e-4, atol=1e-5)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="population sharding needs >1 device; CI sets "
                           "XLA_FLAGS=--xla_force_host_platform_device_count=8")
@pytest.mark.parametrize("scenario", ("default", "markov_fading",
                                      "battery_constrained"))
@pytest.mark.parametrize("transport_name",
                         ("analog", "quantized", "digital", "sparse"))
def test_sharded_matches_dense_per_transport(tdata, transport_name, scenario):
    """Population sharding per transport: client-mesh rounds equal the dense
    reference (psum == eq. (10); quantized streams addressed by GLOBAL id,
    so shard-local rows round identically to the dense program's)."""
    fl = replace(_fl("ca_afl", rounds=5, transport=transport_name,
                     quant_bits=6.0), **SCENARIOS[scenario])
    mesh = sharding.client_mesh(sharding.population_device_count(N))
    ref = run_simulation(MODEL, fl, tdata, seed=3, dense=True)
    got = run_simulation(MODEL, fl, tdata, seed=3, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got.num_scheduled),
                                  np.asarray(ref.num_scheduled))
    _hist_equal(got, ref, msg=f"shard:{transport_name}@{scenario}",
                rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Sweep integration: one compile per scheme, knobs traced
# ---------------------------------------------------------------------------


def test_sweep_compiles_one_executable_per_transport(tdata):
    """A four-transport grid is four compilation groups (the scheme is
    structural), while a bits/power/downlink sub-grid WITHIN a scheme rides
    the vmap axis of one executable; the analog cell equals run_simulation
    exactly."""
    fl = _fl("ca_afl", rounds=4)
    specs = [
        ("analog", fl),
        ("quantized_b4", replace(fl, transport="quantized", quant_bits=4.0)),
        ("quantized_b8", replace(fl, transport="quantized", quant_bits=8.0)),
        ("digital", replace(fl, transport="digital")),
        ("digital_hp", replace(fl, transport="digital", tx_power=0.5)),
        ("sparse", replace(fl, transport="sparse")),
        ("sparse_dl", replace(fl, transport="sparse", dl_rx_power=1e-4)),
    ]
    sweep.reset_trace_log()
    result = sweep.run_sweep(MODEL, tdata, specs, seeds=(3,))
    # analog + quantized + digital + sparse (dl_rx_power stays traced)
    assert sweep.trace_count() == 4
    ref = run_simulation(MODEL, fl, tdata, seed=3)
    got = jax.tree.map(lambda x: x[0], result.history("analog"))
    _hist_equal(got, ref, msg="sweep-analog")
    s = result.summary(window=2)
    assert s["quantized_b4"]["energy"] < s["analog"]["energy"]
    assert s["digital"]["energy"] > s["analog"]["energy"]
    # the sparse uplink uploads ~density of the payload: cheapest of all
    assert s["sparse"]["energy"] < s["quantized_b4"]["energy"]
    # the downlink ledger is additive-only: identical trajectories, larger
    # total energy, and the share is exactly the dl_energy column
    assert s["sparse_dl"]["dl_energy"] > 0.0
    assert s["sparse"]["dl_energy"] == 0.0
    np.testing.assert_allclose(
        s["sparse_dl"]["energy"] - s["sparse_dl"]["dl_energy"],
        s["sparse"]["energy"], rtol=1e-5)
    np.testing.assert_allclose(s["sparse_dl"]["avg_acc"],
                               s["sparse"]["avg_acc"], rtol=1e-6)
