"""Chip smoke test: drive the system's main path once on a TPU.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the population-sharded path, 4 chips

One chip runs four phases, in one process (a chip belongs to one process at
a time):

1. device gate: the first JAX device must be a TPU — never a CPU fallback;
2. the three Pallas AirComp kernels, compiled, at the paper's [40, 7,850]
   and an unaligned [4, 1,000,001], each against its ``ref.py`` oracle run
   on the host CPU;
3. the paper's deployment (N=100, K=40, logreg M=7,850, T=500) through
   ``run_sweep`` for AFL and CA-AFL under all four uplink transports, plus
   round 0 of CA-AFL/analog on the host CPU against the chip;
4. ``ParameterServer`` on qwen2-0.5b at its published widths, 3 rounds,
   through ``repro.launch.train.main``.

``--chips 4`` runs only the population-sharded control plane on a 4-chip
``clients`` mesh (``run_simulation`` and ``run_sweep(devices=4)``) against
the same runs on one chip.

Compile times and rates are printed for information; they are not a
benchmark. Any failed check exits non-zero. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

TRANSPORTS = ("analog", "quantized", "digital", "sparse")
KERNEL_SHAPES = ((40, 7_850), (4, 1_000_001))
# f32 sums over <= 40 clients in a different order (Pallas VPU vs XLA:CPU)
REDUCTION_TOL = dict(rtol=1e-5, atol=1e-5)
# the sharded-vs-unsharded bound of tests/test_control_sharded.py (FMA_TOL)
FMA_TOL = dict(rtol=2e-5, atol=2e-6)
EXACT_FIELDS = ("num_scheduled", "avail_count")
# round 0 on the chip vs the host CPU: the TPU's default f32 matmul precision
# is not the CPU's, so the model update agrees only to this relative L2 norm
ROUND0_UPDATE_RTOL = 2e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def device_gate(chips: int):
    devs = jax.devices()
    dev = devs[0]
    log(f"jax {jax.__version__}: {len(devs)} x {dev.platform} "
        f"({dev.device_kind})")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (first device is "
                         f"{dev.platform!r}); refusing to run on it")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, found {len(devs)}")
    return dev


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def sweep(model, data, specs, **kw):
    from repro.core.sweep import run_sweep

    result = run_sweep(model, data, specs, seeds=(0, 1), **kw)
    jax.block_until_ready(result.histories)
    return result


# ---------------------------------------------------------------------------
# Phase 2: the kernels, compiled, against their oracles
# ---------------------------------------------------------------------------


def phase_kernels():
    from repro.core.transport import quant_step, sparse_thresholds
    from repro.kernels.aircomp.ops import (aircomp_aggregate_flat,
                                           quant_aircomp_flat,
                                           sparse_aircomp_flat)
    from repro.kernels.aircomp.ref import (aircomp_ref, quant_aircomp_ref,
                                           sparse_aircomp_ref)

    cpu = jax.devices("cpu")[0]
    for c, m in KERNEL_SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(c + m), 4)
        x = jax.random.normal(ks[0], (c, m))
        w = (jax.random.uniform(ks[1], (c,)) > 0.3).astype(jnp.float32)
        z = jax.random.normal(ks[2], (m,))
        u = jax.random.uniform(ks[3], (c, m))
        d = quant_step(x, 6.0)
        thr = sparse_thresholds(x, max(m // 100, 1))
        ns, k = 0.3, max(float(w.sum()), 1.0)
        cases = {
            "analog": (aircomp_aggregate_flat, aircomp_ref, (x, w, z)),
            "quantized": (quant_aircomp_flat, quant_aircomp_ref,
                          (x, w, d, u, z)),
            "sparse": (sparse_aircomp_flat, sparse_aircomp_ref,
                       (x, w, thr, z)),
        }
        for name, (op, ref, arrs) in cases.items():
            fn = jax.jit(lambda *a, op=op: op(*a[:-2], noise_std=a[-2],
                                              k=a[-1]))
            t0 = time.perf_counter()
            compiled = fn.lower(*arrs, ns, k).compile()
            t_compile = time.perf_counter() - t0
            check("tpu_custom_call" in compiled.as_text(),
                  f"{name} [{c}, {m}]: no Pallas kernel in the program")
            got, t_run = timed(compiled, *arrs, ns, k)
            got = np.asarray(got)
            want = np.asarray(jax.jit(ref)(*jax.device_put(arrs, cpu), ns, k))
            diff = np.abs(got - want)
            bad = diff > REDUCTION_TOL["atol"] + \
                REDUCTION_TOL["rtol"] * np.abs(want)
            extra = ""
            if name == "quantized" and bad.any():
                # f32 division x/Δ is not correctly rounded alike on the two
                # devices: a coordinate a hair from a grid point may round
                # to the neighbouring one — one grid step Δ_c·w_c/K apart
                step = float(jnp.max(d * w)) / k
                check(diff.max() <= step * 1.001 + REDUCTION_TOL["atol"],
                      f"quantized [{c}, {m}]: off by more than one grid step")
                check(bad.sum() <= max(1, m // 10_000),
                      f"quantized [{c}, {m}]: {bad.sum()} columns flipped")
                extra = f", {int(bad.sum())} rounding-boundary flips"
            else:
                check(not bad.any(),
                      f"{name} [{c}, {m}]: {int(bad.sum())} columns off, "
                      f"max |diff| {diff.max():.3e}")
            log(f"kernel {name:9s} [{c}, {m}]: compile {t_compile:.2f}s, "
                f"run {t_run * 1e3:.2f} ms, max |chip - oracle| "
                f"{diff.max():.3e}{extra}")


# ---------------------------------------------------------------------------
# Phase 3: the paper's deployment through run_sweep
# ---------------------------------------------------------------------------


def paper_specs(fl, control_plane="replicated"):
    return [(f"{method}_{tr}",
             replace(fl, method=method, transport=tr, energy_C=8.0,
                     control_plane=control_plane))
            for tr in TRANSPORTS for method in ("afl", "ca_afl")]


def check_history(label, hist, k):
    for name, leaf in hist._asdict().items():
        leaf = np.asarray(leaf)
        # a static scenario has no battery: min_battery is +inf by design
        ok = (~np.isnan(leaf) if name == "min_battery"
              else np.isfinite(leaf))
        check(ok.all(), f"{label}: non-finite {name}")
    check((np.asarray(hist.num_scheduled) <= k).all(),
          f"{label}: more than K={k} clients scheduled")


def compiled_round(model, fl, data, model_size, device):
    """The compiled round of ``fl`` and its round-0 arguments ``(point,
    state, t, data)``, all placed on ``device``."""
    from repro.core.simulator import init_sim_state, make_param_round_fn
    from repro.core.sweep import sweep_point_from_config

    def round_fn(point, state, t, data):
        return make_param_round_fn(model, fl, data, model_size,
                                   fl.method)(point, state, t)

    point = sweep_point_from_config(fl)
    state = init_sim_state(model, fl, jax.random.PRNGKey(0),
                           process=point.process)
    args = jax.device_put((point, state, jnp.int32(0), data), device)
    return jax.jit(round_fn).lower(*args).compile(), args


def round0_mask(fl, point, state):
    """Round 0's CA-AFL selection mask, drawn exactly as ``round_fn`` draws
    it on a static scenario (the same key split and channel draw)."""
    from repro.core.channel import draw_channels_scenario, effective_channel
    from repro.core.selection import select_clients_sparse

    _, k_chan, k_sel, *_ = jax.random.split(state.key, 7)
    h = effective_channel(draw_channels_scenario(
        k_chan, point.scenario, fl.num_clients, fl.num_subcarriers))
    mask, _ = select_clients_sparse(fl.method, k_sel, state.lam, h,
                                    fl.clients_per_round, C=point.energy_C)
    return mask


def rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def phase_paper():
    from benchmarks.paper_figs import make_setup
    from repro.utils.tree import tree_size

    model, fl, data = make_setup(full=True)
    log(f"paper deployment: N={fl.num_clients} K={fl.clients_per_round} "
        f"T={fl.rounds} M={tree_size(model.init(jax.random.PRNGKey(0)))}")
    specs = paper_specs(fl)
    result, dt = timed(sweep, model, data, specs)
    log(f"run_sweep: {len(specs)} groups x 2 seeds x {fl.rounds} rounds in "
        f"{dt:.1f}s, compilation included")
    final_e = {}
    for label, hist in zip(result.labels, result.histories, strict=True):
        check_history(label, hist, fl.clients_per_round)
        final_e[label] = float(np.mean(np.asarray(hist.energy)[:, -1]))
        log(f"  {label:16s} final energy {final_e[label]:.4e} J, "
            f"worst acc {float(np.mean(np.asarray(hist.worst_acc)[:, -1])):.4f}")
    check(final_e["ca_afl_analog"] < final_e["afl_analog"],
          "CA-AFL does not spend less energy than AFL under analog")

    model_size = tree_size(model.init(jax.random.PRNGKey(0)))
    tpu, cpu = jax.devices()[0], jax.devices("cpu")[0]
    rounds = {}
    for tr in ("analog", "quantized", "sparse"):
        rounds[tr] = compiled_round(model, dict(specs)[f"ca_afl_{tr}"],
                                    data, model_size, tpu)
        check("tpu_custom_call" in rounds[tr][0].as_text(),
              f"ca_afl/{tr}: the compiled round has no Pallas kernel")
        log(f"compiled ca_afl/{tr} round holds the Pallas kernel")

    # round 0 of CA-AFL/analog on the host CPU against the chip
    f = dict(specs)["ca_afl_analog"]
    per_dev = {"tpu": rounds["analog"],
               "cpu": compiled_round(model, f, data, model_size, cpu)}
    outs, masks = {}, {}
    for plat, (compiled, args) in per_dev.items():
        outs[plat] = jax.device_get(compiled(*args))
        point, state = args[:2]
        masks[plat] = np.asarray(jax.jit(
            lambda p, s: round0_mask(f, p, s))(point, state))
    np.testing.assert_array_equal(masks["tpu"], masks["cpu"])
    log(f"round 0 selection: chip == cpu ({int(masks['tpu'].sum())} of "
        f"{f.num_clients} scheduled)")
    w0 = jax.device_get(state.w)

    def update(s):
        return np.concatenate([
            (np.asarray(a) - np.asarray(b)).ravel()
            for a, b in zip(jax.tree.leaves(s.w), jax.tree.leaves(w0),
                            strict=True)])

    (s_tpu, h_tpu), (s_cpu, h_cpu) = outs["tpu"], outs["cpu"]
    diffs = {
        "update": rel_l2(update(s_tpu), update(s_cpu)),
        "lam": rel_l2(s_tpu.lam, s_cpu.lam),
        "energy": rel_l2(s_tpu.energy, s_cpu.energy),
        "loss": rel_l2(h_tpu.loss, h_cpu.loss),
        "avg_acc": rel_l2(h_tpu.avg_acc, h_cpu.avg_acc),
    }
    log("round 0 chip vs cpu, relative L2: " + ", ".join(
        f"{k} {v:.3e}" for k, v in diffs.items()))
    check(diffs["update"] < ROUND0_UPDATE_RTOL,
          f"round 0 update differs from the cpu by {diffs['update']:.3e}")


# ---------------------------------------------------------------------------
# Phase 4: the model tier at published widths
# ---------------------------------------------------------------------------


def phase_model_tier():
    from repro.configs import get_config
    from repro.launch import train

    cfg = get_config("qwen2-0.5b")
    # reckoned before the run (rehearsal memory_analysis for v5e: 2.5 GB
    # arguments + 2.5 GB outputs + 5.0 GB temporaries)
    emb = cfg.vocab_size * cfg.d_model
    log(f"model tier: {cfg.name} {cfg.num_layers}L d_model {cfg.d_model} "
        f"vocab {cfg.vocab_size}, f32; embedding alone {emb * 4 / 1e9:.2f} GB")
    (state, dt) = timed(lambda: train.main([
        "--arch", "qwen2-0.5b", "--rounds", "3", "--clients", "8",
        "--k", "4", "--seq", "128", "--batch-per-client", "2"]))
    hist = state.history
    check(len(hist) == 3, f"{len(hist)} rounds recorded, expected 3")
    for h in hist:
        for key in ("loss", "worst_client_loss"):
            check(np.isfinite(h[key]), f"round {h['round']}: {key}={h[key]}")
    check(np.isfinite(state.energy_joules) and state.energy_joules > 0,
          f"energy ledger {state.energy_joules}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"model tier: 3 rounds in {dt:.1f}s, compilation included; losses "
        f"{[round(float(h['loss']), 4) for h in hist]}; peak device memory "
        f"{peak / 1e9 if peak else float('nan'):.2f} GB")


# ---------------------------------------------------------------------------
# --chips 4: the population-sharded path against one chip
# ---------------------------------------------------------------------------


def assert_agrees(label, ref, sh):
    for f in ref._fields:
        a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(sh, f))
        if f in EXACT_FIELDS:
            np.testing.assert_array_equal(a, b, err_msg=f"{label}: {f}")
        else:
            np.testing.assert_allclose(b, a, err_msg=f"{label}: {f}",
                                       **FMA_TOL)


def phase_sharded(chips: int):
    from benchmarks.paper_figs import make_setup
    from repro.core import sharding
    from repro.core.simulator import run_simulation

    model, fl, data = make_setup(full=True)
    fl = replace(fl, rounds=50)
    specs = paper_specs(fl, control_plane="sharded")
    fl_ca = dict(specs)["ca_afl_analog"]

    mesh = sharding.client_mesh(chips)
    ref, t1 = timed(lambda: run_simulation(model, fl_ca, data, seed=0))
    sh, t4 = timed(lambda: run_simulation(model, fl_ca, data, seed=0,
                                          mesh=mesh))
    assert_agrees("run_simulation", ref, sh)
    # the population really spans the mesh, not its first chip: the data
    # shards the runner places and the history it returns
    _, _, sharded_data = sharding.build_control_sharded_runner(
        model, fl_ca, data, mesh)
    for arr in (*sharded_data, sh.avg_acc):
        check(len(arr.sharding.device_set) == chips,
              f"placed on {arr.sharding.device_set}, not {chips} chips")
    log(f"run_simulation ca_afl/analog N={fl.num_clients} T={fl.rounds}: "
        f"{chips}-chip clients mesh == 1 chip ({t1:.1f}s vs {t4:.1f}s, "
        "compilation included)")

    ref, t1 = timed(sweep, model, data, specs)
    sh, t4 = timed(lambda: sweep(model, data, specs, devices=chips))
    for label, a, b in zip(ref.labels, ref.histories, sh.histories,
                           strict=True):
        check_history(label, b, fl.clients_per_round)
        assert_agrees(f"run_sweep {label}", a, b)
    log(f"run_sweep {len(specs)} groups x 2 seeds: devices={chips} == "
        f"devices=1 ({t1:.1f}s vs {t4:.1f}s, compilation included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = device_gate(args.chips)
    from repro.utils.compile_cache import cache_entries, enable_compile_cache

    cache = enable_compile_cache()
    log(f"compile cache {cache}: {cache_entries(cache)} entries at start")
    t0 = time.perf_counter()
    if args.chips == 1:
        phases = [("kernels", phase_kernels), ("paper", phase_paper),
                  ("model tier", phase_model_tier)]
    else:
        phases = [("sharded", lambda: phase_sharded(args.chips))]
    for name, phase in phases:
        t = time.perf_counter()
        phase()
        log(f"phase {name} passed in {time.perf_counter() - t:.1f}s")
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s; compile "
        f"cache now {cache_entries(cache)} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
