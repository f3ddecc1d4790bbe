"""Hot-path perf benchmark: dense [N, model] reference vs selected-K rounds.

Measures, per cell (N × {dense, sparse, sparse+eval cadence}):

  - compile seconds (AOT ``lower().compile()``)
  - execution wall seconds and rounds/sec for a T-round jitted scan
  - peak live bytes of the compiled executable (XLA memory analysis:
    arguments + outputs + temporaries)

plus a **sharded-sweep throughput cell** (``benchmarks/shard_bench.py``, run
as a subprocess so its forced 8-device host platform cannot skew the
single-device cells): the same seeds-grid swept with ``run_sweep(devices=1)``
vs ``devices=8``, recording the scale-out speedup of the cells mesh. That
child, the popscale child and the lint child run with ``JAX_PLATFORMS=cpu``
(they measure forced host devices, and the parent holds any accelerator);
their cells are labelled ``"device": "cpu"``.

Writes ``benchmarks/results/BENCH_perf.json`` — the artifact CI uploads per
commit, with the headline ``speedup_n100`` = hot path (sparse gather +
eval_every cadence) over the dense path at the paper's N=100, K=10.

`PYTHONPATH=src python -m benchmarks.perf_bench`
"""
from __future__ import annotations

import json
import os
import platform
import subprocess
import sys
import time
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.core.simulator import (init_sim_state, make_param_round_fn)
from repro.core.sweep import sweep_point_from_config
from repro.data.synthetic import make_fmnist_like
from repro.federated.partition import sorted_label_shards
from repro.models.logreg import logistic_regression
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.tree import tree_size

REPO = Path(__file__).resolve().parent.parent
RESULTS = REPO / "benchmarks" / "results"

DIM = 784  # the paper's FMNIST logreg: M = 7850

# (N, rounds): dense N=1000 pays 100x the sparse model work per round, so
# its timing loop is kept short; the per-round rate is what we report.
GRIDS = ((100, 40), (1000, 8))
K = 10


def _data(n):
    per_train, per_test = 20, 5
    x, y, xt, yt = make_fmnist_like(n * per_train, n * per_test, dim=DIM,
                                    seed=0)
    xs, ys = sorted_label_shards(x, y, n)
    xts, yts = sorted_label_shards(xt, yt, n)
    return xs, ys, xts, yts


def bench_cell(model, fl, data, dense: bool):
    point = sweep_point_from_config(fl)
    state = init_sim_state(model, fl, jax.random.PRNGKey(0),
                           process=point.process)
    round_fn = make_param_round_fn(model, fl, data, tree_size(state.w),
                                   fl.method, dense=dense)

    def run(point, state):
        _, hist = jax.lax.scan(
            lambda s, t: round_fn(point, s, t), state,
            jnp.arange(fl.rounds))
        return hist

    t0 = time.perf_counter()
    compiled = jax.jit(run).lower(point, state).compile()
    compile_s = time.perf_counter() - t0

    jax.block_until_ready(compiled(point, state))  # warm-up execution
    # best-of-3: the cells feed ratio floors (quantized/sparse vs analog),
    # and a single timing window on a shared CI runner jitters +-10% — the
    # minimum is the least-contended estimate of the program's true cost
    exec_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(point, state))
        exec_s = min(exec_s, time.perf_counter() - t0)

    try:
        ma = compiled.memory_analysis()
        peak_bytes = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes)
    except Exception:  # backend without memory stats
        peak_bytes = None
    return {
        "compile_seconds": compile_s,
        "exec_seconds": exec_s,
        "rounds_per_second": fl.rounds / exec_s,
        "peak_live_bytes": peak_bytes,
    }


def _host_child(*args):
    """Run ``python -m <args>`` on forced CPU host devices.

    The parent already holds the process's accelerator (a chip belongs to one
    process), and these cells measure forced host devices by design, so the
    child is pinned to the CPU and its result is labelled ``cpu``.
    """
    return subprocess.run(
        [sys.executable, "-m", *args], capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})


def _write(payload):
    RESULTS.mkdir(parents=True, exist_ok=True)
    with open(RESULTS / "BENCH_perf.json", "w") as f:
        json.dump(payload, f, indent=2)


def main():
    enable_compile_cache()
    model = logistic_regression(DIM, 10)
    dev = jax.devices()[0]
    payload = {
        "bench": "perf_bench",
        "model": f"logreg dim={DIM} (M={DIM * 10 + 10})",
        "clients_per_round": K,
        "jax_version": jax.__version__,
        "platform": platform.platform(),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "cells": {},
    }
    for n, rounds in GRIDS:
        data = _data(n)
        fl = FLConfig(num_clients=n, clients_per_round=K, rounds=rounds,
                      batch_size=50, method="ca_afl")
        cells = {
            "dense": bench_cell(model, fl, data, dense=True),
            "sparse": bench_cell(model, fl, data, dense=False),
            # the full hot path: sparse gather + eval cadence
            "sparse_eval10": bench_cell(
                model, FLConfig(**{**fl.__dict__, "eval_every": 10}), data,
                dense=False),
        }
        for name, row in cells.items():
            print(f"[perf_bench] N={n:5d} {name:13s} "
                  f"{row['rounds_per_second']:8.2f} rounds/s  "
                  f"compile {row['compile_seconds']:.2f}s  "
                  f"peak {row['peak_live_bytes'] or 0:>12,} B")
        cells["speedup_sparse"] = (cells["sparse"]["rounds_per_second"]
                                   / cells["dense"]["rounds_per_second"])
        cells["speedup_hot_path"] = (
            cells["sparse_eval10"]["rounds_per_second"]
            / cells["dense"]["rounds_per_second"])
        payload["cells"][f"n{n}"] = cells
        print(f"[perf_bench] N={n}: sparse {cells['speedup_sparse']:.1f}x, "
              f"hot path {cells['speedup_hot_path']:.1f}x over dense")

    payload["speedup_n100"] = payload["cells"]["n100"]["speedup_hot_path"]

    # ---- per-transport round throughput (N=100 hot path): the fused
    # quantize-aggregate and compress-aggregate passes must not tax the
    # round — acceptance floors are quantized AND sparse >= 0.8x analog
    # rounds/sec; digital is recorded for the energy-accounting trajectory
    # (its aggregation is the noise-free mean)
    data = _data(100)
    fl = FLConfig(num_clients=100, clients_per_round=K, rounds=40,
                  batch_size=50, method="ca_afl")
    tcells = {}
    for tr in ("analog", "quantized", "digital", "sparse"):
        row = bench_cell(model, replace(fl, transport=tr), data, dense=False)
        tcells[tr] = row
        print(f"[perf_bench] transport {tr:10s} "
              f"{row['rounds_per_second']:8.2f} rounds/s  "
              f"compile {row['compile_seconds']:.2f}s")
    for tr in ("quantized", "digital", "sparse"):
        tcells[f"{tr}_vs_analog"] = (tcells[tr]["rounds_per_second"]
                                     / tcells["analog"]["rounds_per_second"])
    payload["cells"]["transports_n100"] = tcells
    print(f"[perf_bench] quantized transport at "
          f"{tcells['quantized_vs_analog']:.2f}x, sparse at "
          f"{tcells['sparse_vs_analog']:.2f}x analog throughput")

    # ---- sharded-sweep scale-out cell (subprocess: needs its own 8-device
    # host platform, which must not leak into the cells above) -------------
    try:
        proc = _host_child("benchmarks.shard_bench")
        proc.check_returncode()
        shard = {**json.loads(proc.stdout), "device": "cpu"}
        payload["cells"]["sharded_sweep"] = shard
        print(f"[perf_bench] sharded sweep: devices=8 "
              f"{shard['speedup_devices8']:.2f}x devices=1 "
              f"({shard['cpu_count']} cores)")
    except subprocess.CalledProcessError as e:
        # still write the already-measured cells before failing the job —
        # same artifact-first policy as the floors below
        print(f"[perf_bench] shard_bench failed:\n{e.stderr}", file=sys.stderr)
        payload["cells"]["sharded_sweep"] = {"error": e.stderr[-2000:]}
        _write(payload)
        raise

    # ---- population-scale control-plane cell (subprocess for the same
    # 8-device isolation): N-scaling of control_plane="sharded" up to 10^6
    # clients; popscale_bench itself enforces the O(N/D) per-device-memory
    # ceiling and fails the job on a replication regression ----------------
    try:
        proc = _host_child("benchmarks.popscale_bench")
        proc.check_returncode()
        pop = {**json.loads(proc.stdout), "device": "cpu"}
        payload["cells"]["popscale"] = pop
        big = max(pop["cells"].values(), key=lambda c: c["n_clients"])
        print(f"[perf_bench] popscale: N={big['n_clients']:,} at "
              f"{big['rounds_per_second']:.2f} rounds/s, "
              f"{big['control_bytes_per_client']:.1f} control B/client "
              f"(x{pop['per_client_bytes_ratio_largest_vs_smallest']:.2f} "
              "vs smallest N)")
    except subprocess.CalledProcessError as e:
        print(f"[perf_bench] popscale_bench failed:\n{e.stderr}",
              file=sys.stderr)
        payload["cells"]["popscale"] = {"error": e.stderr[-2000:]}
        _write(payload)
        raise

    # ---- contract-lint cell (ISSUE 9): the CI lint lane's exact command —
    # both layers, AST rules + jaxpr program analyzers — timed end to end
    # (subprocess, so its traces can't warm this process's jit caches). The
    # per-layer seconds come from the linter's own JSON report; the wall
    # ceiling is enforced with the other floors below so the lane stays
    # cheap enough to run on every commit.
    report = RESULTS / "lint-report.json"
    RESULTS.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    proc = _host_child("repro.lint", "--jaxpr", "--json", str(report))
    lint_wall = time.perf_counter() - t0
    lint_report = json.loads(report.read_text())
    payload["cells"]["lint"] = {
        "device": "cpu",
        "wall_seconds": lint_wall,
        "ast_seconds": lint_report["ast"]["seconds"],
        "jaxpr_seconds": lint_report["jaxpr"]["seconds"],
        "exit_code": proc.returncode,
        "violations": len(lint_report["ast"]["violations"]),
        "jaxpr_checks_failed": [c["name"] for c in
                                lint_report["jaxpr"]["checks"]
                                if not c["ok"]],
    }
    print(f"[perf_bench] contract lint: {lint_wall:.1f}s wall "
          f"(AST {lint_report['ast']['seconds']:.1f}s, jaxpr "
          f"{lint_report['jaxpr']['seconds']:.1f}s), "
          f"exit {proc.returncode}")

    _write(payload)
    print(f"[perf_bench] wrote {RESULTS / 'BENCH_perf.json'} "
          f"(speedup_n100={payload['speedup_n100']:.2f}x)")
    # acceptance floors, enforced AFTER the artifact is written so a failing
    # run still leaves the measured cells behind for diagnosis:
    # (1) the hot path must stay >= 3x the dense reference at the paper's
    # N=100, K=10; (2) the sharded sweep must deliver >= 3x at devices=8 —
    # but only where the host can physically provide it (8 forced host
    # devices on a 2-core runner cap out near 2x regardless of the sharding
    # layer, so small hosts record the number without failing the job)
    if payload["speedup_n100"] < 3.0:
        raise SystemExit(
            f"hot-path regression: speedup_n100 = "
            f"{payload['speedup_n100']:.2f}x < 3x acceptance floor")
    q_ratio = payload["cells"]["transports_n100"]["quantized_vs_analog"]
    if q_ratio < 0.8:
        raise SystemExit(
            f"quantized-transport regression: {q_ratio:.2f}x analog round "
            "throughput < 0.8x acceptance floor (fused quantize-aggregate "
            "pass is taxing the round)")
    s_ratio = payload["cells"]["transports_n100"]["sparse_vs_analog"]
    if s_ratio < 0.8:
        raise SystemExit(
            f"sparse-transport regression: {s_ratio:.2f}x analog round "
            "throughput < 0.8x acceptance floor (top-k compress + "
            "error-feedback carry is taxing the round)")
    shard = payload["cells"]["sharded_sweep"]
    if (shard["cpu_count"] or 0) >= 8 and shard["speedup_devices8"] < 3.0:
        raise SystemExit(
            f"sharded-sweep regression: devices=8 speedup "
            f"{shard['speedup_devices8']:.2f}x < 3x floor on "
            f"{shard['cpu_count']} cores")
    lint = payload["cells"]["lint"]
    if lint["exit_code"] != 0:
        raise SystemExit(
            f"contract lint failed (exit {lint['exit_code']}): "
            f"{lint['violations']} violation(s), jaxpr checks failed: "
            f"{lint['jaxpr_checks_failed']}\n{proc.stdout[-2000:]}")
    if lint["wall_seconds"] > 60.0:
        raise SystemExit(
            f"contract-lint ceiling: {lint['wall_seconds']:.1f}s wall > 60s "
            "— the jaxpr analyzer harness grew too expensive for a "
            "per-commit lane")
    return payload


if __name__ == "__main__":
    main()
