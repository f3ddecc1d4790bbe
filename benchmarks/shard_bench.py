"""Sharded-sweep throughput cell: devices=1 vs devices=8 on one host.

Self-contained so it can force ``--xla_force_host_platform_device_count=8``
BEFORE jax initializes — which is why ``perf_bench`` runs it as a subprocess
instead of importing it (the parent's single-device cells must keep seeing
one device, per the conftest convention). Prints one JSON object on stdout;
everything else goes to stderr.

Both cells run inside the same 8-device process: devices=1 is a 1-device
``cells`` mesh-free run on device 0, devices=8 shards the seed axis of the
same grid over all host devices, so the comparison isolates the scale-out
and not the env. Execution wall time ONLY: the sweep runner is built and
compiled once per cell via the engine's own ``_build_runner`` and the timing
loop re-executes the jitted runner (``run_sweep`` would rebuild fresh jit
closures per call and the timing would be dominated by retracing).

`PYTHONPATH=src python -m benchmarks.shard_bench`
"""
from __future__ import annotations

import json
import os
import sys
import time

_FORCE = "--xla_force_host_platform_device_count=8"
if _FORCE not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {_FORCE}"

import jax  # noqa: E402  (env must be set before jax initializes)

from repro.configs.base import FLConfig  # noqa: E402
from repro.core import sweep  # noqa: E402
from repro.data.synthetic import make_fmnist_like  # noqa: E402
from repro.federated.partition import sorted_label_shards  # noqa: E402
from repro.models.logreg import logistic_regression  # noqa: E402

N, DIM, SEEDS, ROUNDS, REPS = 50, 128, tuple(range(8)), 30, 3


def _time_run(model, data, fl, devices):
    """Seconds per sweep execution at ``devices``, compile excluded.

    Builds the group runner once (the same executables ``run_sweep`` uses),
    then times REPS re-executions. The runner donates its state argument and
    returns same-shaped final states, so the timing loop ping-pongs them —
    each iteration feeds the previous iteration's output buffers back in,
    exactly the aliasing the donation exists for.
    """
    import jax.numpy as jnp

    from repro.core import sharding
    from repro.utils.tree import tree_size

    mesh = sharding.cell_mesh(devices) if devices > 1 else None
    point = sweep._stack_points([sweep.sweep_point_from_config(fl)])
    seeds_arr = jnp.asarray(SEEDS, jnp.int32)
    model_size = tree_size(model.init(jax.random.PRNGKey(0)))
    init_fn, runner = sweep._build_runner(
        model, fl, fl.method, noise_free=fl.noise_std == 0,
        model_size=model_size, mesh=mesh)
    data = tuple(jnp.asarray(d) for d in data)
    states = init_fn(point, seeds_arr)
    states, hist = runner(point, states, data)  # warm-up: compile + execute
    jax.block_until_ready(hist)
    t0 = time.perf_counter()
    for _ in range(REPS):
        states, hist = runner(point, states, data)
    jax.block_until_ready((states, hist))
    return (time.perf_counter() - t0) / REPS


def main():
    x, y, xt, yt = make_fmnist_like(N * 24, N * 6, dim=DIM, seed=0)
    xs, ys = sorted_label_shards(x, y, N)
    xts, yts = sorted_label_shards(xt, yt, N)
    data = (xs, ys, xts, yts)
    model = logistic_regression(DIM, 10)
    fl = FLConfig(num_clients=N, clients_per_round=10, rounds=ROUNDS,
                  batch_size=20, lr0=0.3, method="ca_afl", eval_every=5)

    t1 = _time_run(model, data, fl, devices=1)
    t8 = _time_run(model, data, fl, devices=8)
    cells = len(SEEDS)
    payload = {
        "grid": f"1 config x {len(SEEDS)} seeds x T={ROUNDS} "
                f"(N={N}, dim={DIM})",
        "host_devices": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "devices1_seconds": t1,
        "devices8_seconds": t8,
        "devices1_cells_per_second": cells / t1,
        "devices8_cells_per_second": cells / t8,
        "speedup_devices8": t1 / t8,
    }
    print(f"[shard_bench] devices=1 {t1:.2f}s, devices=8 {t8:.2f}s "
          f"-> {payload['speedup_devices8']:.2f}x on {os.cpu_count()} cores",
          file=sys.stderr)
    json.dump(payload, sys.stdout)
    sys.stdout.write("\n")
    return payload


if __name__ == "__main__":
    main()
