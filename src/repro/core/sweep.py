"""Batched scenario-sweep engine: one jit for a whole (seeds × scenarios ×
hyperparameter) grid.

The paper's claims are averages over seeds and comparisons across selection
methods and channel conditions. Running that grid through
``run_simulation`` costs one compilation *per cell*; this engine instead
partitions the grid by its *structural* signature (anything that changes the
traced program: N, K, T, batch size, sub-carriers, flat-vs-selective fading
and the selection method) and runs each group as

    jit( vmap_points( vmap_seeds( lax.scan(round_fn) ) ) )

so every scalar knob — learning rates, ``energy_C``, GCA hyperparameters,
channel floor/noise/shadowing/pathloss — rides a ``vmap`` axis of a single
compiled executable. A five-seed × {FedAvg, AFL, GCA, CA-AFL(C=2), CA-AFL
(C=8)} comparison compiles 4 executables instead of 25.

Usage::

    specs  = expand_grid(base_fl, variants={"afl": {"method": "afl"},
                                            "c8": {"method": "ca_afl",
                                                   "energy_C": 8.0}},
                         scenarios=("default", "noisy_uplink"))
    result = run_sweep(model, data, specs, seeds=(0, 1, 2, 3, 4))
    result.summary()          # per-label mean/std/worst-case across seeds
    result.pareto_front()     # energy-vs-robustness Pareto extraction

Compilations are observable via ``trace_count()`` (a Python side effect at
trace time), which the test suite uses to pin "one compile per method".
"""
from __future__ import annotations

import json
from dataclasses import dataclass, replace
from typing import Any, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.configs.base import FLConfig, GCAParams
from repro.core import sharding
from repro.core.channel import SCENARIOS, scenario_from_config
from repro.core.dynamics import ChannelProcess, process_from_config
from repro.core.transport import TransportParams, transport_from_config
from repro.core.simulator import (SimHistory, init_sim_state,
                                  make_param_round_fn)
from repro.utils.tree import tree_size

__all__ = [
    "SweepPoint", "SweepResult", "sweep_point_from_config", "expand_grid",
    "run_sweep", "trace_count", "reset_trace_log", "pareto_indices",
]


# ---------------------------------------------------------------------------
# Sweep points: the traced per-cell knobs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SweepPoint:
    """All per-cell knobs the round function consumes as traced values.

    ``method`` is pytree metadata (it selects Python branches); the scenario's
    own ``flat`` flag is metadata inside the nested ``ChannelScenario``.
    Points whose metadata differ cannot share a vmap axis — ``run_sweep``
    groups them into separate compilations.
    """

    scenario: Any              # ChannelScenario (data: traced; meta: flat)
    lr0: Any = 0.1
    lr_decay: Any = 0.998
    ascent_lr: Any = 8e-3
    energy_C: Any = 8.0
    gca: Any = GCAParams()     # NamedTuple of (possibly traced) scalars
    process: Any = ChannelProcess()  # temporal dynamics (meta: temporal)
    transport: Any = TransportParams()  # uplink transport (meta: scheme)
    method: str = "ca_afl"


jax.tree_util.register_dataclass(
    SweepPoint,
    data_fields=["scenario", "lr0", "lr_decay", "ascent_lr", "energy_C", "gca",
                 "process", "transport"],
    meta_fields=["method"],
)


def sweep_point_from_config(fl: FLConfig) -> SweepPoint:
    """Promote an ``FLConfig``'s scalar knobs to f32 arrays (vmap-stackable)."""
    f32 = lambda v: jnp.asarray(v, jnp.float32)  # noqa: E731
    return SweepPoint(
        scenario=scenario_from_config(fl),
        lr0=f32(fl.lr0),
        lr_decay=f32(fl.lr_decay),
        ascent_lr=f32(fl.ascent_lr),
        energy_C=f32(fl.energy_C),
        gca=GCAParams(*(f32(v) for v in fl.gca)),
        process=process_from_config(fl),
        transport=transport_from_config(fl),
        method=fl.method,
    )


# Structural FLConfig fields: changing any of these changes the traced
# program, so specs are grouped by this signature (one compile per group).
# `temporal` switches the stateless draw for the ChannelProcess carry
# (core/dynamics.py): all dynamic scenarios share one group per method, and
# the i.i.d. default keeps compiling to exactly PR 1's program. `eval_every`
# changes the metrics sub-program (per-round eval vs cond-gated cadence +
# eval_cache carry), so cells with different cadences cannot share an
# executable — cells with the SAME cadence still do. `transport` selects the
# uplink aggregation/energy program (core/transport.py): each scheme is its
# own group per method, every scheme KNOB (bits, powers, bandwidth) stays
# traced, and "analog" compiles to exactly the pre-transport program.
# `control_plane` selects the per-client randomness discipline (replicated
# full-[N] draws vs per-id fold_in streams + slot assembly, core/simulator.py)
# — two different programs with different key consumption.
# `record_lambda_every` changes the λ-history sub-program (per-round scan
# output vs cond-gated strided snapshot carry vs no history leaf at all), so
# cells with different cadences cannot share an executable.
# `sparse_density` is structural FOR THE SPARSE SCHEME ONLY: it bakes the
# compiled top-k width (`transport.sparse_k_coords`); the other schemes never
# read it, but keeping it in the signature unconditionally is harmless (cells
# that differ only in an unread knob are rare) and keeps the grouping rule
# free of scheme-conditional logic.
STATIC_FIELDS: Tuple[str, ...] = (
    "num_clients", "clients_per_round", "rounds", "batch_size", "local_steps",
    "num_subcarriers", "flat_fading", "temporal", "eval_every", "transport",
    "sparse_density", "method", "control_plane", "record_lambda_every",
)


def _static_signature(fl: FLConfig) -> Tuple:
    return tuple(getattr(fl, f) for f in STATIC_FIELDS)


# ---------------------------------------------------------------------------
# Grid expansion: variants × named scenarios -> labelled FLConfigs
# ---------------------------------------------------------------------------


def expand_grid(
    base: FLConfig,
    variants: Optional[Mapping[str, Mapping[str, Any]]] = None,
    scenarios: Sequence[Any] = ("default",),
) -> list[Tuple[str, FLConfig]]:
    """Cross method/hyperparameter ``variants`` with channel ``scenarios``.

    ``variants`` maps label -> FLConfig field overrides; ``scenarios`` entries
    are names from :data:`repro.core.channel.SCENARIOS`, raw override dicts
    (labelled by their contents, e.g. ``noise_std=0.01``), or explicit
    ``(name, overrides)`` pairs. Returns ``[(label, config), ...]`` ready for
    :func:`run_sweep`.
    """
    variants = dict(variants or {"base": {}})
    specs = []
    for sc in scenarios:
        if isinstance(sc, str):
            sc_name, sc_kw = sc, SCENARIOS[sc]
        elif isinstance(sc, tuple):
            sc_name, sc_kw = sc[0], dict(sc[1])
        else:
            sc_kw = dict(sc)
            sc_name = ",".join(f"{k}={v:g}" if isinstance(v, float) else
                               f"{k}={v}" for k, v in sc_kw.items()) or "default"
        # only the true baseline (no overrides) drops the @suffix — an explicit
        # ("default", {...}) pair with overrides keeps its label distinct
        baseline = sc_name == "default" and not sc_kw
        for vlabel, vkw in variants.items():
            label = vlabel if baseline else f"{vlabel}@{sc_name}"
            specs.append((label, replace(base, **{**sc_kw, **vkw})))
    return specs


# ---------------------------------------------------------------------------
# Compilation accounting (used by tests and the CI benchmark smoke)
# ---------------------------------------------------------------------------

_TRACE_LOG: list[str] = []


def trace_count() -> int:
    """Number of sweep-executable compilations since the last reset."""
    return len(_TRACE_LOG)


def reset_trace_log() -> None:
    _TRACE_LOG.clear()


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


def _stack_points(points: Sequence[SweepPoint]) -> SweepPoint:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *points)


def _build_runner(model, fl_static: FLConfig, method: str,
                  noise_free: bool, model_size: int, mesh=None):
    """Two jitted executables: an initializer ``(points [S], seeds [R]) ->
    SimState`` stack with leading [S, R] axes, and the runner ``(points,
    states, data) -> (final states, SimHistory)``.

    The client data ``(x, y, x_test, y_test)`` is an ARGUMENT of the runner,
    never a closed-over constant: at the paper's scale it is ~220 MB, which
    as a constant would be copied into every executable (making each too
    large for the persistent compilation cache) and recompiled per group.

    The initial-state stack is built OUTSIDE the runner and donated into it
    (``donate_argnums``): the scan carry then reuses the caller's buffers
    in-place instead of holding both generations of [S, R, model] state live
    — returning the final states (same shapes) is what gives XLA the
    input→output aliasing that makes the donation effective (and warning-
    free, which ``tests/test_sweep.py`` asserts).

    ``mesh`` (sweep-cell sharding, ``core/sharding.py``): both executables
    are wrapped in ``shard_map`` splitting the SEED axis over the ``cells``
    mesh — each device initializes and scans its own [S, R/D] block of
    fully-independent cells, so results are bit-identical to the
    single-device program (no cross-cell reduction exists anywhere).
    ``mesh=None`` / size 1 skips the wrapping entirely: today's exact
    programs.
    """
    def init_one(point, seed):
        # the point's process carries the traced battery_init for ChanState
        return init_sim_state(model, fl_static, jax.random.PRNGKey(seed),
                              process=point.process)

    def init_batched(points, seeds):
        over_seeds = jax.vmap(init_one, in_axes=(None, 0))
        return jax.vmap(over_seeds, in_axes=(0, None))(points, seeds)

    def run_one(point, state, data):
        round_fn = make_param_round_fn(model, fl_static, data, model_size,
                                       method, noise_free=noise_free)
        final, hist = jax.lax.scan(
            lambda s, t: round_fn(point, s, t), state,
            jnp.arange(fl_static.rounds))
        if fl_static.record_lambda_every > 1:
            # strided λ snapshots ride the scan carry (lax.scan cannot emit
            # [T/E] stacks); attach the final buffer as the history's λ leaf
            hist = hist._replace(lam=final.lam_snaps)
        return final, hist

    def batched(points, states, data):
        # Python side effect: runs once per *compilation* (trace), never on
        # cached executions — this is the compile counter the tests assert on.
        _TRACE_LOG.append(method)
        over_seeds = jax.vmap(run_one, in_axes=(None, 0, None))
        return jax.vmap(over_seeds, in_axes=(0, 0, None))(points, states,
                                                           data)

    if mesh is not None and mesh.size > 1:
        P = PartitionSpec
        cell = mesh.axis_names[0]
        # points [S, ...] replicated; states/histories [S, R, ...] split on
        # the seed axis. R % mesh.size == 0 is guaranteed by run_sweep's
        # seed padding.
        init_batched = jax.shard_map(init_batched, mesh=mesh,
                                     in_specs=(P(), P(cell)),
                                     out_specs=P(None, cell), check_vma=False)
        batched = jax.shard_map(batched, mesh=mesh,
                                in_specs=(P(), P(None, cell), P()),
                                out_specs=(P(None, cell), P(None, cell)),
                                check_vma=False)
    return jax.jit(init_batched), jax.jit(batched, donate_argnums=(1,))


def _build_sharded_group_runner(model, fl_static: FLConfig, method: str,
                                mesh, noise_free: bool, model_size: int):
    """One jitted executable for a ``control_plane="sharded"`` group on the
    2-D ``cells × clients`` mesh (ISSUE 8): ``fn(points [S], seeds [R],
    *sharded_data) -> SimHistory`` with leading [S, R] axes.

    The per-cell body is ``sharding.control_sharded_cell_run`` — the SAME
    function the 1-D client-mesh runner shard_maps — vmapped over stacked
    points × seeds inside ``shard_map``: the seed axis splits over the
    ``cells`` mesh rows while every client-row collective (psum-bisection
    projection, hierarchical top-k, ownership-psum assembly, eq. (10)) runs
    on the ``clients`` columns and vmaps over the cell batch unchanged. The
    state is initialized INSIDE the body (λ/ChanState born as local rows),
    so no [N]-sized array exists per device at any point — there is no
    donated init stack to build, unlike :func:`_build_runner`.
    """
    P = PartitionSpec
    cell_ax, client_ax = mesh.axis_names
    n_client_dev = mesh.shape[client_ax]
    n_local = fl_static.num_clients // n_client_dev
    cell_run = sharding.control_sharded_cell_run(
        model, fl_static, method, client_ax, n_local, model_size,
        noise_free=noise_free)

    def run_cells(points, seeds, x, y, x_test, y_test):
        # same compile-counter side effect as _build_runner.batched
        _TRACE_LOG.append(method)

        def one(point, seed):
            return cell_run(point, jax.random.PRNGKey(seed),
                            x, y, x_test, y_test)

        over_seeds = jax.vmap(one, in_axes=(None, 0))
        return jax.vmap(over_seeds, in_axes=(0, None))(points, seeds)

    mapped = jax.shard_map(
        run_cells, mesh=mesh,
        in_specs=(P(), P(cell_ax), P(client_ax), P(client_ax), P(client_ax),
                  P(client_ax)),
        out_specs=sharding.control_sharded_history_specs(
            fl_static, client_ax, lead=(None, cell_ax)),
        check_vma=False)
    return jax.jit(mapped)


def _grid_fingerprint(specs, seeds) -> np.ndarray:
    """A [32] uint8 digest of the full grid — labels, every config field
    (traced knobs included), seed list and order. Stored inside the resume
    checkpoint so a rerun whose grid differs in ANY way (reordered specs, a
    changed learning rate under the same label, different seeds) fails
    loudly instead of resuming stale or misattributed histories; the 'done'
    flags are positional and only safe under an identical grid."""
    import hashlib

    desc = repr([(lbl, fl) for lbl, fl in specs]) + repr(tuple(seeds))
    return np.frombuffer(hashlib.sha256(desc.encode()).digest(), np.uint8)


def _history_template(fl: FLConfig, num_seeds: int) -> SimHistory:
    """Zero-filled [R, T(, N)] SimHistory with the shapes/dtypes run_sweep
    produces — the restore template of the checkpoint resume hook."""
    r, t, n = num_seeds, fl.rounds, fl.num_clients
    e = fl.record_lambda_every
    z = lambda *shape: np.zeros(shape, np.float32)  # noqa: E731
    lam = () if e == 0 else (z(r, t, n) if e == 1
                             else z(r, (t + e - 1) // e, n))
    return SimHistory(avg_acc=z(r, t), worst_acc=z(r, t), std_acc=z(r, t),
                      energy=z(r, t), loss=z(r, t), num_scheduled=z(r, t),
                      lam=lam, avail_count=z(r, t),
                      min_battery=z(r, t), lam_max=z(r, t),
                      lam_entropy=z(r, t), lam_ess=z(r, t),
                      dl_energy=z(r, t))


def run_sweep(
    model,
    data,
    specs: Sequence[Tuple[str, FLConfig]],
    seeds: Sequence[int] = (0,),
    devices=None,
    client_devices: Optional[int] = None,
    checkpoint_dir: Optional[str] = None,
) -> "SweepResult":
    """Run every (spec × seed) cell; one compilation per structural group.

    ``specs`` is ``[(label, FLConfig), ...]`` (see :func:`expand_grid`).
    Returns a :class:`SweepResult` whose per-label histories have a leading
    seed axis [R] on every leaf.

    ``devices`` shards the grid's seed axis over a ``cells`` device mesh
    (``None`` = single device, today's exact program; ``"auto"`` = every
    local device; an int caps the count). Cells are independent, so the
    sharded sweep is bit-identical to the unsharded one — the seed list is
    padded up to a multiple of the mesh size internally and the padding
    columns discarded.

    ``client_devices`` (``control_plane="sharded"`` groups only) factors the
    device count into a 2-D ``cells × clients`` mesh: each group runs with
    its seed axis split over ``devices / client_devices`` mesh rows and its
    client population split over ``client_devices`` columns
    (:func:`sharding.cells_clients_mesh`). ``None`` auto-picks the largest
    divisor of the device count that divides N (1 — a pure cells mesh — when
    none fits or the group is replicated-discipline). The 2-D run is
    differential-pinned against the 1-D and single-device paths: discrete
    fields exact, continuous to ulps (``tests/test_control_sharded.py``).

    ``checkpoint_dir`` (opt-in resume for long grids): after each
    compilation group completes, the per-label histories land in a
    ``repro.checkpoint`` msgpack checkpoint; a rerun with the same specs,
    seeds and directory restores the finished groups and computes only the
    rest. Shape validation comes from the fixed restore template, so a
    changed grid (different seeds/rounds/N) fails loudly instead of
    resuming garbage.
    """
    labels = [lbl for lbl, _ in specs]
    if len(set(labels)) != len(labels):
        raise ValueError(f"duplicate sweep labels: {labels}")

    n_dev = sharding.resolve_device_count(devices)
    mesh = sharding.cell_mesh(n_dev) if n_dev > 1 else None
    num_seeds = len(tuple(seeds))
    run_seeds = (sharding.pad_to_multiple(list(seeds), n_dev)
                 if n_dev > 1 else list(seeds))
    seeds_arr = jnp.asarray(tuple(run_seeds), jnp.int32)

    groups: dict[Tuple, list[int]] = {}
    for i, (_, fl) in enumerate(specs):
        groups.setdefault(_static_signature(fl), []).append(i)

    # ---- checkpoint resume hook (opt-in) -------------------------------
    done = np.zeros((len(specs),), np.float32)
    ckpt_template = None
    if checkpoint_dir is not None:
        from repro.checkpoint.ckpt import (latest_step, restore_checkpoint,
                                           save_checkpoint)
        ckpt_template = {
            "done": np.zeros((len(specs),), np.float32),
            "grid": _grid_fingerprint(specs, seeds),
            "hist": {lbl: _history_template(fl, num_seeds)
                     for lbl, fl in specs},
        }

    histories: list[Optional[SimHistory]] = [None] * len(specs)
    if checkpoint_dir is not None and latest_step(checkpoint_dir) is not None:
        restored = restore_checkpoint(checkpoint_dir, ckpt_template)
        if not np.array_equal(np.asarray(restored["grid"]),
                              ckpt_template["grid"]):
            raise ValueError(
                f"checkpoint in {checkpoint_dir} was written by a different "
                "sweep grid (labels/configs/seeds changed or reordered) — "
                "resuming would misattribute histories; point "
                "checkpoint_dir elsewhere or delete the stale checkpoint")
        done = np.asarray(restored["done"]).copy()
        for i, lbl in enumerate(labels):
            if done[i]:
                histories[i] = restored["hist"][lbl]

    model_size = tree_size(model.init(jax.random.PRNGKey(0)))
    data_dev = None  # placed once, on first use by a single-program group
    groups_done = sum(
        1 for idxs in groups.values() if all(done[i] for i in idxs))
    for idxs in groups.values():
        if all(done[i] for i in idxs):
            continue  # restored from the checkpoint
        fl0 = specs[idxs[0]][1]
        points = _stack_points(
            [sweep_point_from_config(specs[i][1]) for i in idxs])
        # elide the eq.-(10) noise draw only if the whole group is noise-free
        noise_free = all(specs[i][1].noise_std == 0 for i in idxs)
        d_clients = 1
        if n_dev > 1 and fl0.control_plane == "sharded":
            d_clients = sharding.factor_client_devices(
                fl0.num_clients, n_dev, client_devices)
        if d_clients > 1:
            # 2-D cells × clients mesh: seeds split over the rows, client
            # rows over the columns. The global seed padding to n_dev is a
            # multiple of the cells dimension (d_cells divides n_dev).
            mesh2 = sharding.cells_clients_mesh(n_dev, d_clients)
            runner = _build_sharded_group_runner(
                model, fl0, fl0.method, mesh2, noise_free, model_size)
            sharded_data = tuple(
                sharding.shard_leading(jnp.asarray(d), mesh2,
                                       mesh2.axis_names[1]) for d in data)
            hist = runner(points, seeds_arr, *sharded_data)
        else:
            if data_dev is None:
                data_dev = tuple(jnp.asarray(d) for d in data)
            init_fn, runner = _build_runner(model, fl0, fl0.method,
                                            noise_free, model_size, mesh=mesh)
            states = init_fn(points, seeds_arr)  # leaves [S_group, R_pad, ..]
            # final states are discarded; returning them is what lets XLA
            # alias the donated inputs (see _build_runner)
            # leaves [S_group, R_pad, T, ..]
            _, hist = runner(points, states, data_dev)
        for s, i in enumerate(idxs):
            # drop the seed-padding columns of a sharded run
            histories[i] = jax.tree.map(lambda x, s=s: x[s, :num_seeds], hist)
            done[i] = 1.0
        if checkpoint_dir is not None:
            groups_done += 1
            tree = {
                "done": done,
                "grid": ckpt_template["grid"],
                "hist": {lbl: (histories[i] if done[i] else
                               ckpt_template["hist"][lbl])
                         for i, (lbl, _) in enumerate(specs)},
            }
            save_checkpoint(checkpoint_dir, groups_done, tree, keep=1)

    return SweepResult(
        labels=labels,
        configs=[fl for _, fl in specs],
        seeds=tuple(int(s) for s in seeds),
        histories=histories,
    )


# ---------------------------------------------------------------------------
# Aggregation: seed statistics + energy/robustness Pareto extraction
# ---------------------------------------------------------------------------


def pareto_indices(costs: np.ndarray, utilities: np.ndarray) -> list[int]:
    """Indices on the (minimize cost, maximize utility) Pareto frontier."""
    keep = []
    for i in range(len(costs)):
        dominated = np.any(
            (costs <= costs[i]) & (utilities >= utilities[i])
            & ((costs < costs[i]) | (utilities > utilities[i])))
        if not dominated:
            keep.append(i)
    return sorted(keep, key=lambda i: costs[i])


@dataclass
class SweepResult:
    """Sweep output: per-label seed-batched histories + aggregation helpers."""

    labels: list[str]
    configs: list[FLConfig]
    seeds: Tuple[int, ...]
    histories: list[SimHistory]  # leaves [R, T, ...] per label

    def __post_init__(self):
        self._by_label = {lbl: i for i, lbl in enumerate(self.labels)}

    def history(self, label: str) -> SimHistory:
        """Per-seed history for one label (leaves [R, T, ...])."""
        return self.histories[self._by_label[label]]

    def mean_history(self, label: str) -> SimHistory:
        """Seed-averaged history (leaves [T, ...]); == old run_multi_seed."""
        return jax.tree.map(lambda x: x.mean(0), self.history(label))

    def summary(self, window: int = 10) -> dict:
        """Per-label statistics over the final ``window`` *evaluated* rounds.

        mean/std across seeds for avg/worst accuracy, the worst-case (min
        over seeds) worst-client accuracy, and final cumulative energy.

        Under ``eval_every = E > 1`` the accuracy columns between evals are
        forward-filled copies of the last eval; a naive tail window would
        count each fresh eval up to E times and bias the statistic toward
        whichever eval happens to sit closest to the end. The accuracy
        window therefore ranges over the label's actual eval rounds
        (``t % E == 0``) only — at E=1 that is exactly the old behavior,
        and an E>1 summary equals the E=1 summary computed on the
        subsampled eval cadence. Per-round quantities (scheduled counts,
        availability) are genuine every round and keep the plain tail
        window.

        λ-derived statistics follow the same rule on the
        ``record_lambda_every`` cadence (the same forward-fill/aliasing bug
        class): when the dense/strided λ history is recorded, the window
        ranges over the last ``window`` *recorded* rows — an E>1 summary
        equals the E=1 summary subsampled onto the recording cadence
        (test-pinned). At E=0 (no λ history) the columns fall back to the
        always-on per-round summary leaves (max / entropy / effective
        support size), whose tail window is genuine every round.
        """
        out = {}
        for lbl in self.labels:
            h = self.history(lbl)
            cfg = self.configs[self._by_label[lbl]]
            rounds = np.asarray(h.avg_acc).shape[1]
            eval_idx = np.arange(0, rounds, max(1, cfg.eval_every))[-window:]
            avg = np.asarray(h.avg_acc)[:, eval_idx].mean(1)     # [R]
            worst = np.asarray(h.worst_acc)[:, eval_idx].mean(1)  # [R]
            std = np.asarray(h.std_acc)[:, eval_idx].mean(1)     # [R]
            energy = np.asarray(h.energy)[:, -1]                 # [R]
            dl_energy = np.asarray(h.dl_energy)[:, -1]           # [R]
            sched = np.asarray(h.num_scheduled)[:, -window:].mean(1)  # [R]
            avail = np.asarray(h.avail_count)[:, -window:].mean(1)    # [R]
            min_batt = float(np.asarray(h.min_battery)[:, -1].mean())
            lam = np.asarray(h.lam) if not isinstance(h.lam, tuple) else None
            if lam is not None and lam.size:
                # window over the last `window` RECORDED rows ([R, T/E, N]) —
                # never over forward-filled round indices
                la = lam[:, -window:, :]
                lam_max = la.max(-1).mean(1)                          # [R]
                plogp = la * np.log(np.where(la > 0, la, 1.0))
                lam_entropy = (-plogp.sum(-1)).mean(1)                # [R]
                lam_ess = (1.0 / np.maximum(
                    (la ** 2).sum(-1), np.finfo(la.dtype).tiny)).mean(1)
            else:
                # E=0: no λ history — the per-round summary leaves are the
                # only λ record and their tail is genuine every round
                lam_max = np.asarray(h.lam_max)[:, -window:].mean(1)
                lam_entropy = np.asarray(h.lam_entropy)[:, -window:].mean(1)
                lam_ess = np.asarray(h.lam_ess)[:, -window:].mean(1)
            out[lbl] = {
                "avg_acc": float(avg.mean()),
                "avg_acc_std": float(avg.std()),
                "worst_acc": float(worst.mean()),
                "worst_acc_std": float(worst.std()),
                "worst_case_acc": float(worst.min()),
                "client_std": float(std.mean()),
                "energy": float(energy.mean()),
                "energy_std": float(energy.std()),
                # downlink share of the TOTAL `energy` column (additive; 0
                # at the default dl_rx_power=0)
                "dl_energy": float(dl_energy.mean()),
                "num_scheduled": float(sched.mean()),
                "avail_count": float(avail.mean()),
                # None (JSON null) for static scenarios, where it is +inf
                "min_battery": min_batt if np.isfinite(min_batt) else None,
                "lam_max": float(lam_max.mean()),
                "lam_entropy": float(lam_entropy.mean()),
                "lam_ess": float(lam_ess.mean()),
            }
        return out

    def pareto_front(self, window: int = 10, cost: str = "energy",
                     utility: str = "worst_acc") -> list[str]:
        """Labels on the energy-vs-robustness Pareto frontier."""
        s = self.summary(window)
        costs = np.array([s[lbl][cost] for lbl in self.labels])
        utils = np.array([s[lbl][utility] for lbl in self.labels])
        return [self.labels[i] for i in pareto_indices(costs, utils)]

    def to_dict(self, window: int = 10) -> dict:
        return {
            "labels": self.labels,
            "seeds": list(self.seeds),
            "summary": self.summary(window),
            "pareto_energy_vs_worst_acc": self.pareto_front(window),
        }

    def save_json(self, path, window: int = 10, extra: Optional[dict] = None):
        payload = self.to_dict(window)
        if extra:
            payload.update(extra)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2)
        return payload
