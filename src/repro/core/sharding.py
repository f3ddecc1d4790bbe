"""Multi-device scale-out: the device-mesh layer (ROADMAP "sharding" lever).

Two embarrassingly-parallel axes of the engine are sharded here:

  - **Sweep cells** (``repro.core.sweep.run_sweep(devices=...)``): the stacked
    [S, R] points × seeds grid of a compilation group is split over a 1-D
    ``"cells"`` mesh with ``shard_map`` — each device scans its own seed
    columns of every point row. Cells are fully independent (no cross-cell
    reduction anywhere in the round), so the sharded sweep is *bit-identical*
    to the single-device sweep on every history leaf.

  - **Client population** (``run_simulation(mesh=...)``, dense/GCA rounds +
    the full N-client test eval): per-client model-sized state — data shards,
    batch gathers, local SGD stacks, per-client gradients/losses/accuracies —
    is sharded over a ``"clients"`` mesh axis, and eq. (10)'s over-the-air
    superposition is computed as a local weighted partial-sum followed by a
    ``psum`` (``aircomp.aircomp_psum_tree``): the multiple-access sum the
    paper gets "for free" in the air IS the all-reduce, exactly the mapping
    ``core/aircomp.py`` documents. Exact-K selection is a local top-k per
    shard followed by a global top-k over the K·n_shards candidates
    (:func:`distributed_top_k`), equal to the dense ``lax.top_k`` by
    construction, tie-break included.

Key discipline under sharding — two generations, selected by the STRUCTURAL
``FLConfig.control_plane`` field:

  - ``"replicated"`` (the pre-ISSUE-7 default): every [N]-shaped
    control-plane draw (channels, Gumbel noise, batch indices, availability,
    process innovations) is drawn *replicated* — each device draws the full-N
    array from the identical key and slices its rows — and the model-sized
    AWGN of eq. (10) is drawn once per leaf with the per-leaf key discipline
    of ``aircomp_aggregate_tree``. Masks, λ inputs, energy and every O(N)
    scalar are bit-identical to the single-device program, and the model
    trajectory differs only in the summation order of the cross-shard
    ``psum``. The control plane is O(N) *per device*, which caps N.

  - ``"sharded"`` (ISSUE 7): per-client draws are content-addressed by
    GLOBAL client id (``channel.client_keys`` fold_in streams — the
    quantizer's trick), so each device draws and stores only its N/D rows of
    channels, availability, selection scores, batch indices and ``ChanState``
    — O(N/D) control plane per device. Exact-K selection runs as a
    hierarchical tree top-k (:func:`hierarchical_top_k`); the K winners'
    batches/channels are assembled replicated via ownership-``psum``
    (:func:`assemble_rows` — adding exact zeros, so bit-exact), and the
    mesh run is BIT-identical to the single-device run of the same
    discipline on every history leaf for exact-K methods
    (``run_simulation_control_sharded``; pinned by
    ``tests/test_control_sharded.py``). The λ simplex projection runs as a
    shard-local bisection on the water level (:func:`project_simplex_sharded`
    — psum-of-local-rows, no gather, no sort; ISSUE 8), so the only O(N)
    gather left is GCA's population-wide threshold statistics.

A mesh of size 1 is a structural no-op: callers skip the ``shard_map``
wrapping entirely and compile today's exact programs.

On this CPU container the mesh is realized with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see the CI
multi-device lane and ``tests/test_sharding.py``); on TPU the same code
shards over real chips and the ``psum`` lowers to the ICI all-reduce.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "CELL_AXIS", "CLIENT_AXIS", "cell_mesh", "client_mesh",
    "cells_clients_mesh", "factor_client_devices",
    "resolve_device_count", "population_device_count", "local_slice",
    "all_gather_axis", "distributed_top_k", "hierarchical_top_k",
    "project_simplex_sharded", "global_client_ids", "assemble_rows",
    "assemble_batch_rows", "shard_leading", "shard_batch",
    "run_simulation_sharded", "run_simulation_control_sharded",
    "control_sharded_cell_run", "build_control_sharded_runner",
    "pad_to_multiple",
]

# Mesh axis names. "cells" parallelizes independent sweep cells (points ×
# seeds); "clients" parallelizes the client population inside one simulation.
CELL_AXIS = "cells"
CLIENT_AXIS = "clients"


# ---------------------------------------------------------------------------
# Mesh construction / device accounting
# ---------------------------------------------------------------------------


def _mesh(n_devices: int, axis: str) -> Mesh:
    devs = jax.devices()
    if n_devices > len(devs):
        raise ValueError(
            f"requested {n_devices} devices, only {len(devs)} present "
            "(on CPU, set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return Mesh(np.array(devs[:n_devices]), (axis,))


def cell_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D ``"cells"`` mesh over the first ``n_devices`` (default: all)."""
    return _mesh(n_devices or jax.device_count(), CELL_AXIS)


def client_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D ``"clients"`` mesh over the first ``n_devices`` (default: all)."""
    return _mesh(n_devices or jax.device_count(), CLIENT_AXIS)


def cells_clients_mesh(n_devices: int, client_devices: int) -> Mesh:
    """2-D ``("cells", "clients")`` mesh: ``n_devices // client_devices``
    rows of sweep cells × ``client_devices`` columns of client shards, so a
    sweep grid and the client populations inside its cells shard
    simultaneously (ISSUE 8 — ``run_sweep`` factors its device budget here).
    """
    devs = jax.devices()
    if n_devices > len(devs):
        raise ValueError(
            f"requested {n_devices} devices, only {len(devs)} present "
            "(on CPU, set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    if isinstance(client_devices, bool) or \
            not isinstance(client_devices, (int, np.integer)) or \
            client_devices < 1:
        raise ValueError(
            f"client_devices must be a positive int, got {client_devices!r}")
    if n_devices % client_devices:
        raise ValueError(
            f"client_devices must divide the device count evenly, got "
            f"{client_devices} of {n_devices}")
    arr = np.array(devs[:n_devices]).reshape(
        n_devices // client_devices, client_devices)
    return Mesh(arr, (CELL_AXIS, CLIENT_AXIS))


def factor_client_devices(num_clients: int, n_devices: int,
                          client_devices=None) -> int:
    """The ``clients``-axis extent of a 2-D sweep mesh: an explicit request
    (validated — it must divide both the device count and N) or, by default,
    the LARGEST divisor of ``n_devices`` that also divides ``num_clients``
    (maximal population sharding, the million-client north star; remaining
    devices parallelize sweep cells). Always >= 1 — a population no divisor
    fits degrades to pure cell sharding, never an error.
    """
    if isinstance(num_clients, bool) or \
            not isinstance(num_clients, (int, np.integer)) or num_clients < 1:
        raise ValueError(
            f"num_clients must be a positive int, got {num_clients!r}")
    if client_devices is not None:
        if isinstance(client_devices, bool) or \
                not isinstance(client_devices, (int, np.integer)) or \
                client_devices < 1:
            raise ValueError(
                f"client_devices must be a positive int or None, got "
                f"{client_devices!r}")
        c = int(client_devices)
        if n_devices % c:
            raise ValueError(
                f"client_devices={c} must divide devices={n_devices} evenly")
        if num_clients % c:
            raise ValueError(
                f"client_devices={c} must divide num_clients={num_clients} "
                "evenly (equal client shards per device)")
        return c
    for c in range(n_devices, 0, -1):
        if n_devices % c == 0 and num_clients % c == 0:
            return c
    return 1


def resolve_device_count(devices) -> int:
    """Normalize a ``devices`` request: None -> 1 (single-device, today's
    exact program), "auto" -> every local device, int -> exactly that many.

    An over-request raises the same actionable error as ``_mesh`` — it used
    to be silently clamped to the present device count, so
    ``run_sweep(devices=16)`` on an 8-device host quietly ran 8-wide and the
    missing parallelism surfaced only as mystery slowness much later.
    """
    if devices is None:
        return 1
    if devices == "auto":
        return jax.device_count()
    if isinstance(devices, bool) or not isinstance(devices, (int, np.integer)):
        raise TypeError(
            f"devices must be an int, 'auto' or None, got {devices!r}")
    n = int(devices)
    if n < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    if n > jax.device_count():
        raise ValueError(
            f"requested {n} devices, only {jax.device_count()} present "
            "(on CPU, set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return n


def population_device_count(num_clients: int,
                            devices: Optional[int] = None) -> int:
    """Largest device count <= ``devices`` (default: all) dividing N evenly —
    population sharding keeps equal client shards per device.

    Validates its inputs: ``num_clients`` must be a positive int (0 used to
    spin the divisor search forever) and ``devices`` must be an int or None
    (a stray ``"auto"`` belongs to :func:`resolve_device_count`; here it
    used to be treated as truthy garbage by the modulo).
    """
    if isinstance(num_clients, bool) or \
            not isinstance(num_clients, (int, np.integer)):
        raise TypeError(
            f"num_clients must be an int, got {num_clients!r}")
    if num_clients < 1:
        raise ValueError(
            f"num_clients must be >= 1, got {num_clients}")
    if devices is None:
        n_dev = jax.device_count()
    else:
        if isinstance(devices, bool) or \
                not isinstance(devices, (int, np.integer)):
            raise TypeError(
                f"devices must be an int or None, got {devices!r} "
                "(resolve 'auto' via resolve_device_count first)")
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        n_dev = int(devices)
    while num_clients % n_dev:
        n_dev -= 1
    return n_dev


# ---------------------------------------------------------------------------
# In-shard-map primitives
# ---------------------------------------------------------------------------


def local_slice(arr: jnp.ndarray, axis_name: str, n_local: int) -> jnp.ndarray:
    """This device's rows of a *replicated* leading-[N] array.

    The control plane draws full-N arrays on every device (identical values —
    same key, same shape); the model-sized work then runs on the local rows
    only. ``n_local`` must be static (N // mesh size)."""
    d = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(arr, d * n_local, n_local)


def all_gather_axis(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Concatenate per-shard leading axes back to the global [N] order."""
    return jax.lax.all_gather(x, axis_name, tiled=True)


def _auto_group_size(n_shards: int) -> int:
    """Default tree fan-in: the largest divisor of D not above sqrt(D), so
    both gather stages carry O(sqrt(D))·k candidates. Below 16 shards the
    flat two-level pass (group = all shards) is already minimal."""
    if n_shards < 16:
        return n_shards
    best = 1
    for g in range(2, int(n_shards ** 0.5) + 1):
        if n_shards % g == 0:
            best = g
    return best if best > 1 else n_shards


def hierarchical_top_k(scores_local: jnp.ndarray, k: int, axis_name: str,
                       n_shards: int, group_size: Optional[int] = None
                       ) -> jnp.ndarray:
    """Global top-k indices [k] of a sharded score vector, tree-reduced.

    Three levels — per-shard → group → global (ISSUE 7):

      1. each shard ``lax.top_k``'s its own rows: kk = min(k, n_local)
         candidates (a shard can contribute at most that many to the true
         top-k, so nothing is lost);
      2. shards ``all_gather`` within *contiguous groups* of ``group_size``
         (``axis_index_groups``) and keep the group's top min(k, G·kk);
      3. one representative gather across the groups (each device sits in
         exactly one transposed representative group, and every member of a
         group computed identical stage-2 results) and a final top-k.

    Per-device traffic is O(G·kk + (D/G)·k) ≈ O(k·sqrt(D)) at the default
    fan-in instead of the flat pass's O(k·D); with ``group_size`` in
    {None at D<16, 1, D} the tree degenerates to the flat two-level pass.

    Equal to dense ``lax.top_k`` *by construction*, ties included: top_k
    emits ties lowest-index-first, groups are contiguous shard ranges
    gathered in shard order, and representative gathers run in group order —
    so every level resolves ties to the lowest global index, recursively
    reproducing the dense semantics. Returns the replicated winner indices;
    callers scatter their own (local or global) masks.
    """
    n_local = scores_local.shape[0]
    kk = min(k, n_local)
    v, i = jax.lax.top_k(scores_local, kk)
    gi = i + jax.lax.axis_index(axis_name) * n_local
    g = group_size if group_size is not None else _auto_group_size(n_shards)
    if g <= 1 or g >= n_shards or n_shards % g:
        # flat two-level: gather all D shards' candidates at once
        cand_v = all_gather_axis(v, axis_name)        # [D*kk], shard order
        cand_i = all_gather_axis(gi, axis_name)
    else:
        n_groups = n_shards // g
        # stage 2: contiguous groups [r·g, (r+1)·g) gather in shard order
        groups = [[b * g + r for r in range(g)] for b in range(n_groups)]
        vv = jax.lax.all_gather(v, axis_name, axis_index_groups=groups,
                                tiled=True)           # [g*kk]
        ii = jax.lax.all_gather(gi, axis_name, axis_index_groups=groups,
                                tiled=True)
        k2 = min(k, g * kk)
        gv, gpos = jax.lax.top_k(vv, k2)
        gidx = ii[gpos]
        # stage 3: transposed representative groups — member r of every
        # group gathers all groups' (identical per member) stage-2 winners
        # in group order; each device appears in exactly one rep group
        rep = [[b * g + r for b in range(n_groups)] for r in range(g)]
        cand_v = jax.lax.all_gather(gv, axis_name, axis_index_groups=rep,
                                    tiled=True)       # [n_groups*k2]
        cand_i = jax.lax.all_gather(gidx, axis_name, axis_index_groups=rep,
                                    tiled=True)
    _, pos = jax.lax.top_k(cand_v, k)
    return cand_i[pos]


def distributed_top_k(scores_local: jnp.ndarray, k: int, axis_name: str,
                      n_global: int, group_size: Optional[int] = None):
    """Exact-K selection over a sharded score vector: ``(mask [N], idx [k])``.

    The winner indices come from :func:`hierarchical_top_k` (flat two-level
    by default below 16 shards — the pre-tree program — and a per-shard →
    group → global tree above, or at an explicit ``group_size``); the [N]
    mask is their scatter. Equal to the dense ``lax.top_k(scores, k)`` by
    construction, tie-break pinned to the lowest global index (see
    :func:`hierarchical_top_k` for the argument). Callers that must not
    materialize O(N) use ``hierarchical_top_k`` directly and scatter a
    local mask.
    """
    n_local = scores_local.shape[0]
    idx = hierarchical_top_k(scores_local, k, axis_name,
                             n_shards=n_global // n_local,
                             group_size=group_size)
    mask = jnp.zeros((n_global,), jnp.float32).at[idx].set(1.0)
    return mask, idx


def project_simplex_sharded(v_local: jnp.ndarray,
                            axis_name: Optional[str] = None,
                            iters: int = 64) -> jnp.ndarray:
    """Euclidean simplex projection of a row-sharded vector — bisection on
    the water level θ, the distributed replacement for the sort-based
    ``dro.project_simplex`` (ISSUE 8).

    θ* is the unique root of the monotone-decreasing piecewise-linear
    g(θ) = Σᵢ max(vᵢ − θ, 0) − 1: each device sums ``max(v_local − θ, 0)``
    over its own N/D rows and one ``psum`` per iteration yields the global
    g — O(N/D + iters) per device with NO gather and NO sort, following the
    distributed-projection rule (psum-of-local-rows, never
    gather-then-reduce). The initial bracket [vmax − 1, vmax] always
    contains θ*: g(vmax) = −1 < 0, and g(vmax − 1) ≥ vmax − (vmax − 1) − 1
    = 0. ``iters=64`` halvings of the unit-width bracket pin the SUPPORT
    SET {i : vᵢ > θ*} (a discrete object, robust to θ jitter); a final
    closed-form polish then recomputes θ from that support —
    θ = (Σ_supp vᵢ − 1) / |supp|, one more psum pair — which is EXACTLY the
    sort-based reference's θ formula with ρ = |supp|, so the result matches
    it to ≤1e-6 relative at any input magnitude (raw bisection alone
    saturates at ulp(vmax), ~4e-6 already at vmax ≈ 40; pinned by
    ``tests/test_lambda_control.py``).

    ``axis_name=None`` runs the identical program on unsharded rows (local
    sums only) — the single-device reference of the sharded discipline, so
    the mesh and no-mesh programs differ only by psum summation order.
    −inf rows are legal (they project to exact 0, as under the sort); the
    projection is undefined when every row is −inf/+inf, exactly as for the
    sort-based reference.
    """
    v = v_local
    vmax = jnp.max(v)
    if axis_name is not None:
        vmax = jax.lax.pmax(vmax, axis_name)

    def g(theta):
        s = jnp.sum(jnp.maximum(v - theta, 0.0))
        if axis_name is not None:
            s = jax.lax.psum(s, axis_name)
        return s - 1.0

    def body(_, bracket):
        lo, hi = bracket
        mid = 0.5 * (lo + hi)
        above = g(mid) > 0          # θ* lies right of mid
        return (jnp.where(above, mid, lo), jnp.where(above, hi, mid))

    lo, hi = jax.lax.fori_loop(0, iters, body, (vmax - 1.0, vmax))
    # support-set polish: >= keeps the argmax in support even if the
    # collapsed bracket rounds to vmax itself, and a row sitting exactly AT
    # the water level contributes θ* to both sums, leaving θ unchanged
    supp = v >= 0.5 * (lo + hi)
    cnt = jnp.sum(supp.astype(v.dtype))
    ssum = jnp.sum(jnp.where(supp, v, 0.0))
    if axis_name is not None:
        cnt = jax.lax.psum(cnt, axis_name)
        ssum = jax.lax.psum(ssum, axis_name)
    theta = (ssum - 1.0) / cnt
    return jnp.maximum(v - theta, 0.0)


def global_client_ids(axis_name: str, n_local: int) -> jnp.ndarray:
    """This shard's GLOBAL client ids [n_local]: d·n_local + arange."""
    return (jax.lax.axis_index(axis_name) * n_local
            + jnp.arange(n_local, dtype=jnp.int32))


def assemble_rows(values_local: jnp.ndarray, idx: jnp.ndarray,
                  axis_name: str, n_local: int) -> jnp.ndarray:
    """Replicated [K, ...] stack of the rows at GLOBAL indices ``idx`` from a
    row-sharded array — the ownership-``psum`` gather of the sharded control
    plane.

    Each global index is owned by exactly one shard; every shard contributes
    its owned rows and an EXACT zero elsewhere (``jnp.where``, never
    multiplication — 0·inf would be NaN), so the psum adds one value and
    D−1 exact zeros per slot: bit-identical to an unsharded gather. O(K·D)
    traffic, O(K) per-device memory.
    """
    off = jax.lax.axis_index(axis_name) * n_local
    lidx = jnp.clip(idx - off, 0, n_local - 1)
    rows = values_local[lidx]                          # [K, ...]
    owned = (idx >= off) & (idx < off + n_local)
    oshape = (-1,) + (1,) * (rows.ndim - 1)
    rows = jnp.where(owned.reshape(oshape), rows, jnp.zeros_like(rows))
    return jax.lax.psum(rows, axis_name)


def assemble_batch_rows(shards_local: jnp.ndarray, idx: jnp.ndarray,
                        bidx: jnp.ndarray, axis_name: str,
                        n_local: int) -> jnp.ndarray:
    """Replicated [K, B, ...] batch stack gathered from sharded client data.

    ``shards_local`` [n_local, S, ...] is this device's client rows;
    ``idx`` [K] global winner ids; ``bidx`` [K, B] their in-shard sample
    indices (content-addressed by id, so any device can draw them — only the
    data rows need the ownership-psum). Same exact-zero argument as
    :func:`assemble_rows`.
    """
    off = jax.lax.axis_index(axis_name) * n_local
    lidx = jnp.clip(idx - off, 0, n_local - 1)
    rows = jax.vmap(lambda c, b: shards_local[c][b])(lidx, bidx)  # [K, B, ...]
    owned = (idx >= off) & (idx < off + n_local)
    oshape = (-1,) + (1,) * (rows.ndim - 1)
    rows = jnp.where(owned.reshape(oshape), rows, jnp.zeros_like(rows))
    return jax.lax.psum(rows, axis_name)


# ---------------------------------------------------------------------------
# Host-side sharding helpers
# ---------------------------------------------------------------------------


def shard_leading(tree, mesh: Mesh, axis: Optional[str] = None):
    """``device_put`` every leaf with its leading axis split over ``mesh``."""
    axis = axis or mesh.axis_names[0]
    sh = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


def shard_batch(batch: dict, mesh: Mesh) -> dict:
    """Shard a production-tier batch dict over the clients axis.

    Leaves whose leading (example) axis divides the mesh size are split; any
    other leaf is replicated. With the canonical one-block-per-client layout
    this partitions per-client forward/backward work across devices under
    jit's SPMD partitioner — semantics are unchanged (sharding is metadata to
    XLA), it is purely a placement hint.
    """
    axis = mesh.axis_names[0]
    split = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    def put(x):
        arr = jnp.asarray(x)
        ok = arr.ndim >= 1 and arr.shape[0] % mesh.size == 0
        return jax.device_put(arr, split if ok else repl)
    return {k: put(v) for k, v in batch.items()}


# ---------------------------------------------------------------------------
# Population-sharded simulation runner
# ---------------------------------------------------------------------------


def run_simulation_sharded(model, fl, data, mesh: Mesh, seed=None,
                           dense: bool = True):
    """Run T rounds with the client population sharded over ``mesh``.

    The whole scan runs inside one ``shard_map``: per-client data shards ride
    in split over the ``clients`` axis, the carry (global model, λ, energy,
    keys, ChanState) is replicated, and the round body is the simulator's own
    ``round_fn`` built with ``axis_name`` set (see
    ``simulator.make_param_round_fn``) — dense/GCA rounds only, the regime
    population sharding exists for. Exact-K methods run their dense reference
    program (sharded D ways); the selected-K gather path stays single-device.
    """
    from repro.core.simulator import init_sim_state, make_param_round_fn
    from repro.core.sweep import sweep_point_from_config
    from repro.utils.tree import tree_size

    axis = mesh.axis_names[0]
    n_dev = mesh.size
    if fl.num_clients % n_dev:
        raise ValueError(
            f"population sharding needs N % devices == 0, got "
            f"N={fl.num_clients}, devices={n_dev} "
            "(pick a count via population_device_count)")

    seed = fl.seed if seed is None else seed
    point = sweep_point_from_config(fl)
    state = init_sim_state(model, fl, jax.random.PRNGKey(seed),
                           process=point.process)
    model_size = tree_size(state.w)

    def run(point, state, x, y, x_test, y_test):
        # x/y/x_test/y_test arrive as this device's client rows
        round_fn = make_param_round_fn(
            model, fl, (x, y, x_test, y_test), model_size, fl.method,
            dense=dense, axis_name=axis)
        final, hist = jax.lax.scan(
            lambda s, t: round_fn(point, s, t), state, jnp.arange(fl.rounds))
        if fl.record_lambda_every > 1:
            # strided λ snapshots ride the scan carry, not the per-round
            # stacked outputs (lax.scan cannot emit [T/E] stacks)
            hist = hist._replace(lam=final.lam_snaps)
        return hist

    shard_mapped = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(), check_vma=False)
    sharded_data = tuple(shard_leading(jnp.asarray(d), mesh, axis)
                         for d in data)
    return jax.jit(shard_mapped)(point, state, *sharded_data)


def run_simulation_control_sharded(model, fl, data, mesh: Mesh, seed=None,
                                   group_size: Optional[int] = None):
    """Run T rounds with the CONTROL PLANE sharded over ``mesh`` (ISSUE 7).

    The ``control_plane="sharded"`` discipline end to end: each device holds
    only its N/D client rows of data, λ, ``ChanState`` and every per-round
    draw (content-addressed by global client id — ``channel.client_keys``),
    selection is the hierarchical tree top-k, and the K winners' batches and
    channels are assembled replicated via ownership-``psum``. Every
    per-client value is sharding-independent by construction (same fold_in
    stream per id, slot assembly adds exact zeros, the tree top-k preserves
    dense tie-breaks); what remains between this and ``run_simulation`` of
    the same config on one device is compiler instruction selection — XLA
    contracts mul+add chains to FMA differently for differently-shaped
    programs — so discrete decisions (scheduled counts, masks, availability)
    agree exactly and continuous histories to a few ulps
    (``tests/test_control_sharded.py`` pins both). ``group_size`` tunes the
    top-k tree fan-in (None = auto).

    The scan carry stays O(model + N/D) per device; the λ simplex projection
    is the psum-bisection :func:`project_simplex_sharded` (O(N/D + iters)
    per device) and the λ history is strided/elidable via
    ``FLConfig.record_lambda_every``, so no O(N) array lands on any single
    device during a round — only the host-side [T, N] stitch of the λ
    history output remains at ``record_lambda_every=1``.
    """
    fn, point, sharded_data = build_control_sharded_runner(
        model, fl, data, mesh, group_size=group_size)
    seed = fl.seed if seed is None else seed
    return fn(point, jax.random.PRNGKey(seed), *sharded_data)


def build_control_sharded_runner(model, fl, data, mesh: Mesh,
                                 group_size: Optional[int] = None):
    """Assemble the sharded-control-plane executable without running it.

    Returns ``(fn, point, sharded_data)`` where
    ``fn(point, key, *sharded_data) -> SimHistory`` is the jitted T-round
    scan of ``run_simulation_control_sharded``. Split out so callers that
    need the compiled artifact itself — ``benchmarks/popscale_bench.py``
    queries ``fn.lower(...).compile().memory_analysis()`` for the O(N/D)
    per-device-memory ceiling — share one definition with the public runner.
    """
    from repro.core.sweep import sweep_point_from_config

    axis = mesh.axis_names[0]
    n_dev = mesh.size
    if fl.control_plane != "sharded":
        raise ValueError(
            "run_simulation_control_sharded needs control_plane='sharded' "
            f"(got {fl.control_plane!r}); the replicated discipline shards "
            "via run_simulation_sharded")
    if fl.num_clients % n_dev:
        raise ValueError(
            f"population sharding needs N % devices == 0, got "
            f"N={fl.num_clients}, devices={n_dev} "
            "(pick a count via population_device_count)")
    n_local = fl.num_clients // n_dev
    point = sweep_point_from_config(fl)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    model_size = int(sum(int(np.prod(l.shape))
                         for l in jax.tree_util.tree_leaves(shapes)))

    run = control_sharded_cell_run(model, fl, fl.method, axis, n_local,
                                   model_size, group_size=group_size)
    shard_mapped = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis)),
        out_specs=control_sharded_history_specs(fl, axis), check_vma=False)
    sharded_data = tuple(shard_leading(jnp.asarray(d), mesh, axis)
                         for d in data)
    return jax.jit(shard_mapped), point, sharded_data


def control_sharded_cell_run(model, fl, method: str, axis_name,
                             n_local: int, model_size: int,
                             noise_free=None, group_size=None):
    """The shared per-cell body of the sharded control plane:
    ``run(point, key, x, y, x_test, y_test) -> SimHistory`` over THIS
    device's client rows, with the state initialized inside (λ/ChanState
    born local, ids = this shard's global client ids).

    One definition serves both meshes (ISSUE 8): the 1-D clients mesh of
    :func:`build_control_sharded_runner` wraps it in ``shard_map`` directly,
    and the sweep engine's 2-D ``cells × clients`` group runner ``vmap``s it
    over stacked points × seeds inside the donated per-group jit —
    collectives on the clients axis vmap over the cells batch unchanged.
    ``axis_name=None`` builds the unsharded reference program of the same
    discipline. The strided λ snapshot buffer (``record_lambda_every > 1``)
    rides the scan carry and is attached as ``hist.lam`` on the way out.
    """
    from repro.core.simulator import (init_sim_state,
                                      make_control_sharded_round_fn)

    def run(point, key, x, y, x_test, y_test):
        ids = (global_client_ids(axis_name, n_local)
               if axis_name is not None
               else jnp.arange(n_local, dtype=jnp.int32))
        state = init_sim_state(model, fl, key, process=point.process,
                               ids=ids)
        round_fn = make_control_sharded_round_fn(
            model, fl, (x, y, x_test, y_test), model_size, method,
            noise_free=noise_free, axis_name=axis_name,
            topk_group_size=group_size)
        final, hist = jax.lax.scan(
            lambda s, t: round_fn(point, s, t), state, jnp.arange(fl.rounds))
        if fl.record_lambda_every > 1:
            hist = hist._replace(lam=final.lam_snaps)
        return hist

    return run


def control_sharded_history_specs(fl, axis: str, lead: Sequence = ()):
    """``shard_map`` out_specs for a sharded-control-plane ``SimHistory``:
    every leaf is a replicated scalar-per-round except λ, whose rows live
    sharded on their LAST axis and stitch back to global client order
    (``[T, N]`` dense at ``record_lambda_every=1``, ``[ceil(T/E), N]``
    strided at E > 1, the leaf-less ``()`` at E = 0 — the spec on an empty
    subtree is inert). ``lead`` prefixes batch axes (the sweep group
    runner's ``[points, seeds]`` leading dims)."""
    from repro.core.simulator import SimHistory

    rep = P(*lead)
    lam = rep if fl.record_lambda_every == 0 else P(*lead, None, axis)
    return SimHistory(
        avg_acc=rep, worst_acc=rep, std_acc=rep, energy=rep, loss=rep,
        num_scheduled=rep, lam=lam, avail_count=rep, min_battery=rep,
        lam_max=rep, lam_entropy=rep, lam_ess=rep, dl_energy=rep)


def pad_to_multiple(values: Sequence[int], multiple: int) -> list[int]:
    """Pad a seed list so its length divides the cells mesh evenly; padding
    reuses existing entries (the padded columns are computed and discarded).

    An empty ``values`` used to crash with ZeroDivisionError deep in the
    modulo; a non-positive ``multiple`` would pad garbage. Both are caller
    bugs — reject them with actionable errors.
    """
    if not isinstance(multiple, (int, np.integer)) or \
            isinstance(multiple, bool) or multiple < 1:
        raise ValueError(f"multiple must be a positive int, got {multiple!r}")
    values = list(values)
    if not values:
        raise ValueError(
            "pad_to_multiple needs at least one value to pad from "
            "(got an empty sequence)")
    pad = (-len(values)) % multiple
    return values + [values[i % len(values)] for i in range(pad)]
