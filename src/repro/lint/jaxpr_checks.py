"""Layer-2 program analyzers: trace the COMPILED programs and assert
primitive-level invariants the AST rules cannot see.

``jax.make_jaxpr`` traces the real round/sweep/projection programs on a
size-1 ``clients`` mesh (collective primitives appear in the jaxpr with
group size 1, so the census is mesh-size-independent) and the checks walk
every nested jaxpr (pjit/scan/cond/shard_map bodies):

  - **sharded round collective census** — for every exact-K method ×
    transport, the sharded-control-plane round contains ZERO ``sort``
    primitives and every ``all_gather`` operand is K-bounded (the
    hierarchical top-k's ≤ ``clients_per_round`` candidate vectors — never
    an O(n_local) row block). GCA is the documented dense exception (its
    population-wide median threshold sorts). ``psum`` counts are pinned per
    (method, transport) so a new hidden collective fails loudly.
  - **λ-projection psum budget** — ``project_simplex_sharded`` spends
    exactly 1 psum per bisection iteration (inside the loop body) plus
    1 pmax + 2 polish psums outside.
  - **negative control** — the replicated round DOES contain a ``sort``
    (``dro.project_simplex``), proving the census sees sorts at all.
  - **donation** — the sweep runner's lowered StableHLO carries
    input→output aliasing for the donated state stack.
  - **compile count** — ``run_sweep`` compiles once per structural group
    (traced-knob-only spec changes reuse the executable).

Traces compile nothing (abstract evaluation only); the full pass is a
benchmark cell (``cells.lint``) with a <60 s ceiling.
"""
from __future__ import annotations

import functools
from collections import Counter
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import FLConfig
from repro.core import sharding
from repro.core.sweep import sweep_point_from_config

# Tiny trace harness: big enough that n_local (8) strictly exceeds K (3), so
# an O(n_local) all_gather operand is distinguishable from a K-bounded one.
N, K, DIM, ROUNDS, BATCH = 8, 3, 8, 2, 4
AXIS = "clients"
EXACT_K_METHODS = ("fedavg", "afl", "ca_afl", "greedy")
METHODS = EXACT_K_METHODS + ("gca",)
TRANSPORTS = ("analog", "quantized", "digital", "sparse")

# Pinned collective budgets of the sharded round, per (method, transport):
# psum count in the fully-traced T-round program (loop bodies counted once).
# Derived from the real programs; a drift in either direction is a contract
# change that must be reviewed (a new hidden collective, or a lost one).
# Exact-K methods share one budget regardless of transport (aggregation rides
# the same psum-tree shape) EXCEPT sparse, whose one extra psum is the
# ownership assembly of the winners' error-feedback residual rows
# (``slot_vals(state.ef_resid, sel_idx)``); GCA's dense path differs per
# transport (sparse matches quantized: the fused partial-sum replaces the
# per-leaf aggregation psums).
PINNED_PSUMS: dict[tuple[str, str], int] = {
    **{(m, t): 14 for m in EXACT_K_METHODS for t in TRANSPORTS},
    **{(m, "sparse"): 15 for m in EXACT_K_METHODS},
    ("gca", "analog"): 11,
    ("gca", "quantized"): 10,
    ("gca", "digital"): 11,
    ("gca", "sparse"): 10,
}


def _fl(method: str, transport: str = "analog", temporal: bool = False,
        control_plane: str = "sharded") -> FLConfig:
    return FLConfig(num_clients=N, clients_per_round=K, rounds=ROUNDS,
                    batch_size=BATCH, method=method, transport=transport,
                    temporal=temporal, control_plane=control_plane)


@functools.lru_cache(maxsize=1)
def _setup():
    from repro.data.synthetic import make_fmnist_like
    from repro.federated.partition import sorted_label_shards
    from repro.models.logreg import logistic_regression
    from repro.utils.tree import tree_size

    model = logistic_regression(dim=DIM, num_classes=10)
    x, y, xt, yt = make_fmnist_like(num_train=80, num_test=40, dim=DIM,
                                    seed=0)
    xs, ys = sorted_label_shards(x, y, N)
    xts, yts = sorted_label_shards(xt, yt, N)
    model_size = tree_size(model.init(jax.random.PRNGKey(0)))
    mesh = Mesh(np.array(jax.devices()[:1]), (AXIS,))
    return model, (xs, ys, xts, yts), model_size, mesh


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _as_jaxprs(v):
    if hasattr(v, "eqns"):                                   # Jaxpr
        yield v
    elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):   # ClosedJaxpr
        yield v.jaxpr
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _as_jaxprs(item)


def iter_eqns(jaxpr):
    """Every equation in ``jaxpr`` and all nested jaxprs (pjit / scan /
    cond branches / while bodies / shard_map / custom_jvp ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                yield from iter_eqns(sub)


def primitive_census(closed) -> Counter:
    """Counter of primitive names over the whole (nested) program."""
    return Counter(e.primitive.name for e in iter_eqns(closed.jaxpr))


def all_gather_operand_sizes(closed) -> list[int]:
    """Element count of every ``all_gather`` operand in the program."""
    return [int(np.prod(v.aval.shape) or 1)
            for e in iter_eqns(closed.jaxpr)
            if e.primitive.name == "all_gather"
            for v in e.invars]


# ---------------------------------------------------------------------------
# Traced programs
# ---------------------------------------------------------------------------


def trace_sharded_round(method: str, transport: str = "analog",
                        temporal: bool = False):
    """Jaxpr of the full sharded-control-plane cell (T-round scan) on a
    size-1 clients mesh — the same ``control_sharded_cell_run`` body both
    the 1-D runner and the 2-D sweep mesh execute."""
    model, data, model_size, mesh = _setup()
    fl = _fl(method, transport, temporal)
    point = sweep_point_from_config(fl)
    run = sharding.control_sharded_cell_run(
        model, fl, method, AXIS, N, model_size)
    mapped = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
        out_specs=sharding.control_sharded_history_specs(fl, AXIS),
        check_vma=False)
    return jax.make_jaxpr(mapped)(point, jax.random.PRNGKey(0), *data)


def trace_replicated_round(method: str = "ca_afl",
                           transport: str = "analog"):
    """Jaxpr of one replicated-discipline round (single device) — the
    negative control: it sorts (``dro.project_simplex``)."""
    from repro.core.simulator import init_sim_state, make_param_round_fn

    model, data, model_size, _ = _setup()
    fl = _fl(method, transport, control_plane="replicated")
    point = sweep_point_from_config(fl)
    state = init_sim_state(model, fl, jax.random.PRNGKey(0),
                           process=point.process)
    round_fn = make_param_round_fn(model, fl, data, model_size, method)
    return jax.make_jaxpr(
        lambda p, s, t: round_fn(p, s, t))(point, state, jnp.int32(0))


def trace_projection():
    """Jaxpr of ``project_simplex_sharded`` alone on the size-1 mesh."""
    _, _, _, mesh = _setup()
    mapped = jax.shard_map(
        lambda v: sharding.project_simplex_sharded(v, AXIS), mesh=mesh,
        in_specs=(P(AXIS),), out_specs=P(AXIS), check_vma=False)
    return jax.make_jaxpr(mapped)(jnp.ones((N,), jnp.float32))


# ---------------------------------------------------------------------------
# Checks — each returns (ok, detail)
# ---------------------------------------------------------------------------


def check_sharded_round_collectives():
    """Exact-K sharded rounds: zero sorts, K-bounded gathers, pinned psums."""
    bad = []
    seen = {}
    for method in METHODS:
        for transport in TRANSPORTS:
            closed = trace_sharded_round(method, transport)
            census = primitive_census(closed)
            seen[(method, transport)] = census["psum"]
            if method in EXACT_K_METHODS:
                if census["sort"]:
                    bad.append(f"{method}/{transport}: {census['sort']} "
                               "sort primitive(s) in the sharded round")
                over = [s for s in all_gather_operand_sizes(closed) if s > K]
                if over:
                    bad.append(f"{method}/{transport}: all_gather operands "
                               f"{over} exceed the K={K} candidate bound "
                               "(an O(n_local) row block is being gathered)")
            pinned = PINNED_PSUMS.get((method, transport))
            if pinned is not None and census["psum"] != pinned:
                bad.append(f"{method}/{transport}: psum count "
                           f"{census['psum']} != pinned {pinned}")
    if bad:
        return False, "; ".join(bad)
    table = {f"{m}/{t}": c for (m, t), c in sorted(seen.items())}
    return True, (f"{len(seen)} method×transport programs sort-free "
                  f"(exact-K), gathers K-bounded; psums {table}")


def check_projection_psum_budget():
    """1 psum per bisection iteration, pmax + 2 polish psums outside."""
    closed = trace_projection()
    census = primitive_census(closed)
    if census["pmax"] != 1:
        return False, f"expected 1 pmax, got {census['pmax']}"
    if census["psum"] != 3:
        return False, (f"expected 3 psums total (1 loop + 2 polish), got "
                       f"{census['psum']}")
    loop_bodies = []
    for e in iter_eqns(closed.jaxpr):
        if e.primitive.name in ("scan", "while"):
            for v in e.params.values():
                loop_bodies.extend(_as_jaxprs(v))
    if not loop_bodies:
        return False, "no bisection loop found in the projection jaxpr"
    in_loop = sum(Counter(ee.primitive.name for ee in iter_eqns(b))["psum"]
                  for b in loop_bodies)
    if in_loop != 1:
        return False, (f"expected exactly 1 psum inside the bisection loop "
                       f"body, got {in_loop}")
    return True, "1 psum/iteration + pmax + 2 polish psums"


def check_replicated_negative_control():
    """The replicated round must contain a sort — proves the census works."""
    census = primitive_census(trace_replicated_round("ca_afl"))
    if not census["sort"]:
        return False, ("replicated round shows zero sorts — the census is "
                       "not seeing sort primitives (analyzer broken)")
    return True, (f"replicated round has {census['sort']} sort(s) "
                  "(dro.project_simplex), sharded has none")


def check_sweep_donation():
    """The sweep runner's lowered program aliases the donated state stack."""
    from repro.core import sweep as sweep_mod

    model, data, model_size, _ = _setup()
    fl = _fl("fedavg", control_plane="replicated")
    init_fn, runner = sweep_mod._build_runner(
        model, fl, "fedavg", noise_free=True, model_size=model_size)
    points = sweep_mod._stack_points([sweep_point_from_config(fl)])
    seeds = jnp.asarray([0], jnp.int32)
    states = init_fn(points, seeds)
    text = runner.lower(points, states, data).as_text()
    if "tf.aliasing_output" not in text and "jax.buffer_donor" not in text:
        return False, ("no input->output aliasing marker in the sweep "
                       "runner's StableHLO — donate_argnums lost")
    return True, "donated state stack aliased in StableHLO"


def check_compile_count():
    """run_sweep: one compile per method × structural point, not per spec."""
    from repro.core.sweep import reset_trace_log, run_sweep, trace_count

    model, data, _, _ = _setup()
    fl_a = _fl("fedavg", control_plane="replicated")
    specs = [
        ("a", fl_a),
        ("b", replace(fl_a, lr0=0.3)),       # traced knob: same group as a
        ("c", _fl("afl", control_plane="replicated")),  # new structural group
    ]
    reset_trace_log()
    run_sweep(model, data, specs, seeds=(0,))
    n = trace_count()
    if n != 2:
        return False, (f"3 specs / 2 structural groups compiled {n} "
                       "executables (expected 2) — the structural grouping "
                       "regressed")
    return True, "3 specs, 2 structural groups, 2 compiles"


ALL_CHECKS = (
    ("sharded-round-collectives", check_sharded_round_collectives),
    ("projection-psum-budget", check_projection_psum_budget),
    ("replicated-negative-control", check_replicated_negative_control),
    ("sweep-donation", check_sweep_donation),
    ("compile-count", check_compile_count),
)


def run_all() -> list[tuple[str, bool, str]]:
    """Run every jaxpr check; never raises — failures are (name, False, …)."""
    results = []
    for name, fn in ALL_CHECKS:
        try:
            ok, detail = fn()
        except Exception as e:  # noqa: BLE001 — a crashed check is a failure
            ok, detail = False, f"check crashed: {type(e).__name__}: {e}"
        results.append((name, ok, detail))
    return results
