"""Fully-jitted FL simulator at the paper's native scale (Algorithm 1).

The entire T-round run is a single ``lax.scan``; the per-round body is
factored as ``round_fn(point, state, t)`` where ``point`` is a
:class:`repro.core.sweep.SweepPoint` pytree of *traced* knobs (learning
rates, energy_C, GCA params, channel scenario). ``run_simulation`` binds one
point and scans; the sweep engine (``repro.core.sweep``) instead ``vmap``s
the same body over a whole stacked grid of points × seeds under a single
compilation.

Hot-path contract (see ROADMAP): per-round *model-sized* work scales with
the scheduled set K, not the population N. For exact-K selection methods
(``selection.EXACT_K_METHODS``) the round is gather-compute-scatter:

  1. selection returns the ``lax.top_k`` *indices* [K] alongside the mask
     (``select_clients_sparse``) — availability/battery-gated slots keep
     their index but carry weight 0, so variable-K rounds stay one
     static-shape program;
  2. the K selected clients' batches are gathered and ``local_update`` runs
     on a [K, ...] stack — the [N, model] weight stack is never built;
  3. eq. (10) is one fused pass over the raveled [K, P] flat buffer
     (``aircomp.aircomp_aggregate_stack_tree``: Pallas on TPU, fused jnp
     elsewhere), and the ascent-side losses are evaluated only at the
     ascent + descent slots and scattered back to [N].

GCA's thresholded scheduled count is unbounded by K, so it stays on the
dense [N, model] path — which is also kept (``dense=True``) as the reference
implementation the differential tests pin the sparse path against.

The uplink transport (``repro.core.transport``) is a structural axis of the
round: ``fl.transport`` selects the aggregation + energy program (analog
AirComp / quantized AirComp / digital OFDMA) while every scheme knob rides
traced in ``point.transport`` — the analog program is the pre-transport one
bit-for-bit. The full
N-client test-set eval runs every ``fl.eval_every`` rounds (structural knob;
metrics forward-fill in between). All key consumption is identical across
the sparse/dense/GCA paths, so masks, channels, λ and energy agree
bit-for-bit and model trajectories agree to summation-order.

Faithfulness notes:
  - Descent (Alg. 1 lines 3-9): K clients sampled from ρ^(t) (eq. 9) w/o
    replacement (Gumbel-top-K == the sequential renormalized sampling of
    Prop. 2's analysis); each runs `local_steps` SGD steps with the
    exponentially-decayed η; the PS aggregates over the air (eq. 10).
  - Ascent (lines 10-15): K clients sampled uniformly; scalar losses of the
    *new* global model update λ via γ-ascent + simplex projection.
  - Energy (eqs. 3-6): channel-inversion energy of the selected set only;
    the ascent scalars ride the control channel (no energy charged), as in
    the paper.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.core.aircomp import (aircomp_aggregate_stack_tree,
                                aircomp_aggregate_tree, aircomp_psum_tree)
from repro.core.channel import (client_keys, draw_channels_scenario,
                                draw_channels_scenario_ids, effective_channel)
from repro.core.dro import lambda_ascent, lambda_summary
from repro.core.dynamics import (commit_process, init_chan_state,
                                 init_chan_state_ids, process_from_config,
                                 step_process)
from repro.core.selection import (EXACT_K_METHODS, availability_logits,
                                  client_gumbel, exact_k_scores, gumbel_topk,
                                  select_clients, select_clients_pop,
                                  select_clients_sparse)
from repro.core.sharding import (all_gather_axis, assemble_batch_rows,
                                 assemble_rows, hierarchical_top_k,
                                 local_slice, project_simplex_sharded)
from repro.core import transport as transport_mod
from repro.core.transport import (TRANSPORTS, quantized_aggregate_psum_tree,
                                  quantized_aggregate_stack_tree,
                                  sparse_aggregate_psum_tree,
                                  sparse_aggregate_stack_tree, sparse_k_coords)
from repro.models.logreg import SimModel
from repro.utils.tree import tree_size


class SimState(NamedTuple):
    w: object          # global model pytree
    lam: jnp.ndarray   # [N] simplex weights
    energy: jnp.ndarray  # cumulative Joules
    key: jnp.ndarray
    # ChanState for temporal scenarios (core/dynamics.py); the empty tuple
    # for static scenarios — a leaf-less slot, so the i.i.d. program (and the
    # scan carry XLA sees) is exactly PR 1's.
    chan_state: Any = ()
    # [3] last computed (avg, worst, std) test accuracy when eval_every > 1
    # (forward-filled between evals); the leaf-less () when eval_every == 1,
    # so the per-round-eval program is carried unchanged.
    eval_cache: Any = ()
    # [ceil(T/E), n_rows] strided λ snapshot buffer when
    # record_lambda_every = E > 1 (lax.scan cannot emit strided stacked
    # outputs, so the snapshots ride the carry and the runner attaches the
    # final buffer as SimHistory.lam); the leaf-less () at E in {0, 1}, so
    # the dense-recording program is carried unchanged.
    lam_snaps: Any = ()
    # [n_rows, P] per-client error-feedback residual memory of the sparse
    # transport (transport="sparse" only; the leaf-less () otherwise, so the
    # analog/quantized/digital programs are carried unchanged). Rows are
    # indexed by client id — LOCAL rows under population sharding, per the
    # ChanState new-carry-leaf rule (core/dynamics.py).
    ef_resid: Any = ()
    # scalar cumulative downlink Joules (the broadcast share of `energy`);
    # exactly zero at the default dl_rx_power = 0
    dl_energy: Any = ()


class SimHistory(NamedTuple):
    avg_acc: jnp.ndarray    # [T]
    worst_acc: jnp.ndarray  # [T]
    std_acc: jnp.ndarray    # [T]
    energy: jnp.ndarray     # [T] cumulative
    loss: jnp.ndarray       # [T] mean train loss of selected set
    num_scheduled: jnp.ndarray  # [T]
    # λ history on the record_lambda_every cadence: [T, N] dense at E=1
    # (today's per-round rows, bit-for-bit), [ceil(T/E), N] snapshots of
    # rounds t % E == 0 at E > 1, the leaf-less () at E=0
    lam: Any
    avail_count: jnp.ndarray  # [T] schedulable clients (avail ∧ battery-ok)
    min_battery: jnp.ndarray  # [T] min remaining Joules (inf when static)
    # always-on O(T) λ diagnostics (dro.lambda_summary — psum-of-local-rows
    # under the sharded control plane): max weight, Shannon entropy, and the
    # effective support size 1/Σλ² (participation ratio)
    lam_max: jnp.ndarray      # [T]
    lam_entropy: jnp.ndarray  # [T]
    lam_ess: jnp.ndarray      # [T]
    # [T] cumulative downlink Joules — the broadcast share of `energy`
    # (which is now uplink + downlink). Additive column: exactly zero at the
    # default dl_rx_power = 0, so pre-downlink trajectories are untouched.
    dl_energy: jnp.ndarray = jnp.float32(0.0)


def _record_lambda(fl: FLConfig, state: SimState, lam_new, t):
    """The λ recording step of a round body: ``(lam history leaf, lam_snaps
    carry)`` under the STRUCTURAL ``fl.record_lambda_every`` cadence.

    E=1 emits the full row as a per-round scan output (the dense [T, N]
    history, today's program bit-for-bit, with an untouched () carry slot);
    E>1 emits a leaf-less () and instead writes row ``t // E`` of the
    fixed-size carry buffer on rounds t % E == 0 (``lax.cond`` +
    ``dynamic_update_slice``, so the buffer is updated in place under the
    scan's donation); E=0 records nothing at all.
    """
    e = fl.record_lambda_every
    if e == 1:
        return lam_new, state.lam_snaps
    if e == 0:
        return (), state.lam_snaps
    snaps = jax.lax.cond(
        t % e == 0,
        lambda buf: jax.lax.dynamic_update_slice_in_dim(
            buf, lam_new[None].astype(buf.dtype), t // e, axis=0),
        lambda buf: buf,
        state.lam_snaps)
    return (), snaps


def _batch_indices(key, n, shard_size, batch_size):
    """The [N, B] in-shard sample indices — the ONLY randomness of batch
    sampling, drawn for all N clients on every path (it is O(N·B) int32s)
    so sparse and dense rounds consume ``k_batch`` identically."""
    return jax.random.randint(key, (n, batch_size), 0, shard_size)


def _sample_batches(key, x, y, batch_size):
    """Sample one batch per client from stacked shards [N, S, ...]."""
    n, s = y.shape
    idx = _batch_indices(key, n, s, batch_size)
    xb = jax.vmap(lambda xc, ic: xc[ic])(x, idx)
    yb = jax.vmap(lambda yc, ic: yc[ic])(y, idx)
    return xb, yb


def _needs_two_stage_gather(n: int, s: int) -> bool:
    """True when the composed flat index ``cidx * s + bidx`` (max N·S - 1)
    no longer fits int32 — the static dispatch predicate of
    :func:`_gather_batches`, decided from shapes at trace time."""
    return n * s - 1 > jnp.iinfo(jnp.int32).max


def _gather_batches(x, y, cidx, bidx, two_stage: bool | None = None):
    """Batches of the selected clients only: [K, B, ...].

    ``cidx`` [K] client indices; ``bidx`` [K, B] in-shard sample indices
    (the selected rows of :func:`_batch_indices`' draw). Composed into one
    flat gather so no [K, shard] intermediate is materialized.

    The composed flat index ``cidx * S + bidx`` needs log2(N·S) bits: at
    population scale (N·S > 2^31, e.g. 2^26 clients × 64-sample shards) the
    int32 arithmetic silently wraps negative and gathers garbage rows. Since
    int64 indices need the x64 mode the rest of the engine does not run
    under, such populations take a two-stage per-client gather instead
    (client row, then in-shard take) — the [K, S, ...] intermediate it may
    materialize is small exactly in the huge-N/modest-S regime that
    overflows. ``two_stage`` forces the choice (tests pin path equality);
    the default decides statically from the shapes.
    """
    n, s = y.shape
    if two_stage is None:
        two_stage = _needs_two_stage_gather(n, s)
    if two_stage:
        xb = jax.vmap(lambda c, b: jnp.asarray(x)[c][b])(cidx, bidx)
        yb = jax.vmap(lambda c, b: jnp.asarray(y)[c][b])(cidx, bidx)
        return xb, yb
    flat = cidx[:, None] * s + bidx                       # [K, B]
    xb = jnp.reshape(jnp.asarray(x), (n * s,) + x.shape[2:])[flat]
    yb = jnp.reshape(jnp.asarray(y), (n * s,))[flat]
    return xb, yb


def make_param_round_fn(model: SimModel, fl: FLConfig, data, model_size: int,
                        method: str, noise_free: bool | None = None,
                        dense: bool = False, axis_name: str | None = None):
    """Build ``round_fn(point, state, t)``.

    Everything structural (N, K, T, batch/local-step counts, subcarriers,
    flat-vs-selective fading, selection *method*, ``eval_every``) comes
    statically from ``fl``/``method``; every scalar knob that may ride a
    sweep axis comes traced from ``point`` (see ``repro.core.sweep``).

    ``dense=True`` forces the [N, model] reference path for exact-K methods
    (GCA always uses it) — the oracle the sparse gather path is pinned
    against by ``tests/test_hotpath.py``.

    ``noise_free=True`` statically elides the receiver-noise draw of eq. (10)
    (adding z with std 0 is the identity, but the Gaussian sample itself is
    model-sized work per round). The sweep engine sets it when *every* point
    in a compilation group has ``noise_std == 0``; a traced ``noise_std``
    stays live otherwise.

    ``axis_name`` (population sharding, ``core/sharding.py``): the round body
    runs inside a ``shard_map`` over a clients mesh axis of that name, and
    ``data`` holds THIS shard's client rows while ``fl.num_clients`` stays
    the global N. The control plane (channels, selection scores, λ, energy,
    availability, batch indices) is drawn replicated at full [N] exactly as
    in the unsharded program — bit-identical O(N) scalars — while the
    model-sized per-client work (local SGD stacks, gradients, losses, the
    test eval) runs on the local rows and eq. (10) becomes a local weighted
    partial-sum + ``psum`` (``aircomp.aircomp_psum_tree``). Dense/GCA rounds
    only: the selected-K gather path stays single-device.
    """
    if fl.control_plane == "sharded":
        if dense:
            raise ValueError(
                "control_plane='sharded' has a single per-method program "
                "(the slot path IS the reference); dense=True selects the "
                "replicated-discipline [N, model] path only")
        return make_control_sharded_round_fn(
            model, fl, data, model_size, method, noise_free=noise_free,
            axis_name=axis_name)
    if fl.control_plane != "replicated":
        raise ValueError(
            f"unknown control_plane {fl.control_plane!r}; "
            "pick 'replicated' or 'sharded'")
    x, y, x_test, y_test = data
    n = fl.num_clients
    shard = y.shape[1]
    if noise_free is None:
        noise_free = fl.noise_std == 0
    pop = axis_name is not None
    sparse = (method in EXACT_K_METHODS) and not dense
    # the uplink transport scheme is STRUCTURAL (Python branches below):
    # "analog" compiles to exactly the pre-transport program, "quantized"
    # swaps the aggregation for the fused quantize-aggregate pass over
    # per-client deltas, "digital" statically elides the superposition noise
    # (orthogonal decode) — every scheme KNOB stays traced in point.transport
    scheme = fl.transport
    if scheme not in TRANSPORTS:
        raise ValueError(
            f"unknown transport {scheme!r}; pick one of {TRANSPORTS}")
    if pop and sparse:
        raise ValueError(
            "population sharding runs the dense [N, model] reference "
            "program; build with dense=True (the selected-K gather path "
            "stays single-device)")
    n_local = y.shape[0]  # == n unless population-sharded
    grad_fn = jax.grad(model.loss)
    vloss = jax.vmap(model.loss, in_axes=(None, 0, 0))
    vacc = jax.vmap(model.accuracy, in_axes=(None, 0, 0))
    vgrad_clients = jax.vmap(grad_fn, in_axes=(None, 0, 0))

    def local_update(w, eta, xb, yb):
        """`local_steps` SGD steps from the global model (one client)."""

        def body(wc, _):
            g = grad_fn(wc, xb, yb)
            return jax.tree.map(lambda p, gg: p - eta * gg, wc, g), None

        wc, _ = jax.lax.scan(body, w, None, length=fl.local_steps)
        return wc

    def local_update_rest(w1, eta, xb, yb):
        """Steps 2..local_steps when step 1's gradient was precomputed."""

        def body(wc, _):
            g = grad_fn(wc, xb, yb)
            return jax.tree.map(lambda p, gg: p - eta * gg, wc, g), None

        wc, _ = jax.lax.scan(body, w1, None, length=fl.local_steps - 1)
        return wc

    temporal = fl.temporal
    # sparse transport: the kept-coordinate count is STATIC (it bakes the
    # compiled top-k width — fl.sparse_density joins STATIC_FIELDS)
    k_coords = (sparse_k_coords(fl.sparse_density, model_size)
                if scheme == "sparse" else None)

    def aggregate_full(tpt, w_prev, w_stack, mask, mask_l, k_noise,
                       noise_std, k_denom, ef_resid):
        """Transport-dispatched eq. (10) over a full [n(_local), model]
        update stack (the dense/GCA and population-sharded paths); returns
        ``(w_new, ef_resid')``. Analog compiles to exactly the
        pre-transport per-leaf/psum calls; digital statically drops the
        AWGN (orthogonal decode); quantized aggregates stochastically-
        rounded per-client deltas, with global client ids addressing the
        rounding streams so sharded rows quantize identically to dense
        ones; sparse top-k-compresses delta + residual per client and
        carries the dropped mass forward (``ef_resid`` rows are LOCAL under
        population sharding — each device updates only its own clients'
        memory). Non-sparse schemes pass the (leaf-less) residual through
        untouched."""
        if scheme == "quantized":
            if pop:
                ids = (jax.lax.axis_index(axis_name) * n_local
                       + jnp.arange(n_local))
                return quantized_aggregate_psum_tree(
                    w_prev, w_stack, mask_l, ids, k_noise, noise_std,
                    tpt.bits, k_denom, axis_name), ef_resid
            return quantized_aggregate_stack_tree(
                w_prev, w_stack, mask, jnp.arange(n), k_noise, noise_std,
                tpt.bits, k_denom), ef_resid
        if scheme == "sparse":
            if pop:
                return sparse_aggregate_psum_tree(
                    w_prev, w_stack, mask_l, k_noise, noise_std, k_coords,
                    k_denom, ef_resid, axis_name)
            return sparse_aggregate_stack_tree(
                w_prev, w_stack, mask, k_noise, noise_std, k_coords,
                k_denom, ef_resid)
        eff_noise = 0.0 if scheme == "digital" else noise_std
        if pop:
            return aircomp_psum_tree(w_stack, mask_l, k_noise, eff_noise,
                                     k_denom, axis_name), ef_resid
        return aircomp_aggregate_tree(w_stack, mask, k_noise, eff_noise,
                                      k_denom), ef_resid

    def sample_batches(key):
        """One batch per client — local rows [n_local, B, ...] under
        population sharding, the full [N, B, ...] otherwise. The [N, B]
        index draw is ALWAYS full-N and replicated (same key, same shape on
        every device), so sharded and unsharded programs consume ``k_batch``
        identically; only the gather is local."""
        if not pop:
            return _sample_batches(key, x, y, fl.batch_size)
        bidx = local_slice(_batch_indices(key, n, shard, fl.batch_size),
                           axis_name, n_local)
        xb = jax.vmap(lambda xc, ic: xc[ic])(x, bidx)
        yb = jax.vmap(lambda yc, ic: yc[ic])(y, bidx)
        return xb, yb

    def round_fn(point, state: SimState, t):
        key, k_chan, k_sel, k_batch, k_noise, k_asel, k_abatch = jax.random.split(state.key, 7)
        scen = point.scenario
        proc = point.process

        # ---- physical layer: block-fading channels (static: i.i.d. redraw;
        # temporal: Gauss-Markov/walk evolution of the chan_state carry).
        # step_process is shared with ParameterServer.step so the two tiers
        # evolve the identical process; battery gating means a client that
        # cannot afford THIS round's upload is excluded from selection, so
        # batteries deplete monotonically and never go negative.
        if temporal:
            cs = state.chan_state
            pstep = step_process(k_chan, scen, proc, cs, n,
                                 fl.num_subcarriers, model_size,
                                 scheme=scheme, tp=point.transport,
                                 dl_num_tx=fl.clients_per_round)
            h, avail, eligible = pstep.h, pstep.avail, pstep.eligible
        else:
            h = effective_channel(
                draw_channels_scenario(k_chan, scen, n, fl.num_subcarriers)
            )
            avail = eligible = None

        # ---- client selection (descent set D^(t))
        sel_idx = None
        if method == "gca":
            # ONE batch draw: the probe batch IS the descent batch by design
            # — GCA's gradient probe doubles as the first descent step, so
            # grads0 is reused as SGD step 1 below instead of being
            # recomputed inside local_update (the former double-work bug:
            # two identical _sample_batches(k_batch, ...) draws feeding two
            # identical per-client gradient computations).
            xb, yb = sample_batches(k_batch)
            grads0 = vgrad_clients(state.w, xb, yb)
            gnorms = jax.vmap(
                lambda g: jnp.sqrt(
                    sum(jnp.sum(jnp.square(l)) for l in jax.tree_util.tree_leaves(g))
                )
            )(grads0)
            if pop:
                # the per-client probe ran on local rows; GCA's threshold
                # statistics (mean/median) are population-wide, so gather
                # the O(N) norms back to the replicated control plane
                gnorms = all_gather_axis(gnorms, axis_name)
            mask = select_clients("gca", k_sel, state.lam, h, fl.clients_per_round,
                                  grad_norms=gnorms, gca=point.gca,
                                  avail=eligible)
        elif sparse:
            mask, sel_idx = select_clients_sparse(
                method, k_sel, state.lam, h, fl.clients_per_round,
                C=point.energy_C, avail=eligible)
        elif pop:
            # exact-K on the sharded population: local top-k per shard, then
            # a global top-k over the K·n_shards candidates — equal to the
            # dense lax.top_k by construction (ties pinned to lowest index)
            mask, _ = select_clients_pop(
                method, k_sel, state.lam, h, fl.clients_per_round, n_local,
                axis_name, C=point.energy_C, avail=eligible)
        else:
            mask = select_clients(method, k_sel, state.lam, h,
                                  fl.clients_per_round, C=point.energy_C,
                                  avail=eligible)
        # the actual scheduled count: == clients_per_round for exact-K
        # methods on static scenarios, variable for GCA and under
        # availability/battery gating. Always traced, so the static and the
        # degenerate-temporal programs do this arithmetic identically.
        k_denom = jnp.maximum(jnp.sum(mask), 1.0)

        # ---- local updates + AirComp aggregation (eq. 10)
        eta = point.lr0 * (point.lr_decay ** t)
        # lint: allow(structural-field): noise_free is an explicit structural arg; the fl.noise_std==0 default binds only single-config runs, and the sweep engine groups on all-noise-free explicitly (see run_sweep)
        noise_std = 0.0 if noise_free else scen.noise_std
        # under population sharding the update stacks are [n_local, model]
        # and eq. (10) is the local partial-sum + psum; the AWGN key/leaf
        # discipline is shared with the dense reference either way
        mask_l = local_slice(mask, axis_name, n_local) if pop else mask
        if method == "gca":
            # SGD step 1 reuses the probe gradients (same batch, same w)
            w1 = jax.vmap(
                lambda g: jax.tree.map(lambda p, gg: p - eta * gg, state.w, g)
            )(grads0)
            if fl.local_steps > 1:
                w_stack = jax.vmap(local_update_rest,
                                   in_axes=(0, None, 0, 0))(w1, eta, xb, yb)
            else:
                w_stack = w1
            w_new, ef_new = aggregate_full(point.transport, state.w, w_stack,
                                           mask, mask_l, k_noise, noise_std,
                                           k_denom, state.ef_resid)
        elif sparse:
            # gather-compute-scatter: only the K selected clients descend
            bidx = _batch_indices(k_batch, n, shard, fl.batch_size)
            xb_s, yb_s = _gather_batches(x, y, sel_idx, bidx[sel_idx])
            w_sel = jax.vmap(local_update,
                             in_axes=(None, None, 0, 0))(state.w, eta, xb_s, yb_s)
            sel_w = mask[sel_idx]  # 0 for availability/battery-gated slots
            ef_new = state.ef_resid
            if scheme == "quantized":
                # sel_idx addresses the rounding streams, so the K gathered
                # rows quantize bit-identically to the dense [N] program's
                w_new = quantized_aggregate_stack_tree(
                    state.w, w_sel, sel_w, sel_idx, k_noise, noise_std,
                    point.transport.bits, k_denom)
            elif scheme == "sparse":
                # the K winners' residual rows ride the same gather/scatter
                # as their batches: compression is a within-row threshold,
                # so the gathered rows compress bit-identically to dense;
                # gated slots (weight 0) keep their residual, and sel_idx
                # is a top-k output (unique), so the scatter-back is exact
                resid_sel = state.ef_resid[sel_idx]
                w_new, resid_new = sparse_aggregate_stack_tree(
                    state.w, w_sel, sel_w, k_noise, noise_std, k_coords,
                    k_denom, resid_sel)
                ef_new = state.ef_resid.at[sel_idx].set(resid_new)
            else:
                w_new = aircomp_aggregate_stack_tree(
                    w_sel, sel_w, k_noise,
                    0.0 if scheme == "digital" else noise_std, k_denom)
        else:
            xb, yb = sample_batches(k_batch)
            w_stack = jax.vmap(local_update,
                               in_axes=(None, None, 0, 0))(state.w, eta, xb, yb)
            w_new, ef_new = aggregate_full(point.transport, state.w, w_stack,
                                           mask, mask_l, k_noise, noise_std,
                                           k_denom, state.ef_resid)
        if temporal or method == "gca":
            # the scheduled set can be EMPTY (battery/availability gating, or
            # GCA's thresholding): the PS then receives nothing over the air
            # and must keep the current global model — not eq. (10)'s zero
            # sum. Exact-K static methods always transmit, so their program
            # stays untouched.
            any_sched = jnp.sum(mask) > 0
            w_new = jax.tree.map(
                lambda agg, old: jnp.where(any_sched, agg, old), w_new, state.w)

        # ---- energy ledger (only the selected set transmits, priced under
        # the round's uplink transport — analog is eqs. 3-6 verbatim; every
        # listening client pays the broadcast receive, exactly zero at the
        # default dl_rx_power = 0)
        e_round = transport_mod.round_energy(scheme, point.transport, h, mask,
                                             model_size, scen)
        recv_count = jnp.sum(pstep.recv) if temporal else jnp.float32(n)
        e_dl = recv_count * transport_mod.downlink_energy(
            scheme, point.transport, model_size, scen,
            num_tx=fl.clients_per_round)
        dl_energy = state.dl_energy + e_dl
        energy = state.energy + e_round + e_dl

        # ---- temporal carry: deplete batteries, persist the process state
        if temporal:
            chan_state = commit_process(pstep, cs, mask)
            avail_count = jnp.sum(eligible)
            min_battery = jnp.min(chan_state.battery)
        else:
            chan_state = state.chan_state
            avail_count = jnp.float32(n)
            min_battery = jnp.float32(jnp.inf)

        # ---- ascent step on lambda (uniform K of the AVAILABLE clients,
        # control channel — no transmit energy, so no battery gating)
        amask, asc_idx = gumbel_topk(
            k_asel, jnp.zeros((n,)) + availability_logits(avail),
            fl.clients_per_round)
        if temporal:
            amask = amask * avail
        if sparse:
            # loss forwards only where they are consumed: the ascent slots
            # (λ update) and the descent slots (selected-set loss metric),
            # scattered back to [N] — identical values to the dense path,
            # which evaluates all N and masks.
            abidx = _batch_indices(k_abatch, n, shard, fl.batch_size)
            xa, ya = _gather_batches(x, y, asc_idx, abidx[asc_idx])
            asc_losses = vloss(w_new, xa, ya)
            losses = jnp.zeros((n,), asc_losses.dtype).at[asc_idx].set(asc_losses)
            xd, yd = _gather_batches(x, y, sel_idx, abidx[sel_idx])
            sel_loss = jnp.sum(mask[sel_idx] * vloss(w_new, xd, yd)) / k_denom
        else:
            xab, yab = sample_batches(k_abatch)
            losses = vloss(w_new, xab, yab)
            if pop:
                # per-client losses computed on local rows; λ's ascent and
                # the selected-set loss metric live on the replicated [N]
                # control plane, so gather them back in client order
                losses = all_gather_axis(losses, axis_name)
            sel_loss = jnp.sum(mask * losses) / k_denom
        lam_new = lambda_ascent(state.lam, losses, amask, point.ascent_lr)
        lam_max, lam_entropy, lam_ess = lambda_summary(lam_new)
        lam_hist, lam_snaps = _record_lambda(fl, state, lam_new, t)

        # ---- metrics: the full N-client test-set eval runs on the
        # eval_every cadence (forward-filled in between); everything else is
        # O(N) scalars and stays per-round.
        def eval_accs():
            """Full test eval: per-client accuracy over the local rows (the
            sharded O(N·test) work), gathered to [N] for the stats."""
            accs = vacc(w_new, x_test, y_test)
            return all_gather_axis(accs, axis_name) if pop else accs

        if fl.eval_every == 1:
            accs = eval_accs()
            stats = jnp.stack([jnp.mean(accs), jnp.min(accs), jnp.std(accs)])
            eval_cache = state.eval_cache  # the leaf-less ()
        else:
            def fresh_eval(_):
                accs = eval_accs()
                return jnp.stack([jnp.mean(accs), jnp.min(accs),
                                  jnp.std(accs)])

            stats = jax.lax.cond(t % fl.eval_every == 0, fresh_eval,
                                 lambda _: state.eval_cache, None)
            eval_cache = stats
        metrics = SimHistory(
            avg_acc=stats[0],
            worst_acc=stats[1],
            std_acc=stats[2],
            energy=energy,
            loss=sel_loss,
            num_scheduled=jnp.sum(mask),
            lam=lam_hist,
            avail_count=avail_count,
            min_battery=min_battery,
            lam_max=lam_max,
            lam_entropy=lam_entropy,
            lam_ess=lam_ess,
            dl_energy=dl_energy,
        )
        return SimState(w_new, lam_new, energy, key, chan_state,
                        eval_cache, lam_snaps, ef_new, dl_energy), metrics

    return round_fn


def _batch_indices_ids(key, ids, shard_size, batch_size):
    """[n, B] in-shard sample indices, content-addressed per client id.

    Row c is ``randint(fold_in(key, ids[c]), ...)`` — a function of (key,
    id) only, so any device can (re)draw any client's batch indices. The
    control_plane="sharded" replacement for :func:`_batch_indices`'s full-[N]
    draw: a shard draws only its own rows, and the selected-K slot gathers
    re-draw just the K winners' rows from the same streams.
    """
    keys = client_keys(key, ids)
    return jax.vmap(
        lambda k: jax.random.randint(k, (batch_size,), 0, shard_size))(keys)


def make_control_sharded_round_fn(model: SimModel, fl: FLConfig, data,
                                  model_size: int, method: str,
                                  noise_free: bool | None = None,
                                  axis_name: str | None = None,
                                  topk_group_size: int | None = None):
    """Build ``round_fn(point, state, t)`` under the SHARDED control plane.

    The O(N)-replicated discipline of :func:`make_param_round_fn` draws every
    per-client random vector at full [N] on every device. Here each device
    materializes only its own ``n_local`` rows of channels, availability,
    selection scores, λ and batch indices, with every draw content-addressed
    by GLOBAL client id (``channel.client_keys`` / ``selection.client_gumbel``
    / :func:`_batch_indices_ids`) — so the unsharded program
    (``ids = arange(N)``) and the mesh-sharded one (``ids`` = this shard's
    rows) specify identical per-client values by construction. (The two
    compiled programs agree to compiler instruction selection: XLA's FMA
    contraction differs across program shapes, worth a few ulps on
    transcendental-adjacent values — integer draws and all discrete
    decisions built from them agree exactly.)

    Exact-K methods select via ``sharding.hierarchical_top_k`` (per-shard →
    group → global tree reduction, O(n_local + K·log D) per device) and run
    the gather-compute-scatter hot path with slot assembly: each winner's
    row/batch is owned by exactly one shard, contributed as
    ``where(owned, v, 0)`` and ``psum``-assembled — adding exact zeros, so
    slots are bit-identical to a single-device gather. Model-sized [K] work
    then runs replicated on every device (it is O(K·model), independent
    of N). GCA keeps its dense per-client probe on local rows, gathering only
    the O(N) norm/channel scalars for its population-wide threshold.

    ``state.lam`` is the LOCAL λ slice [n_local]; the simplex projection is
    the psum-bisection ``sharding.project_simplex_sharded`` (no gather, no
    sort) and the test-eval statistics are psum-of-local-rows, so the
    exact-K round contains NO O(N) collective at all — GCA's population-wide
    threshold statistics are the single documented exception. Machine-checked
    by ``repro.lint`` (AST gather-then-reduce rule + jaxpr primitive census).
    ``axis_name=None`` builds the unsharded reference program the
    differential tests pin the mesh program against.
    """
    x, y, x_test, y_test = data
    n = fl.num_clients
    shard = y.shape[1]
    if noise_free is None:
        noise_free = fl.noise_std == 0
    pop = axis_name is not None
    scheme = fl.transport
    if scheme not in TRANSPORTS:
        raise ValueError(
            f"unknown transport {scheme!r}; pick one of {TRANSPORTS}")
    if method != "gca" and method not in EXACT_K_METHODS:
        raise ValueError(f"unknown selection method {method!r}")
    n_rows = y.shape[0]  # == n unless mesh-sharded
    n_shards = n // n_rows if pop else 1
    kk = fl.clients_per_round
    # sparse transport: static kept-coordinate count (fl.sparse_density is
    # STRUCTURAL — it bakes the compiled top-k width)
    k_coords = (sparse_k_coords(fl.sparse_density, model_size)
                if scheme == "sparse" else None)
    grad_fn = jax.grad(model.loss)
    vloss = jax.vmap(model.loss, in_axes=(None, 0, 0))
    vacc = jax.vmap(model.accuracy, in_axes=(None, 0, 0))
    vgrad_clients = jax.vmap(grad_fn, in_axes=(None, 0, 0))
    temporal = fl.temporal

    def local_update(w, eta, xb, yb):
        def body(wc, _):
            g = grad_fn(wc, xb, yb)
            return jax.tree.map(lambda p, gg: p - eta * gg, wc, g), None

        wc, _ = jax.lax.scan(body, w, None, length=fl.local_steps)
        return wc

    def local_update_rest(w1, eta, xb, yb):
        def body(wc, _):
            g = grad_fn(wc, xb, yb)
            return jax.tree.map(lambda p, gg: p - eta * gg, wc, g), None

        wc, _ = jax.lax.scan(body, w1, None, length=fl.local_steps - 1)
        return wc

    def topk_idx(scores):
        """Global top-k indices [K] of a (sharded) score vector."""
        if pop:
            return hierarchical_top_k(scores, kk, axis_name, n_shards,
                                      group_size=topk_group_size)
        return jax.lax.top_k(scores, kk)[1]

    def slot_vals(vals, idx):
        """vals[idx] across shards: each index is owned by exactly one
        shard; psum of where(owned, v, 0) adds exact zeros — bit-identical
        to the single-device gather."""
        if pop:
            return assemble_rows(vals, idx, axis_name, n_rows)
        return vals[idx]

    def slot_batches(arr, idx, bidx):
        if pop:
            return assemble_batch_rows(arr, idx, bidx, axis_name, n_rows)
        return jax.vmap(lambda c, b: jnp.asarray(arr)[c][b])(idx, bidx)

    def round_fn(point, state: SimState, t):
        key, k_chan, k_sel, k_batch, k_noise, k_asel, k_abatch = jax.random.split(state.key, 7)
        scen = point.scenario
        proc = point.process
        off = jax.lax.axis_index(axis_name) * n_rows if pop else 0
        ids = off + jnp.arange(n_rows, dtype=jnp.int32)

        def scatter_slots(idx, wvals):
            """[K] slot values → local [n_rows] scatter (owned slots only)."""
            lidx = jnp.clip(idx - off, 0, n_rows - 1)
            owned = (idx >= off) & (idx < off + n_rows)
            return jnp.zeros((n_rows,), wvals.dtype).at[lidx].add(
                jnp.where(owned, wvals, jnp.zeros_like(wvals)))

        # ---- physical layer: per-id channel draws (only this shard's rows)
        if temporal:
            cs = state.chan_state
            pstep = step_process(k_chan, scen, proc, cs, n_rows,
                                 fl.num_subcarriers, model_size,
                                 scheme=scheme, tp=point.transport, ids=ids,
                                 dl_num_tx=kk)
            h, avail, eligible = pstep.h, pstep.avail, pstep.eligible
        else:
            h = effective_channel(
                draw_channels_scenario_ids(k_chan, scen, ids,
                                           fl.num_subcarriers))
            avail = eligible = None

        eta = point.lr0 * (point.lr_decay ** t)
        # lint: allow(structural-field): noise_free is an explicit structural arg; the fl.noise_std==0 default binds only single-config runs, and the sweep engine groups on all-noise-free explicitly (see run_sweep)
        noise_std = 0.0 if noise_free else scen.noise_std

        if method == "gca":
            # dense per-client probe on local rows; the probe batch IS the
            # descent batch (grads0 reused as SGD step 1, as in the
            # replicated program)
            bidx_all = _batch_indices_ids(k_batch, ids, shard, fl.batch_size)
            xb = jax.vmap(lambda xc, ic: xc[ic])(x, bidx_all)
            yb = jax.vmap(lambda yc, ic: yc[ic])(y, bidx_all)
            grads0 = vgrad_clients(state.w, xb, yb)
            gnorms = jax.vmap(
                lambda g: jnp.sqrt(
                    sum(jnp.sum(jnp.square(l))
                        for l in jax.tree_util.tree_leaves(g))
                )
            )(grads0)
            if pop:
                # GCA's threshold statistics (mean/median/max) are
                # population-wide: gather the O(N) control scalars — the
                # documented dense-path exception to the psum-of-local-rows
                # rule (the median has no psum form)
                # lint: allow(gather-then-reduce): GCA median/mean thresholds need the full [N] score vector
                gnorms_f = all_gather_axis(gnorms, axis_name)
                # lint: allow(gather-then-reduce): GCA median/mean thresholds need the full [N] score vector
                h_f = all_gather_axis(h, axis_name)
                # lint: allow(gather-then-reduce): GCA median/mean thresholds need the full [N] score vector
                elig_f = (all_gather_axis(eligible, axis_name)
                          if temporal else None)
            else:
                gnorms_f, h_f, elig_f = gnorms, h, eligible
            mask_f = select_clients("gca", k_sel, jnp.zeros_like(h_f), h_f,
                                    kk, grad_norms=gnorms_f, gca=point.gca,
                                    avail=elig_f)
            mask_l = local_slice(mask_f, axis_name, n_rows) if pop else mask_f
            num_sched = jnp.sum(mask_f)
            k_denom = jnp.maximum(num_sched, 1.0)

            w1 = jax.vmap(
                lambda g: jax.tree.map(lambda p, gg: p - eta * gg, state.w, g)
            )(grads0)
            if fl.local_steps > 1:
                w_stack = jax.vmap(local_update_rest,
                                   in_axes=(0, None, 0, 0))(w1, eta, xb, yb)
            else:
                w_stack = w1
            ef_new = state.ef_resid
            if scheme == "quantized":
                if pop:
                    w_new = quantized_aggregate_psum_tree(
                        state.w, w_stack, mask_l, ids, k_noise, noise_std,
                        point.transport.bits, k_denom, axis_name)
                else:
                    w_new = quantized_aggregate_stack_tree(
                        state.w, w_stack, mask_l, ids, k_noise, noise_std,
                        point.transport.bits, k_denom)
            elif scheme == "sparse":
                # residual rows stay shard-local: every device compresses
                # and updates only its own clients' memory
                if pop:
                    w_new, ef_new = sparse_aggregate_psum_tree(
                        state.w, w_stack, mask_l, k_noise, noise_std,
                        k_coords, k_denom, state.ef_resid, axis_name)
                else:
                    w_new, ef_new = sparse_aggregate_stack_tree(
                        state.w, w_stack, mask_l, k_noise, noise_std,
                        k_coords, k_denom, state.ef_resid)
            else:
                eff_noise = 0.0 if scheme == "digital" else noise_std
                if pop:
                    w_new = aircomp_psum_tree(w_stack, mask_l, k_noise,
                                              eff_noise, k_denom, axis_name)
                else:
                    w_new = aircomp_aggregate_tree(w_stack, mask_l, k_noise,
                                                   eff_noise, k_denom)
            # GCA can schedule nobody (thresholding / gating): keep w
            any_sched = num_sched > 0
            w_new = jax.tree.map(
                lambda agg, old: jnp.where(any_sched, agg, old),
                w_new, state.w)
            e_local = transport_mod.round_energy(
                scheme, point.transport, h, mask_l, model_size, scen)
            e_round = jax.lax.psum(e_local, axis_name) if pop else e_local
        else:
            # ---- exact-K: sharded scores → hierarchical top-k → slot path.
            # λ enters per-client (normalizer-free logits), so local lam
            # rows score identically to the dense program's.
            scores = exact_k_scores(method, k_sel, state.lam, h,
                                    C=point.energy_C, avail=eligible, ids=ids)
            sel_idx = topk_idx(scores)
            # availability/battery-gated slots keep their index, weight 0
            sel_w = (slot_vals(eligible, sel_idx) if temporal
                     else jnp.ones((kk,), jnp.float32))
            num_sched = jnp.sum(sel_w)
            k_denom = jnp.maximum(num_sched, 1.0)
            mask_l = scatter_slots(sel_idx, sel_w)

            bidx_sel = _batch_indices_ids(k_batch, sel_idx, shard,
                                          fl.batch_size)
            xb_s = slot_batches(x, sel_idx, bidx_sel)
            yb_s = slot_batches(y, sel_idx, bidx_sel)
            # O(K·model) work, replicated on every device — independent of N
            w_sel = jax.vmap(local_update,
                             in_axes=(None, None, 0, 0))(state.w, eta,
                                                         xb_s, yb_s)
            ef_new = state.ef_resid
            if scheme == "quantized":
                w_new = quantized_aggregate_stack_tree(
                    state.w, w_sel, sel_w, sel_idx, k_noise, noise_std,
                    point.transport.bits, k_denom)
            elif scheme == "sparse":
                # the winners' residual rows ride the same ownership-psum
                # slot assembly as their batches ([K, P] rows, exact
                # zeros), the [K]-slot compression runs replicated on
                # every device, and each shard scatters back only its
                # OWNED rows — duplicate-safe: non-owned clipped indices
                # contribute a zero hit, owned top-k indices are unique
                resid_sel = slot_vals(state.ef_resid, sel_idx)
                w_new, resid_new = sparse_aggregate_stack_tree(
                    state.w, w_sel, sel_w, k_noise, noise_std, k_coords,
                    k_denom, resid_sel)
                lidx = jnp.clip(sel_idx - off, 0, n_rows - 1)
                owned = (sel_idx >= off) & (sel_idx < off + n_rows)
                upd = jnp.zeros_like(state.ef_resid).at[lidx].add(
                    jnp.where(owned[:, None], resid_new,
                              jnp.zeros_like(resid_new)))
                hit = jnp.zeros((n_rows,), jnp.float32).at[lidx].add(
                    jnp.where(owned, 1.0, 0.0))
                ef_new = jnp.where(hit[:, None] > 0, upd, state.ef_resid)
            else:
                w_new = aircomp_aggregate_stack_tree(
                    w_sel, sel_w, k_noise,
                    0.0 if scheme == "digital" else noise_std, k_denom)
            if temporal:
                any_sched = num_sched > 0
                w_new = jax.tree.map(
                    lambda agg, old: jnp.where(any_sched, agg, old),
                    w_new, state.w)
            # energy ledger as a [K]-slot sum — same shape and op order
            # sharded and unsharded, so the ledger is bit-identical
            h_sel = slot_vals(h, sel_idx)
            e_round = jnp.sum(sel_w * transport_mod.uplink_energy(
                scheme, point.transport, h_sel, model_size, scen))
        # downlink: every receiver that can afford the listen window pays
        # for the broadcast (psum-of-local-rows under pop; static N when the
        # process model is off). dl_power=0 keeps the whole block an exact
        # no-op (x + 0·anything = x), preserving pre-downlink trajectories.
        if temporal:
            rc = jnp.sum(pstep.recv)
            recv_count = jax.lax.psum(rc, axis_name) if pop else rc
        else:
            recv_count = jnp.float32(n)
        e_dl = recv_count * transport_mod.downlink_energy(
            scheme, point.transport, model_size, scen, num_tx=kk)
        dl_energy = state.dl_energy + e_dl
        energy = state.energy + e_round + e_dl

        # ---- temporal carry (local rows only)
        if temporal:
            chan_state = commit_process(pstep, cs, mask_l)
            ac = jnp.sum(eligible)
            avail_count = jax.lax.psum(ac, axis_name) if pop else ac
            mb = jnp.min(chan_state.battery)
            min_battery = jax.lax.pmin(mb, axis_name) if pop else mb
        else:
            chan_state = state.chan_state
            avail_count = jnp.float32(n)
            min_battery = jnp.float32(jnp.inf)

        # ---- ascent on λ: uniform-K of the available clients, per-id
        # Gumbel streams, hierarchical top-k over the sharded scores
        ascores = (jnp.zeros((n_rows,)) + availability_logits(avail)
                   + client_gumbel(k_asel, ids))
        asc_idx = topk_idx(ascores)
        a_gate = (slot_vals(avail, asc_idx) if temporal
                  else jnp.ones((kk,), jnp.float32))
        if method == "gca":
            # dense per-client losses on local rows (GCA keeps the [N]
            # loss vector; ascent and sel_loss read it locally)
            bidx_ab = _batch_indices_ids(k_abatch, ids, shard, fl.batch_size)
            xab = jax.vmap(lambda xc, ic: xc[ic])(x, bidx_ab)
            yab = jax.vmap(lambda yc, ic: yc[ic])(y, bidx_ab)
            losses_l = vloss(w_new, xab, yab)
            amask_l = scatter_slots(asc_idx, a_gate)
            asc_contrib = amask_l * losses_l
            sl = jnp.sum(mask_l * losses_l)
            sel_loss = (jax.lax.psum(sl, axis_name) if pop else sl) / k_denom
        else:
            # slot path: losses only where consumed (ascent + descent slots)
            bidx_a = _batch_indices_ids(k_abatch, asc_idx, shard,
                                        fl.batch_size)
            xa = slot_batches(x, asc_idx, bidx_a)
            ya = slot_batches(y, asc_idx, bidx_a)
            asc_losses = vloss(w_new, xa, ya)
            asc_contrib = scatter_slots(asc_idx, a_gate * asc_losses)
            bidx_d = _batch_indices_ids(k_abatch, sel_idx, shard,
                                        fl.batch_size)
            xd = slot_batches(x, sel_idx, bidx_d)
            yd = slot_batches(y, sel_idx, bidx_d)
            sel_loss = jnp.sum(sel_w * vloss(w_new, xd, yd)) / k_denom
        lam_tilde = state.lam + point.ascent_lr * asc_contrib
        # the simplex projection couples all coordinates, but only through
        # the scalar water level θ: psum-bisection keeps it O(N/D + iters)
        # per device with no gather and no sort (ISSUE 8)
        lam_new = project_simplex_sharded(
            lam_tilde, axis_name=axis_name if pop else None)
        lam_max, lam_entropy, lam_ess = lambda_summary(
            lam_new, axis_name if pop else None)
        lam_hist, lam_snaps = _record_lambda(fl, state, lam_new, t)

        # ---- metrics: test eval as psum-of-local-rows. The accuracy vector
        # used to be all_gather'd to [N] for the stats — the one remaining
        # O(N) gather on the exact-K sharded path, flagged by the contract
        # linter's gather-then-reduce rule. mean/min ride one psum/pmin pair
        # and std the two-pass variance (the same centered formula jnp.std
        # evaluates, so the unsharded reference agrees to summation order).
        def eval_stats():
            accs = vacc(w_new, x_test, y_test)
            if not pop:
                return jnp.stack(
                    [jnp.mean(accs), jnp.min(accs), jnp.std(accs)])
            n_eval = n_rows * n_shards
            mean = jax.lax.psum(jnp.sum(accs), axis_name) / n_eval
            amin = jax.lax.pmin(jnp.min(accs), axis_name)
            var = jax.lax.psum(jnp.sum(jnp.square(accs - mean)),
                               axis_name) / n_eval
            return jnp.stack([mean, amin, jnp.sqrt(var)])

        if fl.eval_every == 1:
            stats = eval_stats()
            eval_cache = state.eval_cache
        else:
            stats = jax.lax.cond(t % fl.eval_every == 0,
                                 lambda _: eval_stats(),
                                 lambda _: state.eval_cache, None)
            eval_cache = stats
        metrics = SimHistory(
            avg_acc=stats[0],
            worst_acc=stats[1],
            std_acc=stats[2],
            energy=energy,
            loss=sel_loss,
            num_scheduled=num_sched,
            lam=lam_hist,  # LOCAL rows; out_specs concatenate to [T, N]
            avail_count=avail_count,
            min_battery=min_battery,
            lam_max=lam_max,
            lam_entropy=lam_entropy,
            lam_ess=lam_ess,
            dl_energy=dl_energy,
        )
        return SimState(w_new, lam_new, energy, key, chan_state,
                        eval_cache, lam_snaps, ef_new, dl_energy), metrics

    return round_fn


def make_round_fn(model: SimModel, fl: FLConfig, data, model_size: int):
    """Back-compat wrapper: bind ``fl``'s own knobs, return (state, t) -> ..."""
    from repro.core.sweep import sweep_point_from_config  # local: avoid cycle

    point = sweep_point_from_config(fl)
    round_fn = make_param_round_fn(model, fl, data, model_size, fl.method)
    return lambda state, t: round_fn(point, state, t)


def init_sim_state(model: SimModel, fl: FLConfig, key,
                   process=None, ids=None) -> SimState:
    """Initial carry. ``process`` (a traced ``ChannelProcess``, e.g. from a
    ``SweepPoint``) overrides the one derived from ``fl`` so traced knobs like
    ``battery_init`` ride the sweep's vmap axis; static scenarios get the
    leaf-less ``chan_state = ()`` and an unchanged key stream.

    ``ids`` (control_plane="sharded" only): the GLOBAL client ids whose rows
    this state holds — λ and ``chan_state`` are initialized for just those
    rows, with per-id draws (``dynamics.init_chan_state_ids``) so a shard's
    slice is bit-identical to the same rows of the unsharded state. Defaults
    to ``arange(N)`` (the unsharded reference) under the sharded discipline.
    """
    k_init, k_run = jax.random.split(key)
    w0 = model.init(k_init)
    if process is None:
        process = process_from_config(fl)
    sharded_cp = fl.control_plane == "sharded"
    if ids is not None and not sharded_cp:
        raise ValueError(
            "ids is a control_plane='sharded' argument; the replicated "
            "discipline always initializes the full [N] state")
    if sharded_cp and ids is None:
        ids = jnp.arange(fl.num_clients, dtype=jnp.int32)
    chan_state = ()
    if process.temporal:
        # fold_in: an independent stream, so the static path's k_init/k_run
        # consumption (and therefore its trajectories) is untouched
        k_cs = jax.random.fold_in(k_init, 1)
        if sharded_cp:
            chan_state = init_chan_state_ids(
                process, k_cs, ids, fl.num_subcarriers, fl.flat_fading)
        else:
            chan_state = init_chan_state(
                process, k_cs, fl.num_clients, fl.num_subcarriers,
                fl.flat_fading)
    n_rows = fl.num_clients if ids is None else ids.shape[0]
    # round 0 always evaluates (0 % eval_every == 0), so the zeros are never
    # read — the slot just keeps the carry static-shape
    eval_cache = () if fl.eval_every == 1 else jnp.zeros((3,), jnp.float32)
    e = fl.record_lambda_every
    if not isinstance(e, int) or isinstance(e, bool) or e < 0:
        raise ValueError(
            f"record_lambda_every must be an int >= 0, got {e!r}")
    # E in {0, 1} needs no snapshot carry (dense recording / no recording);
    # E > 1 carries the fixed [ceil(T/E), n_rows] strided buffer
    lam_snaps = () if e in (0, 1) else jnp.zeros(
        ((fl.rounds + e - 1) // e, n_rows), jnp.float32)
    # sparse transport: per-client error-feedback memory over the FLAT model
    # ([n_rows, P] — local rows only under the sharded control plane, same
    # per-id row discipline as chan_state). Other transports carry the
    # leaf-less () so their scan carries are byte-identical to before.
    ef_resid = ()
    if fl.transport == "sparse":
        p = sum(int(l.size) for l in jax.tree_util.tree_leaves(w0))
        ef_resid = jnp.zeros((n_rows, p), jnp.float32)
    return SimState(
        w=w0,
        lam=jnp.full((n_rows,), 1.0 / fl.num_clients),
        energy=jnp.zeros(()),
        key=k_run,
        chan_state=chan_state,
        eval_cache=eval_cache,
        lam_snaps=lam_snaps,
        ef_resid=ef_resid,
        dl_energy=jnp.zeros(()),
    )


def run_simulation(
    model: SimModel,
    fl: FLConfig,
    data,
    seed: Optional[int] = None,
    dense: bool = False,
    mesh=None,
) -> SimHistory:
    """Run T rounds of Algorithm 1 (or a baseline, per fl.method).

    ``dense=True`` forces the [N, model] reference path (differential tests
    and benchmarks; exact-K methods default to the sparse gather path).

    ``mesh`` (a 1-D ``jax.sharding.Mesh``, see ``sharding.client_mesh``)
    shards the client population across its devices: dense/GCA rounds and
    the full N-client eval run with per-client state split over the mesh and
    eq. (10) as a cross-device ``psum``. A mesh of size 1 (or None) is a
    structural no-op — this function compiles exactly the single-device
    program.
    """
    from repro.core.sweep import sweep_point_from_config  # local: avoid cycle

    if mesh is not None and mesh.size > 1:
        if fl.control_plane == "sharded":
            from repro.core.sharding import run_simulation_control_sharded
            return run_simulation_control_sharded(model, fl, data, mesh,
                                                  seed=seed)
        from repro.core.sharding import run_simulation_sharded
        return run_simulation_sharded(model, fl, data, mesh, seed=seed,
                                      dense=True)
    seed = fl.seed if seed is None else seed
    point = sweep_point_from_config(fl)
    state = init_sim_state(model, fl, jax.random.PRNGKey(seed),
                           process=point.process)
    model_size = tree_size(state.w)

    @jax.jit
    def run(point, state, data):
        # data is an argument, not a closed-over constant (see
        # sweep._build_runner)
        round_fn = make_param_round_fn(model, fl, data, model_size, fl.method,
                                       dense=dense)
        final, hist = jax.lax.scan(
            lambda s, t: round_fn(point, s, t), state, jnp.arange(fl.rounds))
        if fl.record_lambda_every > 1:
            # the strided snapshots ride the carry; attach the final buffer
            # as the history's λ leaf (scan can't emit strided stacks)
            hist = hist._replace(lam=final.lam_snaps)
        return hist

    return run(point, state, tuple(jnp.asarray(d) for d in data))


def run_multi_seed(model: SimModel, fl: FLConfig, data, seeds) -> SimHistory:
    """Average over simulation runs (the paper averages 5 seeds).

    Implemented as a one-point sweep through ``repro.core.sweep``: the seed
    axis is a ``vmap`` inside a single jitted computation, replacing the old
    per-seed re-jit loop (one compilation total instead of ``len(seeds)``).
    """
    from repro.core.sweep import run_sweep  # local: avoid import cycle

    result = run_sweep(model, data, [("run", fl)], seeds=tuple(seeds))
    return result.mean_history("run")
