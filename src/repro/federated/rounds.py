"""The jit'd production FL round: CA-AFL at model scale.

One round = one compiled function on the mesh:

  1. every client (= slice of the ``data`` axis) computes its local gradient
     on its local batch;
  2. per-example weights (selection mask × N/K) scale each client's
     contribution, so the gradient reduction GSPMD inserts over ``data`` IS
     the over-the-air superposition of eq. (10) — AWGN z/K is injected into
     the aggregated update from a PRNG key;
  3. the server optimizer applies the aggregated update (plain SGD = the
     paper's model-averaging for one local step; AdamW is the beyond-paper
     server-optimizer option);
  4. per-client mean losses come back for the λ-ascent (the paper's "control
     channel" scalars).

Selection, λ bookkeeping, channel draws and the energy ledger are host-side
in ``server.py`` — O(N) scalars, exactly the paper's control-channel split.

Both rounds name their device work with ``jax.named_scope``: ``descent``,
``noise`` (the receiver AWGN), ``update`` and ``loss_probe``.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.federated.client import client_weights
from repro.optim import apply_updates


class FLRoundMetrics(NamedTuple):
    loss: jnp.ndarray            # weighted global loss (selected set)
    client_losses: jnp.ndarray   # [N] per-client mean loss (control channel)
    grad_norm: jnp.ndarray


def make_fl_round(model, optimizer, num_clients: int, clients_per_round: int,
                  noise_std: float = 0.0, ctx=None, microbatches: int = 1,
                  fused_probe: bool = False, gather_k: bool = False):
    """Returns round_fn(params, opt_state, batch, mask, key) -> (params,
    opt_state, FLRoundMetrics).

    batch must carry "client_ids" [B] mapping each example to its client.
    ``microbatches`` > 1 runs gradient accumulation: the global batch is
    scanned in B/microbatches slices, dividing activation memory by the same
    factor at no recompute cost (each client's rows must be contiguous so
    every slice still covers all clients).

    ``gather_k=True`` builds the selected-K gather round instead (the
    production-tier leg of the simulator's hot-path contract):
    ``round_fn(params, opt_state, batch, mask, idx, key)`` takes the
    ``lax.top_k`` index vector [K] from
    ``selection.select_clients_sparse`` and computes the descent
    forward+backward on ONLY the K selected clients' example blocks — the
    same weighted-mean normalization, so the update equals the dense round
    to summation order, at K/N of its cost. Requires the canonical batch
    layout (block j = client j's B/N contiguous examples; the server
    verifies host-side and falls back to the dense round otherwise) and is
    exclusive with ``microbatches``/``fused_probe``. Gated slots
    (availability/battery) ride along with per-example weight 0.

    ``fused_probe`` (BEYOND-PAPER optimization, recorded in EXPERIMENTS.md
    §Perf): per-client losses for the λ-ascent come out of the *descent*
    forward (evaluated at w^t) instead of a second forward at w^{t+1} —
    Alg. 1 line 12 becomes one-round stale, removing ~1/3 of the round's
    compute and HBM traffic. The simulator validates that training curves
    are indistinguishable (tests/test_perf_variants.py).
    """
    if not 1 <= clients_per_round <= num_clients:
        raise ValueError(
            f"clients_per_round={clients_per_round} must be in "
            f"[1, num_clients={num_clients}]")
    if gather_k:
        if microbatches != 1 or fused_probe:
            raise ValueError(
                "gather_k is exclusive with microbatches/fused_probe: the "
                "gathered sub-batch covers only the selected clients")
        return _make_gather_round(model, optimizer, num_clients, noise_std,
                                  ctx)

    def weighted_loss_and_perex(p, b, mask):
        # K as the actual scheduled count: identical to the static
        # clients_per_round for exact-K selection, and the correct eq. (10)
        # normalizer when availability/battery gating (or GCA) schedules a
        # variable number of clients
        k_sched = jnp.maximum(jnp.sum(mask), 1.0)
        w = client_weights(mask, b["client_ids"], k_sched)
        if fused_probe:
            # one forward yields BOTH the weighted scalar and per-ex NLL
            per_ex = _per_example_nll(model, p, b, ctx)
            return jnp.mean(per_ex * w), per_ex
        b = dict(b)
        b["weights"] = w
        return model.loss_fn(p, b, ctx), jnp.zeros((w.shape[0],))

    def round_fn(params, opt_state, batch, mask, key):
        cids = batch["client_ids"]

        with jax.named_scope("descent"):
            if microbatches == 1:
                (loss, per_ex), grads = jax.value_and_grad(
                    lambda p: weighted_loss_and_perex(p, batch, mask),
                    has_aux=True)(params)
            else:
                bsz = cids.shape[0]
                assert bsz % microbatches == 0
                mb = {k: v.reshape((microbatches, bsz // microbatches)
                                   + v.shape[1:])
                      for k, v in batch.items()}

                def acc_step(carry, mslice):
                    loss_a, grads_a = carry
                    (l, pe), g = jax.value_and_grad(
                        lambda p: weighted_loss_and_perex(p, mslice, mask),
                        has_aux=True)(params)
                    return (loss_a + l / microbatches,
                            jax.tree.map(lambda a, b_: a + b_ / microbatches,
                                         grads_a, g)), pe

                # accumulate in param dtype: an f32 accumulator would cost an
                # extra 2x params bytes per device at 235B scale (documented
                # precision trade-off; each term is pre-divided by
                # microbatches)
                zero = (jnp.zeros((), jnp.float32),
                        jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype),
                                     params))
                (loss, grads), per_mb = jax.lax.scan(acc_step, zero, mb)
                per_ex = per_mb.reshape(-1)

        # --- AirComp receiver noise: z^(t)/K on the aggregated update, with
        # K the ACTUAL scheduled count — the same normalizer the gradient
        # weights use, mirroring the simulator's aircomp_aggregate ----------
        if noise_std:
            with jax.named_scope("noise"):
                grads = add_awgn(grads, key,
                                 noise_std / jnp.maximum(jnp.sum(mask), 1.0))

        with jax.named_scope("update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)

        # --- control channel: per-client mean losses for the λ-ascent ------
        with jax.named_scope("loss_probe"):
            if fused_probe:
                # beyond-paper: stale (w^t) losses from the descent forward
                ones = jnp.ones_like(per_ex)
                sums = jnp.zeros((num_clients,),
                                 per_ex.dtype).at[cids].add(per_ex)
                cnts = jnp.zeros((num_clients,),
                                 per_ex.dtype).at[cids].add(ones)
                client_losses = sums / jnp.maximum(cnts, 1.0)
            else:
                # paper-faithful: a second forward on the NEW model — exactly
                # Alg. 1 line 12, which evaluates f_i(w̄^{t+1}) on the ascent
                # set
                client_losses = per_client_losses(model, params, batch,
                                                  num_clients, ctx,
                                                  microbatches=microbatches)
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(grads)))
        return params, opt_state, FLRoundMetrics(
            loss=loss, client_losses=client_losses, grad_norm=gnorm)

    return round_fn


def _make_gather_round(model, optimizer, num_clients: int, noise_std, ctx):
    """The selected-K production round (see ``make_fl_round(gather_k=True)``).

    The dense round's weighted mean over all B examples is
    ``(1/B)·Σ_b mask[cid_b]·(N/K)·nll_b`` — every unselected example
    contributes an exact 0 yet still pays its forward+backward. Here the K
    selected blocks are gathered first and the same sum runs over K·(B/N)
    examples with the identical ``/B`` normalizer, so loss and gradients
    match the dense round to summation order while the descent compute
    scales with the scheduled set. The λ-ascent probe
    (``per_client_losses``) stays full-population: Alg. 1's control channel
    needs every client reachable by the uniform ascent draw.
    """

    def round_fn(params, opt_state, batch, mask, idx, key):
        cids = batch["client_ids"]
        bsz = cids.shape[0]
        m = bsz // num_clients  # examples per client block
        with jax.named_scope("descent"):
            k_sched = jnp.maximum(jnp.sum(mask), 1.0)
            rows = (idx[:, None] * m + jnp.arange(m)[None, :]).reshape(-1)
            sub = {name: v[rows] for name, v in batch.items()}
            # per-example weights of the gathered rows: the dense round's
            # mask[cid]·N/K, with gated slots (mask[idx] == 0) contributing 0
            w = jnp.repeat(mask[idx], m) * (num_clients / k_sched)

            def loss_fn(p):
                per_ex = _per_example_nll(model, p, sub, ctx)
                return jnp.sum(per_ex * w) / bsz

            loss, grads = jax.value_and_grad(loss_fn)(params)

        if noise_std:
            with jax.named_scope("noise"):
                grads = add_awgn(grads, key, noise_std / k_sched)

        with jax.named_scope("update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)

        with jax.named_scope("loss_probe"):
            client_losses = per_client_losses(model, params, batch,
                                              num_clients, ctx)
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(grads)))
        return params, opt_state, FLRoundMetrics(
            loss=loss, client_losses=client_losses, grad_norm=gnorm)

    return round_fn


# Bound on the elements one step of ``add_awgn``'s loop draws: 16 MiB of f32
# normals (and as much of threefry bits) at a time.
NOISE_BLOCK_ELEMS = 1 << 22


def add_awgn(grads, key, std: float):
    """z ~ N(0, std²) elementwise on every leaf (eq. 10's receiver noise).

    Key discipline: ``key`` is split once per leaf. A leaf with
    ``ndim >= 2`` and ``shape[0] > 4`` takes row ``i``'s noise from
    ``normal(fold_in(leaf_key, i), shape[1:])``; every other leaf takes one
    ``normal(leaf_key, shape)``.

    The rows of such a leaf are drawn in blocks of
    ``max(1, NOISE_BLOCK_ELEMS // row_size)`` rows, each block one vmapped
    draw over its row ids, added in place by a scan over the leaf; the
    ``shape[0] % rows`` rows left take one more draw, and a leaf that fits
    one block is drawn with no loop. The blocks give the same bits as row-by-row draws. One step
    holds at most ``NOISE_BLOCK_ELEMS`` elements (or one row, where a row is
    larger), so full-leaf threefry, whose u32 bit tensors would be the size
    of the whole gradient (GiBs at 235B), is never materialised; and a leaf
    of many short rows (an embedding, an output head) takes a few large
    steps rather than one per row.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    keys = jax.random.split(key, len(leaves))

    def noisy(g, k):
        if g.ndim < 2 or g.shape[0] <= 4:
            return g + std * jax.random.normal(k, g.shape, g.dtype)
        n, row_shape, dtype = g.shape[0], g.shape[1:], g.dtype
        rows = max(1, NOISE_BLOCK_ELEMS // math.prod(row_shape))

        def add_rows(blk, ids):
            z = jax.vmap(lambda i: jax.random.normal(
                jax.random.fold_in(k, i), row_shape, dtype))(ids)
            return blk + std * z

        if rows >= n:
            return add_rows(g, jnp.arange(n))
        # the loop carries the leaf in the sum's dtype (a bf16 leaf plus an
        # f32 std is f32, as the one-shot draw above gives)
        g = g.astype(jnp.result_type(g, std))

        def body(gl, start):
            blk = jax.lax.dynamic_slice_in_dim(gl, start, rows)
            blk = add_rows(blk, start + jnp.arange(rows))
            return jax.lax.dynamic_update_slice_in_dim(gl, blk, start, 0), None

        n_full = n // rows * rows
        g, _ = jax.lax.scan(body, g, jnp.arange(0, n_full, rows))
        if n_full == n:
            return g
        return g.at[n_full:].set(add_rows(g[n_full:], jnp.arange(n_full, n)))

    return jax.tree_util.tree_unflatten(
        treedef, [noisy(g, k) for g, k in zip(leaves, keys, strict=True)])


def _per_example_nll(model, params, batch, ctx):
    # simulator-style models (e.g. models.logreg.logistic_regression_prod)
    # expose per_example_nll directly; architecture models go through cfg
    if hasattr(model, "per_example_nll"):
        return model.per_example_nll(params, batch)
    cfg = model.cfg
    if cfg.family == "vlm":
        logits = model.mod.forward(cfg, params, batch["tokens"], batch["images"], ctx)
    elif cfg.family == "audio":
        logits = model.mod.forward(cfg, params, batch["tokens"], batch["audio"], ctx)
    elif cfg.family == "moe":
        logits, _aux = model.mod.forward(cfg, params, batch["tokens"], ctx)
    else:
        logits = model.mod.forward(cfg, params, batch["tokens"], ctx)
    from repro.models.dense import per_token_nll
    return jnp.mean(per_token_nll(logits[:, :-1], batch["labels"][:, 1:]),
                    axis=-1)                                          # [B]


def per_client_losses(model, params, batch, num_clients: int, ctx=None,
                      microbatches: int = 1):
    """[N] mean loss per client: forward-only, per-example NLL, segment mean.

    This is Alg. 1's ascent-side evaluation f_i(w̄^{t+1}; ξ̃) for all clients
    at once (the server loop masks it down to the uniform ascent set U^(t)).
    Microbatched with the same slicing as the descent pass so the fp32 logits
    buffer stays 1/microbatches of the global batch.
    """
    cids = batch["client_ids"]
    bsz = cids.shape[0]

    if microbatches == 1:
        per_ex = _per_example_nll(model, params, batch, ctx)
        cid_flat = cids
    else:
        mb = {k: v.reshape((microbatches, bsz // microbatches) + v.shape[1:])
              for k, v in batch.items()}

        def probe(_, mslice):
            return None, _per_example_nll(model, params, mslice, ctx)

        _, per_mb = jax.lax.scan(probe, None, mb)
        per_ex = per_mb.reshape(-1)
        cid_flat = cids
    ones = jnp.ones_like(per_ex)
    sums = jnp.zeros((num_clients,), per_ex.dtype).at[cid_flat].add(per_ex)
    cnts = jnp.zeros((num_clients,), per_ex.dtype).at[cid_flat].add(ones)
    return sums / jnp.maximum(cnts, 1.0)


def make_grad_norm_probe(model, num_clients: int, ctx=None,
                         with_grads: bool = False):
    """GCA's control-channel probe: [N] per-client gradient norms at w^t.

    GCA selection needs ‖∇f_i(w^t)‖ BEFORE the round's mask exists, so this
    runs as a separate forward+backward per client — sequential via
    ``lax.scan`` (N small grads ≈ one full-batch grad in total compute,
    1/N of its activation memory). Requires the round's batch layout: each
    client's examples contiguous and equally sized (B % N == 0), as produced
    by the data pipeline — the reshape below slices clients apart.

    ``with_grads=True`` returns ``(norms [N], losses [N], grads [N, P])``
    with each client's mean gradient raveled to a flat f32 row and its mean
    loss at w^t: the probe's per-client gradients ARE the round's descent
    gradients (same batch, same params), so ``ParameterServer`` reuses them
    as the update via a masked flat aggregate instead of running a second
    full forward+backward. The price is holding the [N, P] stack the probe
    previously discarded — inherent to GCA (it computes all N gradients
    either way), but worth disabling at true model scale via the server's
    ``reuse_probe_grads=False``.
    """

    def client_loss(params, cbatch):
        return jnp.mean(_per_example_nll(model, params, cbatch, ctx))

    gfn = jax.grad(client_loss)
    vgfn = jax.value_and_grad(client_loss)

    def probe(params, batch):
        bsz = batch["client_ids"].shape[0]
        assert bsz % num_clients == 0, "probe needs equal per-client batches"
        mb = {k: v.reshape((num_clients, bsz // num_clients) + v.shape[1:])
              for k, v in batch.items()}

        def one(_, cbatch):
            g = gfn(params, cbatch)
            norm = jnp.sqrt(sum(
                jnp.sum(jnp.square(l.astype(jnp.float32)))
                for l in jax.tree_util.tree_leaves(g)))
            return None, norm

        def one_with_grads(_, cbatch):
            loss, g = vgfn(params, cbatch)
            flat = jnp.concatenate([
                l.astype(jnp.float32).reshape(-1)
                for l in jax.tree_util.tree_leaves(g)])
            return None, (jnp.sqrt(jnp.sum(jnp.square(flat))), loss, flat)

        # scatter by each block's OBSERVED client id, so contiguous-but-
        # permuted batches still attribute every norm to the right client
        obs = mb["client_ids"][:, 0]
        if not with_grads:
            _, norms = jax.lax.scan(one, None, mb)
            return jnp.zeros((num_clients,), norms.dtype).at[obs].set(norms)
        _, (norms, losses, flats) = jax.lax.scan(one_with_grads, None, mb)
        return (jnp.zeros((num_clients,), norms.dtype).at[obs].set(norms),
                jnp.zeros((num_clients,), losses.dtype).at[obs].set(losses),
                jnp.zeros(flats.shape, flats.dtype).at[obs].set(flats))

    return probe
