"""Plain reference of the paper's deployment: the first rounds of one sweep
cell (arXiv:2312.14638, Alg. 1 with eqs. 3-10), written from the paper and
the configuration file alone.

One round, for a cell (selection method, scalar knobs, seed):

1. block flat-fading Rayleigh channels |h| ~ |CN(0, 1)| per client,
   truncated at the floor, and the effective channel of eq. (6);
2. the descent set: K clients without replacement by Gumbel-top-K over the
   method's logits (FedAvg: uniform; AFL: log λ; CA-AFL: log λ + C log|h|,
   eq. 9), or GCA's threshold on its indicator;
3. each scheduled client takes one SGD step (η_t = η0·decay^t) on a batch of
   its shard, and the server averages the K models (eq. 10, no receiver
   noise in this deployment);
4. the energy of eqs. (3)-(6) for the scheduled set;
5. the ascent: K clients drawn uniformly, their losses at the new model,
   λ ← Π_Δ(λ + γ·losses) by the sort-based simplex projection;
6. the test accuracy of every client's test shard.

The random draws follow the documented key discipline of the simulator: the
seed's key splits into (init, run); each round splits the run key seven ways
as (next, channel, selection, batch, noise, ascent selection, ascent batch).
Draws are made in float32 and cast to ``dtype``; every other operation runs
in ``dtype``, with float32 matrix products at the highest precision. The
control runs this same function in bfloat16.

It imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EXACT_K = ("fedavg", "afl", "ca_afl")


def project_simplex(v):
    """Euclidean projection onto the probability simplex (Duchi et al.)."""
    n = v.shape[0]
    u = jnp.sort(v)[::-1]
    css = jnp.cumsum(u)
    k = jnp.arange(1, n + 1, dtype=v.dtype)
    cond = u + (1.0 - css) / k > 0
    rho = jnp.max(jnp.where(cond, k, 0))
    theta = (jnp.sum(jnp.where(cond, u, 0)) - 1.0) / rho
    return jnp.maximum(v - theta, 0)


def _loss(W, b, x, y):
    logp = jax.nn.log_softmax(x @ W + b)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def _acc(W, b, x, y):
    return jnp.mean((jnp.argmax(x @ W + b, -1) == y).astype(W.dtype))


@functools.lru_cache(maxsize=None)
def _cell_fn(method: str, cfg_key: tuple, rounds: int, dtype_name: str):
    cfg = dict(cfg_key)
    dt = jnp.dtype(dtype_name)
    n, k = cfg["num_clients"], cfg["clients_per_round"]
    bsz, nsc = cfg["batch_size"], cfg["num_subcarriers"]
    floor, psi, tau = cfg["channel_floor"], cfg["psi"], cfg["tau"]
    gca = dict(cfg["gca"])
    vgrad = jax.vmap(jax.grad(_loss, argnums=(0, 1)), (None, None, 0, 0))
    vloss = jax.vmap(_loss, (None, None, 0, 0))
    vacc = jax.vmap(_acc, (None, None, 0, 0))

    def run(seed, lr0, decay, gamma, C, x, y, xt, yt):
        x, xt = x.astype(dt), xt.astype(dt)
        shard, dim = x.shape[1], x.shape[2]
        classes = cfg["num_classes"]
        model_size = dim * classes + classes
        _, key = jax.random.split(jax.random.PRNGKey(seed))
        lr0, decay, gamma, C = (jnp.asarray(v, dt) for v in
                                (lr0, decay, gamma, C))

        def one_round(carry, t):
            W, b, lam, energy, key = carry
            key, k_chan, k_sel, k_batch, _, k_asel, k_abatch = \
                jax.random.split(key, 7)
            re, im = jax.random.normal(k_chan, (2, n, 1)) / jnp.sqrt(2.0)
            mag = jnp.broadcast_to(jnp.sqrt(re ** 2 + im ** 2), (n, nsc))
            h_sc = jnp.maximum(mag, floor).astype(dt)
            h = 1.0 / jnp.sqrt(jnp.mean(1.0 / jnp.square(h_sc), axis=-1))
            bidx = jax.random.randint(k_batch, (n, bsz), 0, shard)
            eta = lr0 * decay ** t.astype(dt)
            if method in EXACT_K:
                if method == "fedavg":
                    logits = jnp.zeros((n,), dt)
                else:
                    logits = jnp.log(jnp.maximum(lam, 1e-38))
                noise = jax.random.gumbel(k_sel, (n,)).astype(dt)
                if method == "ca_afl":
                    noise = noise + C * jnp.log(h)
                scores = logits + noise
                vals, idx = jax.lax.top_k(scores, k + 1)
                margin = vals[k - 1] - vals[k]
                idx = idx[:k]
                mask = jnp.zeros((n,), dt).at[idx].set(1)
                k_den = jnp.maximum(jnp.sum(mask), 1)
                gW, gb = vgrad(W, b, x[idx[:, None], bidx[idx]],
                               y[idx[:, None], bidx[idx]])
                wk = mask[idx]
                W_new = jnp.einsum("k,kdc->dc", wk, W - eta * gW) / k_den
                b_new = jnp.einsum("k,kc->c", wk, b - eta * gb) / k_den
            else:  # gca
                rows = jnp.arange(n)[:, None]
                gW, gb = vgrad(W, b, x[rows, bidx], y[rows, bidx])
                g_sq = (jnp.sum(jnp.square(gW), axis=(1, 2))
                        + jnp.sum(jnp.square(gb), axis=1))
                a, s = gca["alpha"], gca["sigma_t"]
                g_signal = jnp.mean(
                    jnp.log1p(a * g_sq / s)
                    / jnp.log1p(a * jnp.maximum(jnp.max(g_sq), 1e-12) / s))
                h_ben = h / jnp.maximum(jnp.max(h), 1e-12)
                ind = gca["lambda_V"] * g_signal + gca["lambda_E"] * h_ben
                thr = (gca["rho1"] * jnp.mean(ind)
                       + gca["rho2"] * jnp.median(ind) + s / a)
                mask = (ind > thr).astype(dt)
                margin = jnp.min(jnp.abs(ind - thr))
                noise = jnp.zeros((n,), dt)
                k_den = jnp.maximum(jnp.sum(mask), 1)
                W_new = jnp.einsum("n,ndc->dc", mask, W - eta * gW) / k_den
                b_new = jnp.einsum("n,nc->c", mask, b - eta * gb) / k_den
                W_new = jnp.where(jnp.sum(mask) > 0, W_new, W)
                b_new = jnp.where(jnp.sum(mask) > 0, b_new, b)
            e_client = psi * model_size * tau / jnp.square(
                jnp.maximum(h, floor))
            energy = energy + jnp.sum(mask * e_client)
            _, aidx = jax.lax.top_k(jax.random.gumbel(k_asel, (n,)), k)
            amask = jnp.zeros((n,), dt).at[aidx].set(1)
            abidx = jax.random.randint(k_abatch, (n, bsz), 0, shard)
            if method in EXACT_K:
                a_l = vloss(W_new, b_new, x[aidx[:, None], abidx[aidx]],
                            y[aidx[:, None], abidx[aidx]])
                losses = jnp.zeros((n,), dt).at[aidx].set(a_l)
                s_l = vloss(W_new, b_new, x[idx[:, None], abidx[idx]],
                            y[idx[:, None], abidx[idx]])
                sel_loss = jnp.sum(mask[idx] * s_l) / k_den
            else:
                rows = jnp.arange(n)[:, None]
                losses = vloss(W_new, b_new, x[rows, abidx], y[rows, abidx])
                sel_loss = jnp.sum(mask * losses) / k_den
            lam = project_simplex(lam + gamma * amask * losses)
            accs = vacc(W_new, b_new, xt, yt)
            row = {"loss": sel_loss, "energy": energy,
                   "num_scheduled": jnp.sum(mask), "lam": lam,
                   "avg_acc": jnp.mean(accs), "worst_acc": jnp.min(accs),
                   "std_acc": jnp.std(accs), "margin": margin,
                   "mask": mask, "noise": noise}
            return ((W_new, b_new, lam, energy, key),
                    {f: v.astype(jnp.float32) for f, v in row.items()})

        init = (jnp.zeros((dim, classes), dt), jnp.zeros((classes,), dt),
                jnp.full((n,), 1.0 / n, dt), jnp.zeros((), dt), key)
        _, out = jax.lax.scan(one_round, init, jnp.arange(rounds))
        return out

    return jax.jit(run)


def first_rounds(cfg: dict, method: str, point: dict, seed: int, data,
                 rounds: int, dtype: str = "float32") -> dict:
    """The reference's first ``rounds`` rounds of one cell, as host arrays:
    ``loss, energy, num_scheduled, avg_acc, worst_acc, std_acc, margin``
    [rounds] and ``lam, mask, noise`` [rounds, N]. ``margin`` is the round's
    selection margin (the gap between the K-th and (K+1)-th score, or GCA's
    nearest indicator to its threshold): a round whose margin lies within
    rounding of zero does not determine the selection at this precision.
    ``noise`` is the part of an exact-K rule's selection score that is not
    log λ (the Gumbel draw, plus C log|h| for CA-AFL), so that the
    selection another λ would make can be worked out; ``mask`` is the
    scheduled set.

    ``data`` is ``(x [N, S, D], y [N, S], x_test, y_test)``; ``point`` holds
    ``lr0, lr_decay, ascent_lr, energy_C``.
    """
    keys = ("num_clients", "clients_per_round", "batch_size",
            "num_subcarriers", "channel_floor", "psi", "tau")
    cfg_key = tuple((k, cfg[k]) for k in keys) + (
        ("num_classes", cfg["data"]["num_classes"]),
        ("gca", tuple(sorted(cfg["gca"].items()))))
    fn = _cell_fn(method, cfg_key, rounds, dtype)
    prec = "highest" if dtype == "float32" else "default"
    with jax.default_matmul_precision(prec):
        out = fn(np.int32(seed), np.float32(point["lr0"]),
                 np.float32(point["lr_decay"]), np.float32(point["ascent_lr"]),
                 np.float32(point["energy_C"]), *data)
    return {f: np.asarray(v) for f, v in out.items()}
