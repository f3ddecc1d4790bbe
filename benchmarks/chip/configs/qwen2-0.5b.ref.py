"""Plain reference of the parameter server's first rounds on Qwen2-0.5B
(arXiv:2407.10671), written from the published architecture, the paper's
Algorithm 1 (arXiv:2312.14638) and the configuration file alone.

The model: token embedding; per layer a pre-norm (RMSNorm) grouped-query
attention with Q/K/V biases and rotary positions (rotate-half, frequencies
θ^(-2i/d_head)), causal softmax at 1/√d_head, output projection and
residual, then a pre-norm SwiGLU MLP and residual; a final RMSNorm and the
output head over the real vocabulary. The loss of a row is its mean
next-token cross-entropy.

One server round of CA-AFL (analog transport, K of N clients):

1. flat-fading Rayleigh channels and their effective channel (eq. 6);
2. the K scheduled clients by Gumbel-top-K over log λ + C log|h| (eq. 9);
3. the descent: the mean loss over the scheduled clients' rows, its
   gradient plus the receiver noise z/K of eq. (10), one SGD step;
4. the energy of eqs. (3)-(6) for the scheduled set, with M every
   parameter the server holds;
5. the ascent: K clients drawn uniformly, every client's mean loss at the
   new model, λ ← Π_Δ(λ + γ·losses).

The random draws follow the server's documented key discipline: each round
splits the server key seven ways as (next, channel, selection, batch,
noise, ascent selection, ascent batch); the noise key splits once per
parameter leaf (in the tree's flattening order), and a leaf of two or more
axes whose first axis is longer than 4 draws each slice along that axis
from ``fold_in(leaf key, i)``. Draws are made in float32 and cast to
``dtype``; everything else runs in ``dtype``, float32 products at the
highest precision. The control runs this same code in bfloat16.

It imports nothing of the program; the weights and batches come from the
benchmark, made from the run's seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


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


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x [B, S, heads..., hd]: rotate-half rotary embedding."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    extra = x.ndim - 3
    cos = jnp.cos(ang).reshape((1, s) + (1,) * extra + (hd // 2,))
    sin = jnp.sin(ang).reshape((1, s) + (1,) * extra + (hd // 2,))
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def row_losses(m: dict, params, tokens):
    """[B] mean next-token cross-entropy of each row."""
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    hd = m["head_dim"]
    s = tokens.shape[1]
    x = params["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"], eps)
        q = jnp.einsum("bsd,dkgh->bskgh", h, lp["wq"]) + lp["bq"]
        k = jnp.einsum("bsd,dkh->bskh", h, lp["wk"]) + lp["bk"]
        v = jnp.einsum("bsd,dkh->bskh", h, lp["wv"]) + lp["bv"]
        q, k = _rope(q, theta), _rope(k, theta)
        sc = jnp.einsum("bqkgh,btkh->bkgqt", q, k) / jnp.sqrt(
            jnp.asarray(hd, x.dtype))
        sc = jnp.where(causal, sc, jnp.finfo(x.dtype).min)
        o = jnp.einsum("bkgqt,btkh->bqkgh", jax.nn.softmax(sc, -1), v)
        x = x + jnp.einsum("bqkgh,kghd->bqd", o, lp["wo"])
        h = _rms(x, lp["mlp_norm"], eps)
        g = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
        return x + g @ lp["w_down"], None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"], eps)
    logits = x[:, :-1] @ params["lm_head"][:, :m["vocab_size"]]
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - tgt, axis=-1)


def receiver_noise(key, tree, dtype):
    """z: one standard normal per parameter, by the server's key discipline."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for leaf, k in zip(leaves, keys, strict=True):
        if leaf.ndim >= 2 and leaf.shape[0] > 4:
            z = jax.vmap(lambda i, k=k, shp=leaf.shape[1:]: jax.random.normal(
                jax.random.fold_in(k, i), shp))(jnp.arange(leaf.shape[0]))
        else:
            z = jax.random.normal(k, leaf.shape)
        out.append(z.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


@functools.lru_cache(maxsize=None)
def _round_fn(m_key: tuple, fl_key: tuple, dtype_name: str):
    m, fl = dict(m_key), dict(fl_key)
    dt = jnp.dtype(dtype_name)
    n, k = fl["num_clients"], fl["clients_per_round"]
    rows = fl["rows_per_client"]

    def one_round(params, lam, key, tokens, model_size):
        key, k_chan, k_sel, _, k_noise, k_asel, _ = jax.random.split(key, 7)
        re, im = jax.random.normal(k_chan, (2, n, 1)) / jnp.sqrt(2.0)
        mag = jnp.broadcast_to(jnp.sqrt(re ** 2 + im ** 2),
                               (n, fl["num_subcarriers"]))
        h_sc = jnp.maximum(mag, fl["channel_floor"]).astype(dt)
        h = 1.0 / jnp.sqrt(jnp.mean(1.0 / jnp.square(h_sc), axis=-1))
        scores = (jnp.log(jnp.maximum(lam, 1e-38))
                  + jnp.asarray(fl["energy_C"], dt) * jnp.log(h)
                  + jax.random.gumbel(k_sel, (n,)).astype(dt))
        _, idx = jax.lax.top_k(scores, k)
        mask = jnp.zeros((n,), dt).at[idx].set(1)
        sel_rows = (idx[:, None] * rows + jnp.arange(rows)).reshape(-1)

        def descent_loss(p):
            return jnp.mean(row_losses(m, p, tokens[sel_rows]))

        loss, grads = jax.value_and_grad(descent_loss)(params)
        z = receiver_noise(k_noise, params, dt)
        sigma = jnp.asarray(fl["noise_std"] / k, dt)
        g_rx = jax.tree.map(lambda g, zz: g + sigma * zz, grads, z)
        lr = jnp.asarray(fl["lr"], dt)
        new = jax.tree.map(lambda p, g: p - lr * g, params, g_rx)
        energy = jnp.sum(mask * fl["psi"] * model_size.astype(dt) * fl["tau"]
                         / jnp.square(jnp.maximum(h, fl["channel_floor"])))
        _, aidx = jax.lax.top_k(jax.random.gumbel(k_asel, (n,)), k)
        amask = jnp.zeros((n,), dt).at[aidx].set(1)
        # client by client, so that one client's logits are held at a time
        client_loss = jnp.mean(jax.lax.map(
            lambda t: row_losses(m, new, t), tokens.reshape(n, rows, -1)),
            axis=1)
        lam = project_simplex(lam + jnp.asarray(fl["ascent_lr"], dt)
                              * amask * client_loss)
        norms = lambda t: jnp.stack([  # noqa: E731
            jnp.linalg.norm(x.astype(jnp.float32).ravel())
            for x in jax.tree_util.tree_leaves(t)])
        return new, lam, key, {
            "loss": loss.astype(jnp.float32),
            "energy": energy.astype(jnp.float32),
            "num_scheduled": jnp.sum(mask).astype(jnp.float32),
            "lam": lam.astype(jnp.float32),
            "grad_norms": norms(g_rx), "pure_grad_norms": norms(grads)}

    return jax.jit(one_round, donate_argnums=(0,))


def first_rounds(m: dict, fl: dict, params, key, batches, model_size: int,
                 dtype: str = "float32", lam=None):
    """Replay ``len(batches)`` rounds from ``params`` (consumed), server key
    ``key`` and ``lam`` (uniform where not given: the first round). Returns
    ``(per-round readings, params, lam, key)`` after the last, from which a
    further call continues: each reading holds ``loss, energy,
    num_scheduled, lam`` and the per-leaf norms of the gradient as the
    optimizer receives it (``grad_norms``, receiver noise included) and
    without the noise (``pure_grad_norms``)."""
    mk = tuple(sorted((a, b) for a, b in m.items()
                      if isinstance(b, (int, float, str))))
    fk = tuple(sorted((a, b) for a, b in fl.items()
                      if isinstance(b, (int, float, str))))
    fn = _round_fn(mk, fk, dtype)
    dt = jnp.dtype(dtype)
    params = jax.tree.map(lambda p: p.astype(dt), params)
    if lam is None:
        lam = jnp.full((fl["num_clients"],), 1.0 / fl["num_clients"], dt)
    prec = "highest" if dtype == "float32" else "default"
    out = []
    with jax.default_matmul_precision(prec):
        for b in batches:
            params, lam, key, rd = fn(params, lam, key,
                                      jnp.asarray(b["tokens"]),
                                      jnp.float32(model_size))
            out.append({f: np.asarray(v) for f, v in rd.items()})
    return out, params, lam, key
