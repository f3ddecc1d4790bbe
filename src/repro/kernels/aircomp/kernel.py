"""Pallas TPU kernel: fused AirComp aggregation (the paper's hot-spot).

Fuses the per-client gain/mask scale, the superposition sum over the client
axis, the AWGN injection and the 1/K normalization into one pass over the
model dimension — one HBM read of the [N, M] stacked updates, one HBM write
of the [M] aggregate. Blocked over M with VMEM tiles of [N, TILE_M]; the
weighted reduction over N runs on the VPU as an fp32 accumulation. The noise
``z`` and the aggregate ride as [1, M] rows with (1, TILE_M) blocks: under
``vmap`` (the sweep's seed and point axes) a 1-D (TILE_M,) block would turn
into a (1, TILE_M) slice of an [R, M] array, which the TPU lowering refuses.

``noise_std`` and ``k`` ride in as (1, 1) SMEM scalars, NOT static compile
args: the simulator traces both (the receiver noise is a sweepable scenario
knob and K is the *actual* scheduled count under availability/battery
gating), so baking them into the executable would force one recompile per
sweep cell — exactly what the batched sweep engine exists to avoid.

TPU adaptation note (DESIGN.md §2): the paper's multiple-access channel does
this sum "for free" in the air; on TPU the sum is explicit, so fusing
scale+sum+noise+normalize removes three extra HBM round-trips a naive
composition would pay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 1024  # lane-dim tile; multiple of 128
LANE = 128


def _blocking(m: int) -> tuple[int, int]:
    """(tile, pad) for a model dimension of ``m`` columns.

    M is zero-padded up to a multiple of the tile, and the tile is at most
    TILE_M lanes and always a multiple of 128: a whole unaligned [C, M] row
    block never lands in VMEM, whatever M is. Each output column is a sum
    over clients only, so the padded columns are sliced off without
    touching the real ones.
    """
    tile = min(TILE_M, -(-m // LANE) * LANE)
    return tile, (-m) % tile


def _aircomp_kernel(ns_ref, ik_ref, x_ref, w_ref, z_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # [N, TM]
    w = w_ref[...].astype(jnp.float32)          # [N, 1]
    acc = jnp.sum(x * w, axis=0, keepdims=True)  # [1, TM]
    acc = acc + ns_ref[0, 0] * z_ref[...].astype(jnp.float32)
    o_ref[...] = acc * ik_ref[0, 0]


def _quant_aircomp_kernel(ns_ref, ik_ref, x_ref, w_ref, d_ref, u_ref, z_ref,
                          o_ref):
    """Fused quantize-aggregate tile (the quantized transport's hot pass).

    SMEM scalar layout (both (1, 1) f32, in argument order):
      ``ns_ref`` — receiver-noise std σ of eq. (10); traced, NOT a compile
      arg (a noise sweep must not recompile the kernel);
      ``ik_ref`` — 1/K with K the round's ACTUAL scheduled count (traced:
      availability/battery gating makes it data-dependent).
    Per-client VMEM operands ride like the gains: ``w_ref`` [C, 1] mask/gain
    entries, ``d_ref`` [C, 1] stochastic-rounding grid steps Δ_c (0 ⇒ the
    row passes through unquantized). ``u_ref`` [C, TM] pre-drawn U[0,1)
    rounding uniforms tile with ``x_ref`` — the PRNG stays outside the
    kernel (per-client fold_in streams, see ``core/transport.py``), the
    kernel fuses round + scale + superposition-sum + AWGN + normalize into
    one pass over the model dimension.
    """
    x = x_ref[...].astype(jnp.float32)          # [C, TM]
    u = u_ref[...].astype(jnp.float32)          # [C, TM]
    w = w_ref[...].astype(jnp.float32)          # [C, 1]
    d = d_ref[...].astype(jnp.float32)          # [C, 1]
    safe = jnp.where(d > 0, d, 1.0)
    q = jnp.where(d > 0, jnp.floor(x / safe + u) * d, x)
    acc = jnp.sum(q * w, axis=0, keepdims=True)  # [1, TM]
    acc = acc + ns_ref[0, 0] * z_ref[...].astype(jnp.float32)
    o_ref[...] = acc * ik_ref[0, 0]


def _sparse_aircomp_kernel(ns_ref, ik_ref, x_ref, w_ref, t_ref, z_ref,
                           o_ref):
    """Fused compress-aggregate tile (the sparse transport's hot pass).

    Same SMEM scalar layout as the quantized kernel (``ns_ref``/``ik_ref``
    both (1, 1) f32, traced). Per-client VMEM operands: ``w_ref`` [C, 1]
    mask/gain entries, ``t_ref`` [C, 1] per-client magnitude thresholds
    (the k-th largest |payload| coordinate — computed OUTSIDE the kernel by
    ``transport.sparse_thresholds``, the top-k does not tile over M). The
    kernel fuses threshold-compress + scale + superposition-sum + AWGN +
    normalize into one pass over the model dimension.
    """
    x = x_ref[...].astype(jnp.float32)          # [C, TM]
    w = w_ref[...].astype(jnp.float32)          # [C, 1]
    t = t_ref[...].astype(jnp.float32)          # [C, 1]
    c = jnp.where(jnp.abs(x) >= t, x, 0.0)
    acc = jnp.sum(c * w, axis=0, keepdims=True)  # [1, TM]
    acc = acc + ns_ref[0, 0] * z_ref[...].astype(jnp.float32)
    o_ref[...] = acc * ik_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def sparse_aircomp_pallas(x: jnp.ndarray, w: jnp.ndarray, thr: jnp.ndarray,
                          z: jnp.ndarray, *, noise_std, k,
                          interpret: bool = False) -> jnp.ndarray:
    """x [C, M]; w/thr [C]; z [M] -> sparse-compressed aggregate [M] fp32.

    Same blocking as :func:`quant_aircomp_pallas` (M padded to whole tiles,
    C whole in VMEM); ``noise_std``/``k`` ride as (1, 1) SMEM scalars. A
    zero-padded column passes the mask only when thr_c = 0 (an all-zero
    payload row) and then contributes w·0 = 0, so padding never leaks.
    """
    c, m = x.shape
    tile, pad = _blocking(m)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        z = jnp.pad(z, (0, pad))
    mp = m + pad
    grid = (mp // tile,)
    ns = jnp.asarray(noise_std, jnp.float32).reshape(1, 1)
    inv_k = (1.0 / jnp.asarray(k, jnp.float32)).reshape(1, 1)
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _sparse_aircomp_kernel,
        grid=grid,
        in_specs=[
            scalar_spec,
            scalar_spec,
            pl.BlockSpec((c, tile), lambda i: (0, i)),
            pl.BlockSpec((c, 1), lambda i: (0, 0)),
            pl.BlockSpec((c, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, mp), jnp.float32),
        interpret=interpret,
    )(ns, inv_k, x, w[:, None], thr[:, None], z[None])
    return out[0, :m]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_aircomp_pallas(x: jnp.ndarray, w: jnp.ndarray, d: jnp.ndarray,
                         u: jnp.ndarray, z: jnp.ndarray,
                         *, noise_std, k, interpret: bool = False
                         ) -> jnp.ndarray:
    """x/u [C, M]; w/d [C]; z [M] -> quantized aggregate [M] fp32.

    Same blocking as :func:`aircomp_pallas` (M padded to whole tiles, C
    whole in VMEM); ``noise_std``/``k`` ride as (1, 1) SMEM scalars per the
    kernel docstring. The zero-padded columns quantize to exact zeros (⌊0 + u⌋ = 0
    for u < 1), so padding never leaks into the output.
    """
    c, m = x.shape
    tile, pad = _blocking(m)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        u = jnp.pad(u, ((0, 0), (0, pad)))
        z = jnp.pad(z, (0, pad))
    mp = m + pad
    grid = (mp // tile,)
    ns = jnp.asarray(noise_std, jnp.float32).reshape(1, 1)
    inv_k = (1.0 / jnp.asarray(k, jnp.float32)).reshape(1, 1)
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _quant_aircomp_kernel,
        grid=grid,
        in_specs=[
            scalar_spec,
            scalar_spec,
            pl.BlockSpec((c, tile), lambda i: (0, i)),
            pl.BlockSpec((c, 1), lambda i: (0, 0)),
            pl.BlockSpec((c, 1), lambda i: (0, 0)),
            pl.BlockSpec((c, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, mp), jnp.float32),
        interpret=interpret,
    )(ns, inv_k, x, w[:, None], d[:, None], u, z[None])
    return out[0, :m]


@functools.partial(jax.jit, static_argnames=("interpret",))
def aircomp_pallas(x: jnp.ndarray, w: jnp.ndarray, z: jnp.ndarray,
                   *, noise_std, k, interpret: bool = False) -> jnp.ndarray:
    """x [N, M]; w [N]; z [M] -> aggregated [M] fp32.

    ``noise_std`` and ``k`` may be Python floats or traced jnp scalars. M is
    zero-padded to a whole number of tiles (:func:`_blocking`) and the pad
    columns are sliced off; N rides whole in VMEM (N=100 clients x 1024
    lanes x 4B = 400 KiB << 16 MiB VMEM).
    """
    n, m = x.shape
    tile, pad = _blocking(m)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        z = jnp.pad(z, (0, pad))
    mp = m + pad
    grid = (mp // tile,)
    ns = jnp.asarray(noise_std, jnp.float32).reshape(1, 1)
    inv_k = (1.0 / jnp.asarray(k, jnp.float32)).reshape(1, 1)
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _aircomp_kernel,
        grid=grid,
        in_specs=[
            scalar_spec,
            scalar_spec,
            pl.BlockSpec((n, tile), lambda i: (0, i)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, mp), jnp.float32),
        interpret=interpret,
    )(ns, inv_k, x, w[:, None], z[None])
    return out[0, :m]
