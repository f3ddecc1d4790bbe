"""Dispatching wrapper for the AirComp aggregation kernel.

The choice between the Pallas kernel and the jnp oracle is made for the
platform the program is LOWERED for (``lax.platform_dependent``), not for the
process's default backend: the same traced round runs the compiled kernel
when it is placed on a TPU and the oracle when it is placed on a CPU device,
and a compile for a described TPU that is not attached picks the kernel too.

``use_pallas``: ``None`` (default) = compiled Pallas on TPU, the jnp oracle
elsewhere; ``True`` = Pallas everywhere (compiled on TPU, interpret mode
elsewhere, for correctness work); ``False`` = the jnp oracle. The Pallas
kernel accumulates in f32 only: buffers wider than f32 (float64 models) are
routed to the dtype-preserving jnp oracle regardless of backend, so enabling
x64 never silently truncates through the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.aircomp.kernel import (aircomp_pallas,
                                          quant_aircomp_pallas,
                                          sparse_aircomp_pallas)
from repro.kernels.aircomp.ref import (aircomp_ref, quant_aircomp_ref,
                                       sparse_aircomp_ref)


def _dispatch(pallas_fn, ref_fn, use_pallas, *arrays, noise_std, k):
    """Run ``pallas_fn`` or ``ref_fn`` on ``(*arrays, noise_std, k)`` per the
    module docstring's ``use_pallas`` rule."""
    if use_pallas is False or jnp.dtype(arrays[0].dtype).itemsize > 4:
        # f64 accumulation: the Pallas kernel is f32-only — keep precision
        return ref_fn(*arrays, noise_std, k)

    def kernel(interpret):
        return lambda *a: pallas_fn(*a[:-2], noise_std=a[-2], k=a[-1],
                                    interpret=interpret)

    other = kernel(True) if use_pallas else (lambda *a: ref_fn(*a))
    # noise_std/k may be traced: they ride as operands, never as closures
    return jax.lax.platform_dependent(
        *arrays, jnp.asarray(noise_std, jnp.float32),
        jnp.asarray(k, jnp.float32), tpu=kernel(False), default=other)


def aircomp_aggregate_flat(x: jnp.ndarray, w: jnp.ndarray, z: jnp.ndarray,
                           *, noise_std, k,
                           use_pallas: bool = None) -> jnp.ndarray:
    """Fused (sum_i w_i x_i + sigma z)/k over stacked flat updates [N, M].

    ``noise_std`` and ``k`` may be traced scalars (the simulator sweeps the
    former and computes the latter from the round's actual scheduled count);
    both paths accept them without recompiling per value.
    """
    return _dispatch(aircomp_pallas, aircomp_ref, use_pallas, x, w, z,
                     noise_std=noise_std, k=k)


def quant_aircomp_flat(x: jnp.ndarray, w: jnp.ndarray, d: jnp.ndarray,
                       u: jnp.ndarray, z: jnp.ndarray, *, noise_std, k,
                       use_pallas: bool = None) -> jnp.ndarray:
    """Fused quantize-aggregate (Σ_c w_c·Q_c(x_c) + σz)/k over flat payload
    rows [C, M] (the quantized transport's eq. (10) hot pass).

    ``d`` [C] per-client stochastic-rounding steps, ``u`` [C, M] pre-drawn
    rounding uniforms (see ``core/transport.quantize_rows`` for the key
    discipline). Dispatch as in the module docstring.
    """
    return _dispatch(quant_aircomp_pallas, quant_aircomp_ref, use_pallas,
                     x, w, d, u, z, noise_std=noise_std, k=k)


def sparse_aircomp_flat(x: jnp.ndarray, w: jnp.ndarray, thr: jnp.ndarray,
                        z: jnp.ndarray, *, noise_std, k,
                        use_pallas: bool = None) -> jnp.ndarray:
    """Fused compress-aggregate (Σ_c w_c·x_c·1{|x_c| ≥ thr_c} + σz)/k over
    flat payload rows [C, M] (the sparse transport's eq. (10) hot pass).

    ``thr`` [C] per-client magnitude thresholds (see
    ``core/transport.sparse_thresholds`` — the top-k runs outside the
    kernel, compression inside is one compare-and-mask). Dispatch as in the
    module docstring.
    """
    return _dispatch(sparse_aircomp_pallas, sparse_aircomp_ref, use_pallas,
                     x, w, thr, z, noise_std=noise_std, k=k)
