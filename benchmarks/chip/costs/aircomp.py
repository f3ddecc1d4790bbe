"""Operations and bytes of the three Pallas AirComp kernels, per launch.

Each kernel makes one pass over a [C, M] f32 stack of client rows (C
clients, M model coordinates) and writes the [M] aggregate; ``batch`` is the
number of such stacks one launch covers (the sweep's vmapped points ×
seeds). Bytes are what the algorithm must move through HBM at least: the
unpadded operands, read once, and the output, written once.

- analog: reads x [C, M], w [C], z [M]; writes [M]; a multiply-add per
  element of x, plus the noise and 1/K per column;
- quantized: also reads the rounding uniforms u [C, M] and the grid steps
  [C]; divide, add, floor, multiply and multiply-add per element;
- sparse: reads the per-row thresholds [C] instead; compare, select and
  multiply-add per element.

All three are bound by memory on a TPU v5e (under 2 operations per byte).
"""
from __future__ import annotations

F32 = 4


def launch(kernel: str, batch: int, c: int, m: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one launch."""
    if kernel == "analog":
        ops, read = 2 * c * m + 3 * m, c * m + c + m
    elif kernel == "quantized":
        ops, read = 6 * c * m + 3 * m, 2 * c * m + 2 * c + m
    elif kernel == "sparse":
        ops, read = 4 * c * m + 3 * m, c * m + 2 * c + m
    else:
        raise ValueError(f"unknown AirComp kernel {kernel!r}")
    return float(batch * ops), float(batch * F32 * (read + m))


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's least time: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


EXACT_K = ("fedavg", "afl", "ca_afl", "greedy")


def sweep_launches(method: str, transport: str, stacks: int, cfg: dict,
                   rounds: int) -> list:
    """The kernel launches of one sweep group over ``rounds`` rounds, as
    ``[(kernel, batch, C, M, count)]``: the exact-K methods aggregate the K scheduled
    clients through the kernel of their transport once per round (digital
    shares the analog kernel); GCA aggregates its dense [N, M] stack
    without a kernel under analog transport."""
    m = cfg["data"]["dim"] * cfg["data"]["num_classes"] \
        + cfg["data"]["num_classes"]
    if method not in EXACT_K and transport in ("analog", "digital"):
        return []
    kernel = "analog" if transport == "digital" else transport
    c = cfg["clients_per_round"] if method in EXACT_K else cfg["num_clients"]
    return [(kernel, stacks, c, m, rounds)]
