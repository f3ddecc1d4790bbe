"""The Pallas AirComp kernels' share of their roofline, in percent: for each
launch the least time its bytes and operations allow on this chip
(``costs/aircomp.py``), summed, over the summed device time of the kernel's
events in the trace. Bound by memory at these shapes."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import BENCH, load_module  # noqa: E402

KERNEL = "aircomp"


def read(ctx):
    launches = ctx.work.get("aircomp_launches") or []
    device_s = ctx.trace.kernel_s(KERNEL)
    if not ctx.complete or not launches or device_s <= 0:
        return None
    costs = load_module(BENCH / "costs" / "aircomp.py")
    least = sum(count * costs.least_seconds(*costs.launch(k, b, c, m),
                                            ctx.peaks)
                for k, b, c, m, count in launches)
    return 100.0 * least / device_s
