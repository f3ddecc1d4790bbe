"""The readings the limits of ``correct`` are set from, for one cell, in one
process on the chip:

    python benchmarks/chip/readings.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds ...] [--fault-seeds ...]

For each of ``--seeds``, a sound run (set-up, a short window, the check) gives
the program's numbers; for each of ``--control-seeds``, the reference run in
bfloat16 in the program's place gives the control's; for each of
``--fault-seeds``, every fault of ``faults.py`` planted in turn. The table
goes to ``chiprun_out/readings-<workload>.json`` and standard output. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
from harness import BENCH, ROOT, load_json, load_module  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    _, entry, config, traffic = harness.cell(args.workload)
    harness.configure_cache()
    import jax

    harness.device_gate(jax, entry["chips"], load_json(BENCH / "peaks.json"))
    faults = load_module(BENCH / "faults.py").FAULTS[config["driver"]]
    driver = load_module(BENCH / "drivers" / f"{config['driver']}.py")
    rows = []

    def one(seed, kind, fault=None, control=None):
        t = time.perf_counter()
        drv = driver.Driver(config, traffic, seed)
        if fault:
            with faults[fault]():
                drv.setup()
                drv.window(args.seconds)
        else:
            drv.setup()
            drv.window(args.seconds)
        drv.release()
        try:
            checks = drv.check(control=control)
        except Exception as e:  # a control that crashes has failed
            checks = [{"name": "crashed", "value": repr(e), "limit": None}]
        row = {"seed": seed, "kind": kind,
               "numbers": {c["name"]: c["value"] for c in checks},
               "seconds": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
        rows.append(row)
        return drv

    for s in args.seeds:
        drv = one(s, "program")
        if s in args.control_seeds:
            t = time.perf_counter()
            checks = drv.check(control="bfloat16")
            row = {"seed": s, "kind": "control_bfloat16",
                   "numbers": {c["name"]: c["value"] for c in checks},
                   "seconds": time.perf_counter() - t}
            print(json.dumps(row), flush=True)
            rows.append(row)
    for s in args.fault_seeds:
        for name in faults:
            one(s, f"fault_{name}", fault=name)
    out = ROOT / "chiprun_out" / f"readings-{args.workload}.json"
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
