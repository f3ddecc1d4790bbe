"""Run one benchmark cell once on the chips of this machine.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Steps: refuse any device that is not a TPU listed in ``peaks.json``; make
the data and weights from ``--seed``; warm up the cell's own shapes
(reported as ``setup_s``); measure for ``--seconds``; check what the timed
path produced against the configuration's plain reference; print the
result as the last line of standard output.

With ``--trace 1`` the window runs under the JAX profiler (no longer than
the mix's ``trace_seconds``, where it sets one) and the line carries the
cell's per-layer metrics, the device's busy and window seconds
and a ``breakdown``; without it, the cell's end-to-end metrics. The numbers
compared for ``correct`` are printed, each beside its limit, as the last
lines of standard error and under ``checks`` in the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
from harness import BENCH, ROOT, load_json, load_module  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def reported(bench: dict, workload: str, key: str) -> list:
    """The entries of ``bench[key]`` that this cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])}
    if key == "end_to_end":
        return [m for m in bench[key] if m["name"] in e2e]
    return [m for m in bench[key]
            if workload in m.get("workloads", [workload] if m["moves"] in e2e
                                 else [])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, entry, config, traffic = harness.cell(args.workload)
    peaks = load_json(BENCH / "peaks.json")
    harness.configure_cache()
    import jax

    devices = harness.device_gate(jax, entry["chips"], peaks)
    counter = harness.CompileCounter(jax)
    cache_before = harness.cache_entries()
    driver = load_module(BENCH / "drivers" / f"{config['driver']}.py")
    drv = driver.Driver(config, traffic, args.seed)
    drv.setup()
    setup_s = time.perf_counter() - T_START
    setup_counts = counter.summary()
    log(f"set-up {setup_s:.3f}s: {setup_counts}; compile cache "
        f"{cache_before} -> {harness.cache_entries()} entries")

    counter.reset()
    seconds = args.seconds
    if args.trace:
        # a mix whose rounds hold very many device operations traces a
        # shorter window, so that reading the trace stays inside the run's
        # time limit
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        # host spans and the runtime's own events; no Python call tracer,
        # which would slow the host it measures
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        win = drv.window(seconds)
    if args.trace:
        jax.profiler.stop_trace()
    in_window = counter.summary()
    log(f"window {win['seconds']:.3f}s, {win['attempted']} attempted: "
        f"{in_window}")
    if in_window["compilations"]:
        log(f"WARNING: {in_window['compilations']} compilations inside "
            "the window")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"attempted": win["attempted"], "failed": win["failed"]}
    if args.trace:
        trace_mod = load_module(BENCH / "trace.py")
        tr = trace_mod.load(TRACE_DIR, len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        lost = tr.lost_events(win["work"].get("kernel_launches", {}))
        for why in lost:
            log(f"the trace lost device events: {why}")
        ctx = trace_mod.Context(trace=tr, work=win["work"],
                                peaks=peaks[device["kind"]],
                                workload=args.workload, complete=not lost)
        metrics = {}
        for m in reported(bench, args.workload, "per_layer"):
            value = load_module(BENCH / "metrics" / f"{m['name']}.py"
                                ).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = tr.breakdown()
    else:
        metrics = {}
        for m in reported(bench, args.workload, "end_to_end"):
            value = setup_s if m["name"] == "setup_s" else \
                win["metrics"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device)

    drv.release()
    gc.collect()
    t = time.perf_counter()
    try:
        checks = drv.check()
    except Exception as e:  # a crash of the comparison is a failed check
        log(f"the check failed to run: {type(e).__name__}: {e}")
        checks = [{"name": "check_failed_to_run", "value": 1, "limit": 0}]
    log(f"reference and comparison took {time.perf_counter() - t:.3f}s")
    result["correct"] = all(passes(c) for c in checks)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit <= {c['limit']!r}) "
              f"{'ok' if passes(c) else 'FAILED'}", file=sys.stderr,
              flush=True)
    order = ("correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks")
    print(json.dumps({k: result[k] for k in order if k in result}),
          flush=True)
    return 0


def passes(c: dict) -> bool:
    return c["value"] <= c["limit"]


if __name__ == "__main__":
    sys.exit(main())
