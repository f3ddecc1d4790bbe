"""What every cell shares: finding its files by name, the device gate, the
compile cache and counting compilations.

A configuration, a traffic mix, a tier driver, a cost model and a per-layer
metric are each a file of their own, found by the name that
``BENCHMARK.json`` gives it:

- ``configs/<config>.json`` and its plain reference ``configs/<config>.ref.py``
- ``traffic/<traffic>.json``
- ``drivers/<driver>.py``, the driver the configuration names
- ``costs/<name>.py``
- ``metrics/<metric>.py``, a reader with ``read(ctx)`` that returns a number
  or ``None`` where it finds nothing to read
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
# the persistent compilation cache: a fixed path inside the checkout, so the
# second run of a cell finds what the first compiled
CACHE_DIR = ROOT / ".jax_cache"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (names may hold dots and dashes), once."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """``(benchmark, workload entry, configuration, traffic)`` of a cell."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    config = load_json(BENCH / "configs" / f"{entry['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    return bench, entry, config, traffic


def enable_program_imports() -> None:
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def configure_cache() -> str:
    """JAX's persistent cache in the checkout's ``.jax_cache/``, every
    program kept. Called before JAX is imported: the variable set here is
    what JAX reads at import and what the program's
    ``enable_compile_cache`` honours, so the program takes this directory
    and not one of its own or of the machine's."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    enable_program_imports()
    import jax
    from repro.utils import compile_cache

    path = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cache_entries() -> int:
    from repro.utils import compile_cache

    return compile_cache.cache_entries(str(CACHE_DIR))


def device_gate(jax, chips: int, peaks: dict):
    """The TPU devices the cell runs on; exits without a result otherwise."""
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: the first device is {devs[0].platform!r}")
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts JAX's traces, lowerings, persistent-cache hits and backend
    compilations between ``reset`` calls (``jax.monitoring`` events). A
    cache hit also reports a backend-compile event, so compilations are the
    difference."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
              "/jax/core/compile/backend_compile_duration": "backend",
              "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self, jax):
        self.counts = {}
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        jax.monitoring.register_event_listener(self._seen)

    def _seen(self, name, *args, **kwargs):
        key = self.EVENTS.get(name)
        if key:
            self.counts[key] = self.counts.get(key, 0) + 1

    def reset(self) -> None:
        self.counts = {}

    def summary(self) -> dict:
        c = {k: self.counts.get(k, 0) for k in self.EVENTS.values()}
        c["compilations"] = c["backend"] - c["cache_hits"]
        return c
