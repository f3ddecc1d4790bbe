"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX's own
``ProfileData``. The traced window is the host span named ``window`` that
the harness puts around the measured loop. On each device used:

- busy time: the union of the intervals in which an operation ran (the
  device plane's ``XLA Ops`` line), clipped to the window; the idle share is
  one minus busy over the window;
- kernel time and launches: the summed durations and the number of the
  operations whose instruction name (the HLO text before `` = ``) holds a
  given kernel name;
- lost events: the profiler keeps a bounded number of device events, and a
  long or busy window can lose some; ``lost_events`` says where the device
  operations fall short of the device's program executions (``XLA
  Modules``), of the kernel launches the driver made, or of the window's
  end, and the readers of device events then read nothing;
- idle gaps: the stretches of the window with no operation running, each
  attributed to the innermost host span open at its midpoint on the thread
  that ran the window.

Busy and kernel seconds are averaged over the devices used.
"""
from __future__ import annotations

import glob
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
# a device operation's event name is its HLO text; the breakdown keeps the
# head of it (instruction name, shape, opcode)
OP_NAME_CHARS = 100
# a complete trace: the operations cover at least this share of the time
# in which the device ran a program, and the last operation ends within
# this share of the window from its end
MIN_COVERAGE = 0.75
MAX_TAIL = 0.1


def instruction(name: str) -> str:
    """An operation event's instruction name: the HLO text before ``=``,
    without the operands, whose names may hold any kernel's."""
    return name.split(" = ", 1)[0]


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals inside [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclass
class Trace:
    window: tuple                      # (start_ns, end_ns)
    ops: list                          # per device: [(start, end, name)]
    host: list = field(default_factory=list)  # [(start, end, name)]
    modules: list = field(default_factory=list)  # per device, as ``ops``

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        lo, hi = self.window
        return sum(union_ns([(s, e) for s, e, _ in dev], lo, hi)
                   for dev in self.ops) / len(self.ops) / 1e9

    def kernel_s(self, name: str) -> float:
        """Device seconds of the operations whose instruction name holds
        ``name``."""
        lo, hi = self.window
        return sum(min(e, hi) - max(s, lo) for dev in self.ops
                   for s, e, n in dev
                   if name in instruction(n) and e > lo and s < hi
                   ) / len(self.ops) / 1e9

    def kernel_count(self, name: str) -> int:
        lo, hi = self.window
        return sum(1 for dev in self.ops for s, e, n in dev
                   if name in instruction(n) and e > lo and s < hi
                   ) // len(self.ops)

    def lost_events(self, launches: dict) -> list:
        """Why the device events look incomplete, or ``[]``: on any device,
        operations that cover less than ``MIN_COVERAGE`` of the time its
        programs ran, or that end more than ``MAX_TAIL`` of the window
        before its end; fewer launches of a kernel than ``launches``
        (``{kernel name: launches the driver made}``)."""
        lo, hi = self.window
        why = []
        for i, dev in enumerate(self.ops):
            ops = union_ns([(s, e) for s, e, _ in dev], lo, hi)
            mods = self.modules[i] if i < len(self.modules) else []
            ran = union_ns([(s, e) for s, e, _ in mods], lo, hi)
            if ran and ops < MIN_COVERAGE * ran:
                why.append(f"device {i}: operations cover {ops / ran:.3f} "
                           "of the time its programs ran")
            last = max((min(e, hi) for s, e, _ in dev if s < hi),
                       default=lo)
            if hi - last > MAX_TAIL * (hi - lo):
                why.append(f"device {i}: the last operation ends "
                           f"{(hi - last) / 1e9:.3f}s before the window")
        for name, want in launches.items():
            got = self.kernel_count(name)
            if got < want:
                why.append(f"{got} of {want} launches of {name}")
        return why

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the idle time by
        the host span open over it."""
        lo, hi = self.window
        by_op = defaultdict(float)
        for dev in self.ops:
            for s, e, n in dev:
                if e > lo and s < hi:
                    by_op[n[:OP_NAME_CHARS]] += (
                        (min(e, hi) - max(s, lo)) / len(self.ops))
        by_span = defaultdict(float)
        for s, e in gaps_ns([(s, e) for s, e, _ in self.ops[0]], lo, hi):
            by_span[self.host_span_at((s + e) / 2)] += e - s
        rank = lambda d: sorted(  # noqa: E731
            ([k, v / 1e9] for k, v in d.items()), key=lambda kv: -kv[1])
        return {"device_ops": rank(by_op)[:top],
                "idle_gaps": rank(by_span)[:top]}

    def host_span_at(self, t: float) -> str:
        best = None
        for s, e, n in self.host:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else "(no host span)"


def from_profile(pd, n_devices: int) -> Trace:
    """Build a :class:`Trace` from a ``jax.profiler.ProfileData``."""
    window, host_line = None, None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    host_line = line
    if window is None:
        raise ValueError("the trace has no host span named 'window'")
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in host_line.events if ev.name != WINDOW_SPAN]
    ops, modules = [], []
    for i in range(n_devices):
        lines = {OPS_LINE: [], MODULES_LINE: []}
        for plane in pd.planes:
            if plane.name not in (f"/device:TPU:{i}",
                                  f"/device:TPU:{i} (pid {i})"):
                continue
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] += [
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events]
        ops.append(lines[OPS_LINE])
        modules.append(lines[MODULES_LINE])
    if not any(ops):
        raise ValueError("the trace has no device operations")
    return Trace(window=window, ops=ops, host=host, modules=modules)


def load(trace_dir: Path, n_devices: int) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]), n_devices)


@dataclass
class Context:
    """What a per-layer metric reader gets: the reduced trace, the work the
    driver reports for the traced window, the device's peaks, and whether
    the trace kept every device event (a reader of device events reads
    nothing where it did not)."""
    trace: Trace
    work: dict
    peaks: dict
    workload: str
    complete: bool = True
