"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device metrics.

Device planes are named ``/device:<KIND>:<n>``; on a TPU their ``XLA Ops``
line holds one event per executed HLO op (a Pallas kernel appears under its
kernel's name) and their ``XLA Modules`` line one event per executed
program (``jit_<function name>(<id>)``). Host planes hold the Python
thread's spans. All times here are seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host threads whose spans say what the program was doing: the Python
# threads (the main one is named after the interpreter)
PYTHON_LINES = ("python", "serve-consumer", "bench-")

_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")
_HLO = re.compile(r"^%?([^\s=]+)\s*=")


def base_name(name: str) -> str:
    """Op or program name without its instance suffix (``fusion.12`` ->
    ``fusion``, ``jit_decode_step(3)`` -> ``jit_decode_step``). A TPU op
    event is named by its HLO text (``%_call.1 = bf16[...] custom-call(...)``);
    its name is the instruction's."""
    m = _HLO.match(name)
    if m:
        name = m.group(1)
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


@dataclasses.dataclass
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """Events of one traced window: per device, its ops and programs; and
    the host threads' spans."""
    ops: dict            # device plane name -> [Event]
    modules: dict        # device plane name -> [Event]
    host: list           # [Event] of the Python threads' lines

    @property
    def devices(self) -> list:
        return sorted(self.ops)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if line.name.startswith(PYTHON_LINES):
                    host.extend(_events(line))
    return Trace(ops=ops, modules=modules, host=host)


def _events(line) -> list:
    return [Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def span(trace: Trace) -> tuple:
    """(start, end) of the traced window: the first and last device event
    of any device."""
    evs = [e for per in trace.ops.values() for e in per]
    return min(e.start for e in evs), max(e.end for e in evs)


def busy_seconds(events, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] during which at least one event ran."""
    total = 0.0
    for s, e in union((e.start, e.end) for e in events):
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def device_busy(trace: Trace, lo: float, hi: float) -> float:
    """Busy seconds in [lo, hi], averaged over the traced devices."""
    devs = trace.devices
    return sum(busy_seconds(trace.ops[d], lo, hi) for d in devs) / len(devs)


def op_seconds(trace: Trace) -> dict:
    """Device seconds per op base name, averaged over devices."""
    out: dict = {}
    for d in trace.devices:
        for e in trace.ops[d]:
            k = base_name(e.name)
            out[k] = out.get(k, 0.0) + e.dur
    n = len(trace.devices)
    return {k: v / n for k, v in out.items()}


def op_events(trace: Trace, name: str) -> list:
    """Every device event whose base name is ``name``."""
    return [e for d in trace.devices for e in trace.ops[d]
            if base_name(e.name) == name]


def module_events(trace: Trace, contains: str) -> list:
    """Every program execution whose name contains ``contains``."""
    return [e for d in sorted(trace.modules) for e in trace.modules[d]
            if contains in e.name]


def idle_gaps(trace: Trace, lo: float, hi: float, top: int = 10) -> list:
    """The ``top`` longest gaps in [lo, hi] in which the first device ran
    nothing, each named by the innermost host span that covers its middle
    (``host idle`` where none does): [[name, seconds], ...]."""
    dev = trace.devices[0]
    busy = union((max(e.start, lo), min(e.end, hi)) for e in trace.ops[dev]
                 if e.end > lo and e.start < hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        covering = [h for h in trace.host if h.start <= mid <= h.end
                    and h.dur > 0]
        name = (min(covering, key=lambda h: h.dur).name if covering
                else "host idle")
        out.append([name, e - s])
    return out


def top_ops(trace: Trace, top: int = 10) -> list:
    secs = op_seconds(trace)
    return [[k, v] for k, v in sorted(secs.items(),
                                      key=lambda kv: -kv[1])[:top]]
