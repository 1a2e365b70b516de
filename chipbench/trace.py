"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
device time by operation name, and idle gaps by the host span around them.

The traced window is the host span ``chipbench.traced`` (``Run.traced``).
Device time is the union of the intervals of the events on each device
plane's operation line, clipped to the window and averaged over devices;
an idle gap is a stretch of the window in which no operation runs, named
after the innermost host span that covers its midpoint.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from pathlib import Path

WINDOW_SPAN = "chipbench.traced"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."


def union(intervals):
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo, hi):
    """The stretches of [lo, hi) that ``busy`` (disjoint, sorted) leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
CONTAINERS = ("while", "conditional", "call")


def opcode(op: str) -> str | None:
    m = _OPCODE.search(op.partition(" = ")[2])
    return m.group(1) if m else None


def short_name(op: str) -> str:
    """``%name = shape opcode(...)`` -> ``name opcode`` (with a custom
    call's target); other names as they are."""
    head, sep, rest = op.partition(" = ")
    if not sep:
        return op
    code = opcode(op)
    name = head.lstrip("%") + (f" {code}" if code else "")
    t = _TARGET.search(rest)
    return name + (f" {t.group(1)}" if t else "")


def module_name(name: str) -> str:
    """``jit_commit(2225259626571999241)`` -> ``jit_commit``."""
    return name.split("(")[0]


def innermost(spans, t):
    """Name of the shortest span covering time t, or ``"(none)"``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "(none)"


def reduce(device_ops: dict, host_spans: list, window: tuple, top: int = 10,
           modules: dict | None = None) -> dict:
    """``device_ops``: {device: [(op name, start_ns, end_ns)]};
    ``host_spans``: [(name, start_ns, end_ns)]; ``window``: (lo, hi) ns;
    ``modules``: {device: [(program name, start_ns, end_ns)]}.
    Returns busy_s and window_s (busy averaged over devices), total device
    seconds by op name, [seconds, runs] by program, every idle gap of
    device 0 with its host span, and the ``top`` longest of each for the
    result's breakdown (ops by their short names)."""
    lo, hi = window
    progs = defaultdict(lambda: [0.0, 0])
    for dev, evs in (modules or {}).items():
        for name, s, e in evs:
            c = clip([(s, e)], lo, hi)
            if c:
                p = progs[module_name(name)]
                p[0] += (c[0][1] - c[0][0]) * 1e-9 / len(modules)
                p[1] += 1
    window_s = (hi - lo) * 1e-9
    by_op = defaultdict(float)
    busy_each, dev_gaps = [], []
    for i, dev in enumerate(sorted(device_ops)):
        ops = device_ops[dev]
        for name, s, e in ops:
            c = clip([(s, e)], lo, hi)
            if c:
                by_op[name] += (c[0][1] - c[0][0]) * 1e-9 / len(device_ops)
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_each.append(sum(e - s for s, e in busy) * 1e-9)
        if i == 0:
            dev_gaps = gaps(busy, lo, hi)
    busy_s = sum(busy_each) / len(busy_each) if busy_each else 0.0
    named = [(innermost(host_spans, (s + e) / 2), (e - s) * 1e-9)
             for s, e in dev_gaps]
    # loops and calls hold the ops that run inside them: list the ops
    ops_sorted = sorted(((n, t) for n, t in by_op.items()
                         if opcode(n) not in CONTAINERS),
                        key=lambda kv: -kv[1])
    return {"busy_s": busy_s, "window_s": window_s, "ops": dict(by_op),
            "modules": dict(progs),
            "gaps": named,
            "breakdown": {
                "device_ops": [[short_name(n), s]
                               for n, s in ops_sorted[:top]],
                "idle_gaps": [[n, s] for n, s in
                              sorted(named, key=lambda g: -g[1])[:top]]}}


def find_xplane(trace_dir) -> str:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path) -> tuple[dict, list, dict]:
    import jax
    return read_profile(jax.profiler.ProfileData.from_file(str(path)))


def read_profile(pd) -> tuple[dict, list, dict]:
    """(device_ops, host_spans, modules) from a ``jax.profiler.ProfileData``:
    the events of each TPU plane's ``XLA Ops`` line, the benchmark's own
    host spans (names starting ``chipbench.``) from every host thread, and
    each TPU plane's ``XLA Modules`` line (one event per program run)."""
    device_ops, host_spans, modules = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events] if line.name in (
                    OPS_LINE, MODULES_LINE) else None
                if line.name == OPS_LINE:
                    device_ops[plane.name] = evs
                elif line.name == MODULES_LINE:
                    modules[plane.name] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    return device_ops, host_spans, modules


def reduce_dir(trace_dir, top: int = 10) -> dict:
    """Read the newest trace under ``trace_dir`` and reduce it over the
    ``chipbench.traced`` window."""
    device_ops, host_spans, modules = read_xplane(find_xplane(trace_dir))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows or not device_ops:
        raise ValueError(f"trace holds no window span or no device ops: "
                         f"{len(windows)} windows, devices {list(device_ops)}")
    spans = [x for x in host_spans if x[0] != WINDOW_SPAN]
    return reduce(device_ops, spans, windows[0], top, modules)
