"""Profiler trace -> device busy time, idle gaps, executable and op times.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``. Device planes are the ``/device:TPU:<n>``
planes; their ``XLA Ops`` line holds one event per operation run and their
``XLA Modules`` line one event per executable run. Host spans are the
benchmark's own ``TraceAnnotation``s (names starting ``bench.``) on the
host plane, on the same clock to about a millisecond (on a v5e the device
events read about 1.2 ms behind the host span that launched them).

The traced window is the host span named ``bench.session``. Busy time is
the union of the op intervals inside it, per device, averaged over the
devices; idle share is one minus busy over the window.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.session"
HOST_PREFIX = "bench."
TOP = 10


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                     # averaged over devices
    n_devices: int
    modules: dict                     # executable name -> [runs, seconds]
    ops: dict                         # op kind -> seconds (all devices)
    collective_s: float               # all-reduce ops, seconds per device
    idle_gaps: list                   # [(host span, seconds)], longest first

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str):
    """-> (device planes {name: {line: [(name, start_ns, end_ns)]}},
    host spans [(name, start_ns, end_ns)])."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices[plane.name] = {
                line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                for line in plane.lines
                if line.name in ("XLA Ops", "XLA Modules")}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return devices, host


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(merged, lo: float, hi: float) -> list:
    """The idle [start, end) intervals of [lo, hi) between merged spans."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(spans, t: float) -> str:
    """The innermost host span open at time ``t`` (shortest that covers
    it), or ``"none"``."""
    open_ = [(e - s, n) for n, s, e in spans
             if s <= t < e and n != WINDOW_SPAN]
    return min(open_)[1] if open_ else "none"


def op_kind(name: str) -> str:
    """An op event's name is its HLO text on the TPU
    (``%convert.17 = bf16[28,1024,3072]{...} convert(...)``): keep the
    instruction without its number and its output shape without layout
    (``convert = bf16[28,1024,3072]``); a bare name loses its number
    (``fusion.123`` -> ``fusion``)."""
    head, sep, rest = name.partition(" = ")
    head = re.sub(r"[.:]\d+$", "", head.strip().lstrip("%"))
    if not sep:
        return head
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{head} = {shape.group(1)}" if shape else head


def leaves(events: list) -> list:
    """Drop the events that enclose later ones (a ``while`` spans the ops
    of its body): what is left ran no op inside it."""
    ev = sorted(events, key=lambda x: (x[1], -x[2]))
    return [e for i, e in enumerate(ev)
            if i + 1 == len(ev) or ev[i + 1][1] >= e[2]]


def summarize(devices: dict, host: list) -> Summary:
    window = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if len(window) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span in the "
                           f"trace, found {len(window)}")
    lo, hi = window[0]
    if not devices:
        raise RuntimeError("no /device:TPU plane in the trace")
    busy, modules, ops, coll, idle = 0.0, {}, {}, 0.0, []
    for lines in devices.values():
        op_events = [(n, s, e) for n, s, e in lines.get("XLA Ops", [])
                     if s < hi and e > lo]
        merged = union([(s, e) for _, s, e in op_events], lo, hi)
        busy += sum(e - s for s, e in merged)
        for n, s, e in leaves(op_events):
            k = op_kind(n)
            ops[k] = ops.get(k, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
            if k.startswith("all-reduce"):
                coll += (min(e, hi) - max(s, lo)) * 1e-9
        for n, s, e in lines.get("XLA Modules", []):
            if lo <= s < hi:
                m = modules.setdefault(n, [0, 0.0])
                m[0] += 1
                m[1] += (e - s) * 1e-9
        idle.extend(gaps(merged, lo, hi))
    nd = len(devices)
    idle.sort(key=lambda g: g[0] - g[1])
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / nd, n_devices=nd,
        modules=modules, ops=ops, collective_s=coll / nd,
        idle_gaps=[(label(host, (s + e) / 2), (e - s) * 1e-9)
                   for s, e in idle[:TOP]])


def breakdown(summary: Summary) -> dict:
    """The result line's ``breakdown``: the op kinds that took most device
    time, and the longest idle gaps by the host span open in them."""
    top = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
