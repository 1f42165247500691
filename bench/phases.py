"""The program's own names in a profiler trace: its phase spans on the host
plane, and the GEMM-site scopes of the compiled decode step's ops.

``trace_reduce`` reads the benchmark's spans (``bench.*``) and the device
planes. This module reads, from the same ``.xplane.pb``, the spans the
program opens itself (``batcher.*``, ``serving.*``: ``repro.obs.phase`` and
scoped ``repro.obs.span``), and maps each device op to the GEMM site it ran
for. Op events on a TPU carry only the HLO instruction's text, so the map
goes instruction name -> ``op_name`` (from the compiled step's optimized
HLO text, ``ContinuousBatcher.step_hlo_text()``) -> the ``site.<name>.<phase>
[.<operand>]`` scope in it (``repro.core.dispatch.GemmSite.scope``), or the
``kv_cache`` scope of the cache writes.

Against a program that opens no such spans or scopes, every reading here
comes out empty, and the functions that derive a number return ``None``.
"""

from __future__ import annotations

import bisect
import math
import re
from collections import Counter

import trace_reduce as tr
from serve import pct

PROGRAM_PREFIXES = ("batcher.", "serving.")
LAUNCH_SPAN = "batcher.launch"
WAIT_SPAN = "bench.wait_arrival"
STEP_EXECUTABLE = "_step_fn"
KV_SCOPE = "kv_cache"
SITE_SCOPE = re.compile(r"site\.(\w+)\.(fwd|bwd\.d[AB]|bwd)(?![\w.])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[\w.\-]+(?:,\s*%[\w.\-]+)*)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HEAVY = (" dot(", " convolution(")


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def load(path: str) -> list:
    """The program's spans on the host planes: [(name, start_ns, end_ns)]."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if is_program(e.name)]


# -- idle time by the span open in it ---------------------------------------
def idle_by_span(idle: list, spans: list) -> dict:
    """Idle time (the same unit as the intervals) by the innermost span
    open in it, by exact interval intersection: a gap split across two
    phases counts for each its own part; time under no span is ``"none"``.
    ``idle`` is [(start, end)], ``spans`` [(name, start, end)]; the
    innermost of the open spans is the one that opened last."""
    points = []
    for i, (_, s, e) in enumerate(spans):
        if e > s:
            points += [(s, 1, i), (e, -1, i)]
    for s, e in idle:
        if e > s:
            points += [(s, 2, None), (e, -2, None)]
    points.sort(key=lambda p: p[0])
    out, open_, idle_depth, last = {}, {}, 0, None
    for t, kind, i in points:
        if idle_depth > 0 and last is not None and t > last:
            inner = max(open_.items(), key=lambda kv: kv[1],
                        default=(None, None))[0]
            name = spans[inner][0] if inner is not None else "none"
            out[name] = out.get(name, 0) + (t - last)
        if kind == 1:
            open_[i] = (spans[i][1], -spans[i][2])
        elif kind == -1:
            open_.pop(i, None)
        else:
            idle_depth += kind // 2
        last = t
    return out


def phase_table(devices: dict, host: list, program: list,
                lag_ns: int = 0) -> dict:
    """Idle seconds of the traced window (the ``bench.session`` span) by
    the innermost benchmark or program span open in them, averaged over
    the devices. ``lag_ns`` moves the device events that much later onto
    the host's clock (``device_lag_ns``)."""
    (lo, hi), = [(s, e) for n, s, e in host if n == tr.WINDOW_SPAN]
    spans = [sp for sp in host + program if sp[0] != tr.WINDOW_SPAN]
    table = {}
    for lines in devices.values():
        busy = tr.union([(s + lag_ns, e + lag_ns)
                         for _, s, e in lines.get("XLA Ops", [])], lo, hi)
        for name, ns in idle_by_span(tr.gaps(busy, lo, hi), spans).items():
            table[name] = table.get(name, 0.0) + ns * 1e-9 / len(devices)
    return table


def program_idle_share(table: dict) -> float:
    """Share of the idle time not spent waiting for an arrival that lies
    under a program span (``None`` where there is no such idle time)."""
    rest = sum(v for k, v in table.items() if k != WAIT_SPAN)
    return (sum(v for k, v in table.items() if is_program(k)) / rest
            if rest > 0 else None)


def host_gap_ms(table: dict, program: list, lo: int, hi: int):
    """Device-idle time under a program span, per engine step (steps
    counted by their ``batcher.launch`` spans that start in [lo, hi))."""
    steps = sum(1 for n, s, _ in program if n == LAUNCH_SPAN and lo <= s < hi)
    if not steps:
        return None
    return 1e3 * sum(v for k, v in table.items() if is_program(k)) / steps


# -- device op time by GEMM site ---------------------------------------------
def scope_label(op_name: str):
    """The site key an ``op_name`` lies under (the innermost site scope;
    autodiff's ``transpose(jvp(...))`` wrappers are seen through), else
    ``"kv_cache"`` under the cache-write scope, else ``None``."""
    sites = SITE_SCOPE.findall(op_name)
    if sites:
        name, phase = sites[-1]
        return name if phase == "fwd" else f"{name}@{phase}"
    return KV_SCOPE if KV_SCOPE in op_name.split("/") else None


def hlo_labels(hlo_text: str) -> dict:
    """Instruction name -> site key / ``"kv_cache"`` / ``None``, from an
    optimized HLO module's text. A fusion takes its own label together
    with those of the work it fuses (the dots, convolutions and inner
    fusions of the computations it calls), joined by ``+`` where one op
    runs several sites (``mlp_in+mlp_out``); with none of them, the most
    common label among its instructions (a fusion is named for its root,
    which may lie outside every scope)."""
    own, calls, comps, work, comp = {}, {}, {}, set(), None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = comps.setdefault(c.group(1), [])
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = scope_label(op.group(1)) if op else None
        called = _CALLS.search(line)
        if called:
            calls[name] = [c.strip().lstrip("%")
                           for c in called.group(1).split(",")]
        if called or any(h in line for h in _HEAVY):
            work.add(name)
        if comp is not None:
            comp.append(name)
    memo = {}

    def label(name: str, depth: int = 0):
        if name not in calls or depth > 4:
            return own.get(name)
        if name not in memo:
            inner = [(n, label(n, depth + 1))
                     for c in calls[name] for n in comps.get(c, ())]
            fused = {part for n, v in inner if n in work and v is not None
                     for part in v.split("+")}
            if own.get(name) is not None:
                fused.add(own[name])
            votes = Counter(v for _, v in inner if v is not None)
            memo[name] = ("+".join(sorted(fused)) if fused else
                          votes.most_common(1)[0][0] if votes else None)
        return memo[name]

    return {name: label(name) for name in own}


def instruction(event_name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def site_table(devices: dict, labels: dict, lo: int, hi: int,
               executable: str = STEP_EXECUTABLE) -> dict:
    """Device seconds of the leaf ops run inside ``executable``'s runs in
    [lo, hi), by label: a site key, ``kv_cache``, ``other: <op kind>`` for
    an op under no scope, ``unmapped: <op kind>`` for an instruction the
    HLO text does not have. The rows add up to the executable's leaf-op
    time."""
    out = {}
    for lines in devices.values():
        runs = sorted((s, e) for n, s, e in lines.get("XLA Modules", [])
                      if executable in n and s < hi and e > lo)
        starts = [s for s, _ in runs]
        ops = [(n, s, e) for n, s, e in lines.get("XLA Ops", [])
               if s < hi and e > lo]
        for n, s, e in tr.leaves(ops):
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= runs[k][1]:
                continue                       # not inside a run of the step
            name = instruction(n)
            if name not in labels:
                row = f"unmapped: {tr.op_kind(n)}"
            else:
                row = labels[name] or f"other: {tr.op_kind(n)}"
            out[row] = out.get(row, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
    return out


def gemm_share(table: dict):
    """Percent of the step's leaf-op time that ran under a GEMM site."""
    total = sum(table.values())
    if not total:
        return None
    sites = sum(v for k, v in table.items()
                if k != KV_SCOPE and not k.startswith(("other:", "unmapped:")))
    return 100.0 * sites / total


def launch_pairs(devices: dict, program: list,
                 executable: str = STEP_EXECUTABLE) -> list:
    """Each ``batcher.launch`` span with the run of ``executable`` whose
    start lies nearest the span's end (the run it launched, where the two
    clocks disagree by less than half a step): [(launch start, launch end,
    run start)]."""
    runs = sorted(s for lines in devices.values()
                  for n, s, _ in lines.get("XLA Modules", [])
                  if executable in n)
    out = []
    for n, s, e in program:
        if n == LAUNCH_SPAN and runs:
            k = bisect.bisect_left(runs, e)
            near = min(runs[max(0, k - 1):k + 1], key=lambda r: abs(r - e))
            out.append((s, e, near))
    return out


def launch_delays_ms(pairs: list) -> list:
    """Run start minus launch end, per launch (``launch_pairs``): below
    zero where the device's events read earlier than the host's."""
    return [(r - e) * 1e-6 for _, e, r in pairs]


def device_lag_ns(pairs: list, q: float = 0.95) -> int:
    """How far the device's events read behind the host's at least: a run
    cannot start before the launch that enqueued it began, so the trace's
    clocks lag by at least launch start minus run start, taken at the
    ``q`` quantile (nearest rank) over the launches (0 where no run reads early)."""
    lags = [s - r for s, _, r in pairs]
    return max(0, pct(lags, q)) if lags else 0


# -- counts and queue wait from the program's own numbers ---------------------
def queue_wait_p90_ms(stamps: list, lo: float, hi: float):
    """Nearest-rank p90 of admit - submit, in ms, over the requests
    submitted in [lo, hi): ``stamps`` is [(submitted_at, admitted_at)]
    ``perf_counter`` readings; one never admitted waits forever."""
    waits = [(a - s) if a is not None else math.inf
             for s, a in stamps if s is not None and lo <= s < hi]
    return 1e3 * pct(waits, 0.90) if waits else None


def prefill_share(before: dict, after: dict):
    """Percent of the slot-steps fed between two snapshots of a batcher's
    ``slot_steps`` that fed a prompt token."""
    d = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    total = sum(d.values())
    if not total:
        return None
    return 100.0 * (d.get("prefill", 0) + d.get("prefill_last", 0)) / total
