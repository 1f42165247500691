"""The trace -> metric reduction, on hand-made events and on a small trace
recorded on a TPU v5e (``data/small_trace.xplane.pb``: three runs each of
two jitted programs inside a ``bench.session`` span, with ``bench.f`` and
``bench.sleep`` host spans).

Run by hand: ``python -m pytest bench/tests -q``.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402

SMALL = Path(__file__).parent / "data" / "small_trace.xplane.pb"


def test_union_merges_and_clips():
    got = tr.union([(5, 8), (0, 3), (2, 4), (7, 12), (20, 30)], 1, 25)
    assert got == [[1, 4], [5, 12], [20, 25]]


def test_gaps_between_and_at_the_edges():
    assert tr.gaps([[1, 4], [5, 12]], 0, 15) == [(0, 1), (4, 5), (12, 15)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def test_label_is_the_innermost_open_span():
    spans = [("bench.session", 0, 100), ("bench.run", 10, 50),
             ("bench.submit", 20, 30)]
    assert tr.label(spans, 25) == "bench.submit"
    assert tr.label(spans, 40) == "bench.run"
    assert tr.label(spans, 70) == "none"


def test_summarize_hand_made():
    ns = 1_000_000_000
    devices = {"/device:TPU:0": {
        "XLA Ops": [("fusion.1", 1 * ns, 2 * ns), ("fusion.2", 2 * ns, 3 * ns),
                    ("all-reduce.3", 5 * ns, 6 * ns),
                    ("fusion.9", 11 * ns, 12 * ns)],         # after the window
        "XLA Modules": [("jit__step_fn", 1 * ns, 3 * ns),
                        ("jit_other", 5 * ns, 6 * ns)]}}
    host = [("bench.session", 0, 10 * ns), ("bench.wait_arrival", 6 * ns,
                                              10 * ns)]
    s = tr.summarize(devices, host)
    assert s.window_s == 10 and s.busy_s == 3 and s.n_devices == 1
    assert s.idle_frac == pytest.approx(0.7)
    assert s.modules == {"jit__step_fn": [1, 2.0], "jit_other": [1, 1.0]}
    assert s.ops == {"fusion": 2.0, "all-reduce": 1.0}
    assert s.collective_s == 1.0
    assert s.idle_gaps[0] == ("bench.wait_arrival", 4.0)
    assert sorted(g for _, g in s.idle_gaps) == [1.0, 2.0, 4.0]
    b = tr.breakdown(s)
    assert b["device_ops"][0] == ["fusion", 2.0]
    assert len(b["idle_gaps"]) == 3


@pytest.mark.skipif(not SMALL.exists(), reason="no recorded trace")
def test_recorded_trace_against_brute_force():
    devices, host = tr.load(str(SMALL))
    s = tr.summarize(devices, host)
    (lo, hi), = [(a, b) for n, a, b in host if n == tr.WINDOW_SPAN]
    ops = devices["/device:TPU:0"]["XLA Ops"]
    # busy time by brute force: the set of covered 1 ns ticks, in a
    # sparse form (sorted boundary sweep with a counter)
    edges = sorted([(max(a, lo), 1) for _, a, b in ops if b > lo and a < hi]
                   + [(min(b, hi), -1) for _, a, b in ops
                      if b > lo and a < hi])
    busy, depth, last = 0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert 0 < s.busy_s < s.window_s
    mods = devices["/device:TPU:0"]["XLA Modules"]
    assert sum(v[0] for v in s.modules.values()) == sum(
        1 for _, a, _ in mods if lo <= a < hi)
    # each program ran three times; the device plane's clock reads about a
    # millisecond behind the host's, so the first run may fall before the
    # session span opened on the host
    assert len(mods) == 6 and sum(v[0] for v in s.modules.values()) >= 5
    assert any(n == "bench.sleep" for n, _ in s.idle_gaps)
