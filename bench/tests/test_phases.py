"""The program's spans and site scopes -> per-phase idle time, per-site
device time, queue wait and prefill share; on hand-made events, on an HLO
module compiled here, and on the small trace recorded on a TPU v5e (which
has no program spans).

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest bench/tests -q``.
"""

from __future__ import annotations

import math
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import phases  # noqa: E402
import trace_reduce as tr  # noqa: E402

SMALL = Path(__file__).parent / "data" / "small_trace.xplane.pb"
NS = 1_000_000_000


def test_idle_split_across_two_phases_and_under_no_span():
    spans = [("batcher.step", 0, 100), ("batcher.launch", 10, 30),
             ("batcher.sample", 30, 60)]
    # one gap across launch -> sample, one under the step alone, one after
    got = phases.idle_by_span([(20, 40), (70, 80), (120, 130)], spans)
    assert got == {"batcher.launch": 10, "batcher.sample": 10,
                   "batcher.step": 10, "none": 10}


def test_innermost_is_the_span_opened_last():
    spans = [("serving.step_live", 0, 50), ("bench.engine_step", 1, 50),
             ("batcher.step", 5, 45)]
    assert phases.idle_by_span([(0, 10)], spans) == {
        "serving.step_live": 1, "bench.engine_step": 4, "batcher.step": 5}


def _devices(ops, modules=()):
    return {"/device:TPU:0": {"XLA Ops": list(ops),
                              "XLA Modules": list(modules)}}


def test_phase_table_host_gap_and_share():
    host = [("bench.session", 0, 10 * NS),
            ("bench.wait_arrival", 8 * NS, 10 * NS)]
    program = [("batcher.step", 1 * NS, 4 * NS),
               ("batcher.launch", 1 * NS, 2 * NS),
               ("batcher.sample", 3 * NS, 4 * NS),
               ("batcher.step", 5 * NS, 7 * NS),
               ("batcher.launch", 5 * NS, 6 * NS)]
    ops = [("%fusion.1 = f32[8]", 2 * NS, 3 * NS),
           ("%fusion.1 = f32[8]", 6 * NS, 7 * NS)]
    table = phases.phase_table(_devices(ops), host, program)
    # idle: [0,1) none, [1,2) launch, [3,4) sample, [4,5) none,
    # [5,6) launch, [7,8) none, [8,10) waiting for an arrival
    assert table == pytest.approx({
        "none": 3.0, "batcher.launch": 2.0, "batcher.sample": 1.0,
        "bench.wait_arrival": 2.0})
    assert phases.program_idle_share(table) == pytest.approx(3 / 6)
    assert phases.host_gap_ms(table, program, 0, 10 * NS) == \
        pytest.approx(1e3 * 3.0 / 2)
    assert phases.host_gap_ms(table, [], 0, 10 * NS) is None
    # read with the device a second later: the idle moves with it
    late = phases.phase_table(_devices(ops), host, program, lag_ns=NS)
    assert late == pytest.approx({
        "none": 2.0, "batcher.launch": 2.0, "batcher.step": 2.0,
        "bench.wait_arrival": 2.0})


HLO = """HloModule jit__step_fn, entry_computation_layout={()->f32[8]}

%fused_computation.3 (param_0: f32[8,8], param_1: f32[8,8]) -> f32[8,8] {
  %param_0 = f32[8,8]{1,0} parameter(0)
  %convolution.1 = f32[8,8]{1,0} convolution(%param_0, %param_1), metadata={op_name="jit(_step_fn)/while/body/site.attn_q.fwd/dot_general"}
  ROOT %bitcast.2 = f32[8,8]{1,0} bitcast(%convolution.1), metadata={op_name="jit(_step_fn)/while/body/reshape"}
}

%fused_computation.7 (param_0.2: f32[8,8]) -> f32[8,8] {
  %param_0.2 = f32[8,8]{1,0} parameter(0)
  ROOT %multiply.1 = f32[8,8]{1,0} multiply(%param_0.2, %param_0.2)
}

%fused_computation.5 (param_0.1: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  %fusion.70 = f32[8,8]{1,0} fusion(%param_0.1), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(_step_fn)/while/body/closed_call/site.mlp_in.fwd/dot_general"}
  ROOT %convolution.25 = f32[8,8]{1,0} convolution(%fusion.70, %param_0.1), metadata={op_name="jit(_step_fn)/while/body/closed_call/site.mlp_out.fwd/dot_general"}
}

ENTRY %main.9 (p: f32[8,8]) -> f32[8] {
  %p = f32[8,8]{1,0} parameter(0)
  %convolution_bitcast_fusion = f32[8,8]{1,0} fusion(%p, %p), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(_step_fn)/while/body/reshape"}
  %dynamic-update-slice.4 = f32[8,8]{1,0} dynamic-update-slice(%p, %p), metadata={op_name="jit(_step_fn)/while/body/closed_call/kv_cache/dynamic_update_slice"}
  %dot.7 = f32[8,8]{1,0} dot(%p, %p), metadata={op_name="jit(_step_fn)/transpose(jvp(site.mlp_up.bwd.dA))/dot_general"}
  %copy.5 = f32[8,8]{1,0} copy(%p)
  %multiply_reduce_fusion.13 = f32[8,8]{1,0} fusion(%p), kind=kOutput, calls=%fused_computation.5, metadata={op_name="jit(_step_fn)/while/body/closed_call/site.mlp_out.fwd/dot_general"}
  ROOT %reduce.6 = f32[8]{0} reduce(%p), metadata={op_name="jit(_step_fn)/reduce_max"}
}
"""


def test_hlo_labels_through_fusions_and_autodiff():
    labels = phases.hlo_labels(HLO)
    assert labels["convolution_bitcast_fusion"] == "attn_q"   # its dot's
    assert labels["convolution.1"] == "attn_q"
    assert labels["dynamic-update-slice.4"] == "kv_cache"
    assert labels["dot.7"] == "mlp_up@bwd.dA"
    assert labels["copy.5"] is None and labels["reduce.6"] is None
    # one op that runs two sites is named for both
    assert labels["multiply_reduce_fusion.13"] == "mlp_in+mlp_out"
    assert phases.scope_label("jit(f)/site.lm_head.fwd/dot") == "lm_head"
    assert phases.scope_label("jit(f)/site.a.fwd/site.b.bwd.dB/x") == \
        "b@bwd.dB"


def test_site_table_adds_up_and_names_unmapped_ops():
    labels = phases.hlo_labels(HLO)
    modules = [("jit__step_fn(123)", 0, 10 * NS), ("jit_other(9)", 11 * NS,
                                                   12 * NS)]
    ops = [("%while.1 = (f32[8]) while(...)", 0, 9 * NS),      # encloses
           ("%convolution_bitcast_fusion = f32[8,8]{1,0} fusion(...)",
            0, 4 * NS),
           ("%dynamic-update-slice.4 = f32[8,8]{1,0} dynamic-update-slice",
            4 * NS, 5 * NS),
           ("%copy.5 = f32[8,8]{1,0} copy(...)", 5 * NS, 6 * NS),
           ("%fusion.99 = f32[8]{0} fusion(...)", 6 * NS, 8 * NS),  # not in HLO
           ("%reduce.6 = f32[8]{0} reduce(...)", 11 * NS, 12 * NS)]  # other run
    table = phases.site_table(_devices(ops, modules), labels, 0, 20 * NS)
    assert table == pytest.approx({
        "attn_q": 4.0, "kv_cache": 1.0, "other: copy = f32[8,8]": 1.0,
        "unmapped: fusion = f32[8]": 2.0})
    assert sum(table.values()) == pytest.approx(8.0)    # the step's leaves
    assert phases.gemm_share(table) == pytest.approx(50.0)
    assert phases.gemm_share({}) is None


def test_hlo_labels_of_a_module_compiled_here():
    import jax
    import jax.numpy as jnp

    def f(a, b):
        with jax.named_scope("site.mlp_in.fwd"):
            h = jnp.tanh(a @ b)
        with jax.named_scope("kv_cache"):
            return jax.lax.dynamic_update_slice_in_dim(h, h[:1] * 2, 1, 0)

    x = jnp.ones((8, 8))
    labels = phases.hlo_labels(jax.jit(f).lower(x, x).compile().as_text())
    assert {"mlp_in", "kv_cache"} <= set(labels.values())


def test_launch_delay_queue_wait_and_prefill_share():
    modules = [("jit__step_fn(1)", 3_000_000, 5_000_000),
               ("jit__step_fn(1)", 9_000_000, 11_000_000)]
    # the second run reads as starting before its launch did
    program = [("batcher.launch", 1_000_000, 2_000_000),
               ("batcher.launch", 9_200_000, 9_500_000)]
    pairs = phases.launch_pairs(_devices([], modules), program)
    assert [r for *_, r in pairs] == [3_000_000, 9_000_000]
    assert phases.launch_delays_ms(pairs) == pytest.approx([1.0, -0.5])
    assert phases.device_lag_ns(pairs) == 200_000
    assert phases.device_lag_ns(pairs[:1]) == 0
    stamps = [(0.5, 0.6), (1.0, 1.0), (1.5, None), (9.0, 9.5)]
    assert phases.queue_wait_p90_ms(stamps[:2], 0, 2) == pytest.approx(100.0)
    assert phases.queue_wait_p90_ms(stamps, 0, 2) == math.inf
    assert phases.queue_wait_p90_ms(stamps, 5, 6) is None
    before = {"prefill": 10, "prefill_last": 2, "decode": 30}
    after = {"prefill": 40, "prefill_last": 4, "decode": 48}
    assert phases.prefill_share(before, after) == pytest.approx(
        100 * 32 / 50)
    assert phases.prefill_share(after, after) is None


@pytest.mark.skipif(not SMALL.exists(), reason="no recorded trace")
def test_recorded_trace_has_no_program_spans():
    """A program that opens no phases reads as empty, not as an error."""
    devices, host = tr.load(str(SMALL))
    program = phases.load(str(SMALL))
    assert program == []
    table = phases.phase_table(devices, host, program)
    s = tr.summarize(devices, host)
    assert sum(table.values()) == pytest.approx(s.window_s - s.busy_s)
    assert phases.host_gap_ms(table, program, 0, 1 << 62) is None
    assert phases.site_table(devices, {}, 0, 1 << 62) == {}
