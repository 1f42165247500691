"""The serving path explains its own time: phase spans on the profiler's
clock, GEMM sites named in the compiled step, slot-steps counted by the
batcher, queue wait stamped by the frontend."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import dispatch
from repro.core.dispatch import FDP91, MXU_FP32, GemmSite
from repro.launch.batching import SLOT_KINDS, ContinuousBatcher, Request
from repro.models import init
from repro.obs import phase, recorder, span
from repro.serving import (BucketedEnginePool, PlanRouter, RoutedFrontend,
                           ServeRequest)

PLANS_DIR = os.path.join(os.path.dirname(__file__), "..", "examples", "plans")
STEP_PHASES = ("batcher.fill", "batcher.prepare", "batcher.launch",
               "batcher.sample", "batcher.deliver")


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen3-0.6b").reduced()
    return cfg, init(cfg, jax.random.key(0))


def _host_events(log_dir: str) -> list:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _scopes(hlo: str) -> set:
    """Scope names in the HLO's op_name metadata, without the transforms
    autodiff wraps them in (``transpose(jvp(site.g.bwd.dA))``)."""
    return {re.sub(r"^(?:\w+\()+|\)+$", "", part)
            for op in re.findall(r'op_name="([^"]*)"', hlo)
            for part in op.split("/")}


def test_step_phases_nest_in_the_profiler_trace(tiny, tmp_path):
    cfg, params = tiny
    eng = ContinuousBatcher(cfg, params, n_slots=2, max_len=32,
                            warmup=MXU_FP32)
    for i in range(2):
        eng.submit(Request(i, [3, 4, 5], max_new=3))
    recorder().clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("serving.outer"):
            for _ in range(3):
                assert eng.step()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    steps = [e for e in events if e[0] == "batcher.step"]
    assert len(steps) == 3
    for _, lo, hi in steps:
        inside = {n for n, s, e in events if lo <= s and e <= hi}
        assert set(STEP_PHASES) <= inside
    # a scoped span is a profiler annotation too, around the steps ...
    (outer,) = [e for e in events if e[0] == "serving.outer"]
    assert all(outer[1] <= s and e <= outer[2] for _, s, e in steps)
    # ... and still a recorded span; the phases are not
    assert [e["name"] for e in recorder().events()] == ["serving.outer"]


@pytest.mark.parametrize("policy", [MXU_FP32, FDP91], ids=["native", "fdp91"])
def test_every_site_is_a_scope_in_the_compiled_step(tiny, policy):
    cfg, params = tiny
    dispatch.reset_sites_seen()
    eng = ContinuousBatcher(cfg, params, n_slots=2, max_len=16,
                            warmup=policy)
    sites = dispatch.sites_seen()
    assert {"attn_qk", "attn_av", "lm_head"} <= sites
    scopes = _scopes(eng.step_hlo_text())
    assert {GemmSite.parse(k).scope for k in sites} <= scopes
    assert "kv_cache" in scopes


def test_bias_add_is_charged_to_its_site():
    """A projection's bias add runs under its site's scope, not beside it."""
    cfg = get_config("qwen1.5-4b").reduced()
    eng = ContinuousBatcher(cfg, init(cfg, jax.random.key(0)), n_slots=2,
                            max_len=16, warmup=MXU_FP32)
    assert re.search(r'add\(.*op_name="[^"]*/site\.attn_q\.fwd/add"',
                     eng.step_hlo_text())


def test_backward_sites_are_scopes_too():
    """fwd and both bwd GEMMs of gemm, grouped_qk/av and ragged_gemm run
    under their site's scope (read from the lowered module's locations: the
    CPU compiler rewrites some batched dots without their metadata)."""
    k = jax.random.split(jax.random.key(1), 6)
    a, b = jax.random.normal(k[0], (4, 8)), jax.random.normal(k[1], (8, 4))
    q = jax.random.normal(k[2], (1, 2, 2, 3, 8))
    kk = jax.random.normal(k[3], (1, 2, 5, 8))
    x, w = jax.random.normal(k[4], (6, 8)), jax.random.normal(k[5], (2, 8, 4))
    gs = jnp.array([4, 2], jnp.int32)

    def loss(a, b, q, kk, x, w):
        # sin, not a bare sum: ones for cotangents let XLA fold a dot away
        s = jnp.sin(dispatch.gemm(a, b, site="g")).sum()
        p = jnp.sin(dispatch.grouped_qk(q, kk, site="qk"))
        s += jnp.sin(dispatch.grouped_av(p, kk, site="av")).sum()
        return s + jnp.sin(dispatch.ragged_gemm(x, w, gs, site="moe")).sum()

    with dispatch.use_policy(MXU_FP32):
        text = jax.jit(jax.value_and_grad(loss, argnums=range(6))).lower(
            a, b, q, kk, x, w).as_text(debug_info=True)
    want = {GemmSite.parse(f"{n}{p}").scope for n in ("g", "qk", "av", "moe")
            for p in ("", "@bwd.dA", "@bwd.dB")}
    assert "site.g.bwd.dA" in want
    assert set(re.findall(r"site\.\w+\.(?:fwd|bwd\.d[AB])", text)) == want


def test_batcher_counts_match_requests_and_step_probe(tiny):
    cfg, params = tiny
    eng = ContinuousBatcher(cfg, params, n_slots=3, max_len=64,
                            warmup=MXU_FP32)
    reqs = [Request(i, [2 + i] * (2 + i % 3), max_new=2 + i % 4)
            for i in range(6)]
    for r in reqs:
        eng.submit(r)
    # the benchmark's arithmetic, from outside: slots live around each step
    probe_steps = probe_slots = 0
    while True:
        before = [r for r in eng.active if r is not None]
        if not eng.step():
            break
        after = [r for r in eng.active if r is not None]
        probe_steps += 1
        probe_slots += len({id(r) for r in before + after})
    assert all(r.done for r in reqs) and not eng.queue
    c = eng.slot_steps
    assert set(c) == set(SLOT_KINDS)
    assert c["prefill"] + c["prefill_last"] == sum(r.prefill_tokens
                                                   for r in reqs)
    assert c["prefill_last"] + c["decode"] == sum(r.decode_tokens
                                                  for r in reqs)
    assert sum(c.values()) == sum(r.steps for r in reqs) == probe_slots
    assert eng.steps_run == probe_steps
    occupancy = sum(c.values()) / (eng.n_slots * eng.steps_run)
    assert occupancy == probe_slots / (eng.n_slots * probe_steps)


def test_queue_wait_is_stamped_on_the_request_span():
    cfg = get_config("paper-mlp").reduced()
    params = init(cfg, jax.random.key(0))
    router = PlanRouter.from_manifest(PLANS_DIR, arch="paper-mlp")
    front = RoutedFrontend(BucketedEnginePool(cfg, params, "2x32"), router)
    recorder().clear()
    # four requests on two slots: two of them wait for a slot
    comps = [front.submit(ServeRequest(uid=i, prompt=[3 + i, 7, 1],
                                       max_new=4, workload="chat"))
             for i in range(4)]
    assert all(c.admitted_at is None for c in comps)
    front.run()
    assert all(c.ok for c in comps)
    waits = [c.admitted_at - c.submitted_at for c in comps]
    assert min(waits) >= 0
    spans = {e["args"]["uid"]: e["args"] for e in recorder().events()
             if e["name"] == "serving.request"}
    for c, wait in zip(comps, waits):
        assert spans[c.request.uid]["queue_ms"] == pytest.approx(1e3 * wait)
    assert max(waits) > min(waits)


def test_phases_record_nothing_without_a_profiler(tiny):
    cfg, params = tiny
    eng = ContinuousBatcher(cfg, params, n_slots=2, max_len=32,
                            warmup=MXU_FP32)
    eng.submit(Request(0, [1, 2], max_new=3))
    recorder().clear()
    with phase("serving.activate") as got:
        assert got is None
    while eng.step():
        pass
    eng.reset_cache()
    assert recorder().events() == []
    assert recorder().dropped == 0
