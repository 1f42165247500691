#!/usr/bin/env python
"""Plan-zoo refresh: calibrate + search a PrecisionPlan for every architecture.

The paper tailors one GEMM; ``repro.numerics`` tailors one model; this sweep
tailors the whole zoo. Per architecture it

  1. **calibrates** — one forward pass under the fast fp32 native policy with
     the dispatch trace hook installed, recording every call-site's operand
     statistics and samples (transformer attn/mlp sites, MoE router + expert
     sites, SSM scan-block sites, multimodal prefix sites);
  2. **persists the trace** — a versioned ``CalibrationTrace`` JSON keyed by
     the config fingerprint, so later refreshes (and ``--check`` CI runs)
     search from the saved trace without re-calibrating;
  3. **searches** — the per-site (format x accumulator x backend) Pareto
     sweep against the bit-exact FDP oracle, validated end-to-end vs the
     uniform 91-bit policy;
  4. **emits** ``examples/plans/<arch>.json`` plus a ``MANIFEST.json``
     summarizing modeled-energy savings and validated bits per arch — the
     artifacts the CI ``plan-zoo`` lane guards.

``--phases fwd,bwd`` (the default) additionally calibrates through a
``value_and_grad`` training-loss step, so every gradient GEMM is traced and
searched under its own phase-qualified site (``attn_qk@bwd.dA``) and the
emitted v2 plan carries backward assignments plus a modeled fwd/bwd energy
split in the MANIFEST.

End-to-end acceptance runs through the ``repro.workloads`` scenario zoo:
``--validators grad,logits,repro`` (the default) scores every assembled
policy on a real training-gradient step (vs the 91-bit-bwd reference), logit
fidelity (vs the uniform 91-bit oracle — this is what ``validated_bits``
records), and K-reorder bit-stability; failing workloads drive the greedy
upgrade loop toward the sites they attribute the deficit to (the gradient
workload upgrades ``@bwd`` sites). Every report is serialized into the plan
(``meta.validation``) and summarized per arch in the MANIFEST. The hostile
ill-conditioned ``solve`` workload is opt-in (``--validators solve,...``).

Usage:
    PYTHONPATH=src python scripts/refresh_plans.py --reduced            # all
    PYTHONPATH=src python scripts/refresh_plans.py --only dbrx_132b --reduced
    JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/refresh_plans.py --reduced --jobs 3
    PYTHONPATH=src python scripts/refresh_plans.py --only paper_mlp --reduced \
        --check     # recompute from the saved trace, compare to checked-in
    PYTHONPATH=src python scripts/refresh_plans.py --schedules
        # refresh the GemmPlan schedule zoo (examples/plans/schedules/)
    PYTHONPATH=src python scripts/refresh_plans.py --envelopes
        # derive meta["envelope"] for every checked-in plan from its saved
        # trace (no recalibration, no search) — the live-monitor boundary
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

MANIFEST_VERSION = 1
MANIFEST_KIND = "repro.numerics.PlanManifest"
DEFAULT_OUT = os.path.join(os.path.dirname(__file__), os.pardir,
                           "examples", "plans")

# Calibration shape: small enough for CPU, large enough that every scanned
# site fires and operand extremes are representative. One source of truth
# (repro.workloads.base) shared with WorkloadContext.for_model, so the CI
# workloads smoke recomputes scores on the data the plans recorded them on.
# NOTE: these feed the trace fingerprint — changing them invalidates every
# saved trace. The import costs a few seconds of jax startup on --help-style
# invocations (the package __init__ pulls it in); sweep children pay minutes
# of calibration anyway, and one shared constant beats a silent CI-gate skew.
from repro.workloads.base import (PROBE_BATCH as CAL_BATCH,          # noqa: E402
                                  PROBE_SEQ as CAL_SEQ,
                                  PROBE_SEED as CAL_SEED)


# ---------------------------------------------------------------------------
# --schedules: the GemmPlan schedule zoo (block-size schedules, not numerics)
# ---------------------------------------------------------------------------
# Representative GEMM signatures for the serving/CI hotpaths: decode-step
# (M=batch), prefill (M=batch*seq) and training shapes at the reduced-config
# scale the checked-in zoo serves. Small enough to autotune on CPU interpret
# mode in minutes; the fit() clamp keeps every winner legal at deploy time.
SCHEDULE_SHAPES = (
    (8, 64, 64), (8, 128, 64),          # decode-step projections
    (32, 64, 64), (64, 64, 64),         # small prefill
    (64, 128, 128), (128, 128, 128),    # reduced-config train/prefill
)
SCHEDULE_FMTS = ("ieee_fp32", "bfloat16")


def refresh_schedules(args) -> None:
    """Autotune the representative GEMM signatures and persist the winners
    as ``<out>/schedules/<backend>.json`` — the schedule zoo the launch
    drivers preload so a warm process takes zero autotune misses."""
    import jax

    from repro.core.accumulator import AccumulatorSpec
    from repro.core.dispatch import (clear_plan_cache, plan_cache_stats,
                                     plan_gemm)
    from repro.core.formats import get_format
    from repro.core.schedules import ScheduleZoo, zoo_path

    spec = AccumulatorSpec.paper_91bit()
    backend = jax.default_backend()
    clear_plan_cache()
    t0 = time.time()
    for fmt_name in SCHEDULE_FMTS:
        fmt = get_format(fmt_name)
        for (m, n, k) in SCHEDULE_SHAPES:
            plan = plan_gemm(m, n, k, fmt=fmt, spec=spec, autotune=True)
            print(f"[schedules] {fmt_name} {m}x{n}x{k}: tile={plan.tile} "
                  f"({plan.source})")
    zoo = ScheduleZoo.from_cache(
        backend, meta={"generated_by": "scripts/refresh_plans.py",
                       "shapes": [list(s) for s in SCHEDULE_SHAPES],
                       "fmts": list(SCHEDULE_FMTS),
                       "spec": "paper_91bit",
                       "provenance": _provenance()})
    path = zoo_path(os.path.join(args.out, "schedules"), backend)
    zoo.save(path)
    st = plan_cache_stats()
    print(f"[schedules] {len(zoo.entries)} schedules "
          f"({st.autotuned} autotuned) -> {path} "
          f"({time.time() - t0:.0f}s)")


def refresh_envelopes(args) -> None:
    """Back-fill ``meta["envelope"]`` on every checked-in plan from its saved
    calibration trace — pure derivation (``numerics.build_envelope``), no
    recalibration and no search, so site assignments, scores, and the trace
    fingerprints are untouched. Fresh searches stamp the envelope themselves;
    this path exists for the zoo that predates it."""
    from repro.numerics import build_envelope, load_plan, load_trace

    failures, done = 0, 0
    only = set(args.only or ())
    for fn in sorted(os.listdir(args.out)):
        if not fn.endswith(".json") or fn == "MANIFEST.json":
            continue
        arch_id = fn[:-len(".json")]
        if only and arch_id not in only:
            continue
        path = os.path.join(args.out, fn)
        plan = load_plan(path)
        trace_rel = plan.meta.get("trace")
        if not trace_rel:
            print(f"[{arch_id}] SKIP: plan records no trace path — "
                  "recalibrate before deriving an envelope")
            failures += 1
            continue
        try:
            trace = load_trace(os.path.join(args.out, trace_rel),
                               expect_fingerprint=plan.meta.get("fingerprint"))
        except (OSError, ValueError) as e:
            print(f"[{arch_id}] FAIL: {e}")
            failures += 1
            continue
        plan.meta["envelope"] = build_envelope(trace, plan)
        plan.save(path)
        n = len(plan.meta["envelope"]["sites"])
        print(f"[{arch_id}] envelope derived from {trace_rel} "
              f"({n} gemm sites) -> {fn}")
        done += 1
    if not args.no_manifest:
        rebuild_manifest(args.out)
    print(f"[envelopes] {done} plan(s) updated, {failures} failure(s)")
    if failures:
        sys.exit(1)


def _provenance() -> dict:
    """Where this artifact was measured/searched: backend + device topology.
    Consumers (check_plan_zoo.py) treat an absent record as the historical
    single-device default, so pre-provenance artifacts stay valid."""
    import jax
    return {"backend": jax.default_backend(),
            "devices": jax.device_count(),
            "process_count": jax.process_count()}


def _alias_of(arch_id: str) -> str:
    from repro.configs import _ALIASES
    for alias, mod in _ALIASES.items():
        if mod == arch_id:
            return alias
    return arch_id


def _calibration_spec(cfg, reduced: bool, phases: tuple) -> dict:
    """Everything the trace depends on — hashed into the fingerprint.
    ``phases`` joins the spec only when the backward namespace is calibrated,
    so every pre-phase (fwd-only) trace keeps its original fingerprint and
    the checked-in zoo stays reproducible without a recalibration sweep."""
    import dataclasses
    spec = {"config": dataclasses.asdict(cfg), "reduced": reduced,
            "batch": CAL_BATCH, "seq": CAL_SEQ, "seed": CAL_SEED,
            "calibration_policy": "mxu_fp32"}
    if "bwd" in phases:
        spec["phases"] = sorted(phases)
    return spec


def _calibration_batch(cfg, *, with_targets: bool = False):
    # the bwd calibration step (and the grad workload) runs the real training
    # loss, so gradient sites see CE-shaped cotangents rather than synthetic
    # ones; the recipe lives in repro.workloads so validators probe the same
    # data distribution the plan was calibrated on
    from repro.workloads import make_probe_batch
    return make_probe_batch(cfg, batch_size=CAL_BATCH, seq=CAL_SEQ,
                            seed=CAL_SEED + 1, with_targets=with_targets)


def _profile_aux_sites(trace, cfg, params, *, steps: int = 3,
                       lr: float = 3e-3) -> None:
    """Profile the non-GEMM precision sites — optimizer-moment value streams
    (``opt.m@state`` / ``opt.v@state``) and the gradient-collective payload
    (``grad_psum@coll``) — with a short fp32 Adam run, so the search can
    enumerate block-scaled formats against the magnitudes the sites really
    carry. Runs *outside* the calibration hook (the GEMM profiles' call/mac
    counts must not double-count these extra steps) and only on fresh
    calibrations: the aux profiles persist inside the saved trace, keeping
    ``--check`` reruns deterministic, and pre-aux saved traces simply search
    no aux sites."""
    import jax
    import jax.numpy as jnp

    from repro.core import qformat
    from repro.core.dispatch import MXU_FP32, use_policy
    from repro.models import LOCAL
    from repro.train.loop import make_loss_fn
    from repro.train.optimizer import adamw, apply_updates

    loss_fn = make_loss_fn(cfg, LOCAL, remat="none")
    grad_batch = _calibration_batch(cfg, with_targets=True)
    opt = adamw(lr)
    with use_policy(MXU_FP32):
        p, ostate = params, opt.init(params)
        grads = None
        for _ in range(steps):
            (_, _aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p, grad_batch)
            trace.record_aux(qformat.GRAD_PSUM_SITE, grads)
            updates, ostate = opt.update(grads, ostate, p)
            p = apply_updates(p, updates)
        trace.record_aux(qformat.OPT_M_SITE, ostate["mu"])
        # nu is *stored* in sqrt domain (train.optimizer's second-moment
        # safety contract), so the profiled stream is sqrt(nu)
        trace.record_aux(qformat.OPT_V_SITE,
                         jax.tree.map(jnp.sqrt, ostate["nu"]))


class CheckDrift(Exception):
    """--check failure with a readable per-key drift summary."""

    def __init__(self, arch_id: str, lines: list):
        self.arch_id = arch_id
        self.lines = list(lines)
        super().__init__(f"[{arch_id}] --check FAILED "
                         f"({len(self.lines)} divergence(s))")


def _drift_lines(recomputed, checked_in) -> list:
    """Human-readable divergences between a recomputed plan and the
    checked-in one: which site / score / key moved, and how."""
    lines = []
    got = {s.site: s.cfg.tag() for s in recomputed.sites}
    want = {s.site: s.cfg.tag() for s in checked_in.sites}
    for site in sorted(want.keys() - got.keys()):
        lines.append(f"site {site}: checked-in has {want[site]}, "
                     "recomputed search dropped it")
    for site in sorted(got.keys() - want.keys()):
        lines.append(f"site {site}: recomputed search added {got[site]}, "
                     "not in checked-in plan")
    for site in sorted(got.keys() & want.keys()):
        if got[site] != want[site]:
            lines.append(f"site {site}: recomputed {got[site]} != "
                         f"checked-in {want[site]}")
    if recomputed.budget_bits != checked_in.budget_bits:
        lines.append(f"budget_bits: recomputed {recomputed.budget_bits} != "
                     f"checked-in {checked_in.budget_bits}")
    # end-to-end scores: exact equality is a same-machine property, so the
    # gate allows a small cross-machine tolerance on native-backend noise
    tol = 1.0
    gv = recomputed.meta.get("validation", {})
    wv = checked_in.meta.get("validation", {})
    for name in sorted(gv.keys() ^ wv.keys()):
        side = "recomputed" if name in gv else "checked-in"
        lines.append(f"workload {name!r}: only the {side} plan has a score "
                     "(validator sets differ?)")
    for name in sorted(gv.keys() & wv.keys()):
        g, w = gv[name].get("score"), wv[name].get("score")
        if g is None or w is None:
            if g != w:
                lines.append(f"workload {name!r}: score {g!r} vs {w!r}")
        elif abs(g - w) > tol:
            lines.append(f"workload {name!r}: recomputed score {g:.2f} "
                         f"drifted from checked-in {w:.2f} (> {tol} bits)")
    for key in ("validated_bits",):
        g, w = recomputed.meta.get(key), checked_in.meta.get(key)
        if g is not None and w is not None and abs(g - w) > tol:
            lines.append(f"{key}: recomputed {g:.2f} != checked-in {w:.2f} "
                         f"(> {tol} bits)")
    return lines


def refresh_arch(arch_id: str, args) -> dict:
    """Calibrate (or reload the saved trace) + search one architecture;
    returns the plan's manifest entry. Writes the plan unless --check."""
    import jax

    from repro.configs import get_config
    from repro.core.dispatch import MXU_FP32, use_policy
    from repro.models import LOCAL, forward, init
    from repro.numerics import (calibrate, config_fingerprint, load_plan,
                                load_trace, search)
    from repro.workloads import WorkloadContext, build_validators

    t0 = time.time()
    phases = tuple(args.phases.split(","))
    cfg = get_config(arch_id)
    if args.reduced:
        cfg = cfg.reduced()
    fp = config_fingerprint(_calibration_spec(cfg, args.reduced, phases))
    traces_dir = os.path.join(args.out, "traces")
    os.makedirs(traces_dir, exist_ok=True)
    trace_path = os.path.join(traces_dir, f"{arch_id}.trace.json")
    plan_path = os.path.join(args.out, f"{arch_id}.json")

    params = init(cfg, jax.random.key(CAL_SEED))
    batch = _calibration_batch(cfg)

    trace = None
    if os.path.exists(trace_path) and not args.recalibrate:
        try:
            trace = load_trace(trace_path, expect_fingerprint=fp)
            print(f"[{arch_id}] trace loaded from {trace_path} "
                  f"(calibration skipped, fingerprint {fp})")
        except ValueError as e:
            print(f"[{arch_id}] saved trace is stale: {e}")
    if trace is None and args.check:
        # the reproducibility gate's whole claim is "searched from the saved
        # trace, no recalibration" — a missing/stale trace must fail loudly,
        # not quietly recalibrate into a possibly-matching plan
        raise CheckDrift(arch_id, [
            f"no usable saved trace at {trace_path} (expected fingerprint "
            f"{fp}) — refresh and commit the trace before gating on it"])
    if trace is None:
        print(f"[{arch_id}] calibrating {cfg.name} "
              f"(batch={CAL_BATCH}, seq={CAL_SEQ}, phases={phases})")
        with calibrate() as trace, use_policy(MXU_FP32):
            jax.block_until_ready(
                forward(params, cfg, batch, LOCAL, remat="none"))
            if "bwd" in phases:
                # a real value_and_grad step through the training loss: the
                # dispatch custom_vjp fires every gradient GEMM under its
                # phase-qualified site key, so the trace records the bwd
                # namespace's own exponent ranges / cancellation / samples
                from repro.train.loop import make_loss_fn
                loss_fn = make_loss_fn(cfg, LOCAL, remat="none")
                grad_batch = _calibration_batch(cfg, with_targets=True)
                jax.block_until_ready(jax.value_and_grad(
                    loss_fn, has_aux=True)(params, grad_batch))
        if "bwd" in phases:
            _profile_aux_sites(trace, cfg, params)
        trace.save(trace_path, fingerprint=fp,
                   meta={"arch": arch_id, "arch_alias": _alias_of(arch_id),
                         "config_name": cfg.name, "family": cfg.family,
                         "reduced": args.reduced, "phases": sorted(phases),
                         "batch": CAL_BATCH, "seq": CAL_SEQ})
        n_bwd = len(trace.sites("bwd"))
        print(f"[{arch_id}] trace saved to {trace_path} "
              f"({len(trace.sites('fwd'))} fwd / {n_bwd} bwd / "
              f"{len(trace.aux_sites())} aux sites)")

    # end-to-end acceptance: the workload zoo (grad vs 91-bit-bwd reference,
    # logit fidelity vs the uniform oracle, K-reorder stability, ... per
    # --validators), wired into the search's upgrade loop
    names = [n for n in args.validators.split(",") if n and n != "none"]
    validators = None
    if names:
        ctx = WorkloadContext(
            budget_bits=args.budget, cfg=cfg, params=params, batch=batch,
            grad_batch=_calibration_batch(cfg, with_targets=True),
            dist=LOCAL, seed=CAL_SEED)
        validators = build_validators(names, ctx)

    grid = dict(widths=(32,)) if args.reduced else dict(widths=(24, 40, 64))
    res = search(trace, budget_bits=args.budget, name=cfg.name,
                 validators=validators, phases=phases, **grid)
    plan = res.plan
    plan.meta.update({
        "arch": arch_id, "arch_alias": _alias_of(arch_id),
        "family": cfg.family, "reduced": args.reduced,
        "phases": sorted(phases),
        "validators": names,
        "fingerprint": fp,
        "trace": os.path.join("traces", f"{arch_id}.trace.json"),
        "provenance": _provenance(),
    })
    print(res.describe())

    if args.check:
        try:
            want = load_plan(plan_path)
        except FileNotFoundError:
            raise CheckDrift(arch_id, [f"no checked-in plan at {plan_path}"])
        lines = _drift_lines(plan, want)
        if lines:
            raise CheckDrift(arch_id, lines)
        print(f"[{arch_id}] --check OK: recomputed plan matches {plan_path} "
              f"({len(plan.sites)} sites, {time.time() - t0:.0f}s)")
    else:
        plan.save(plan_path)
        print(f"[{arch_id}] plan written to {plan_path} "
              f"({time.time() - t0:.0f}s)")
    return manifest_entry(arch_id, plan)


def manifest_entry(arch_id: str, plan) -> dict:
    from repro.workloads import validation_summary
    m = plan.meta
    return {
        "file": f"{arch_id}.json",
        "name": plan.name,
        "arch": m.get("arch_alias", arch_id),
        "family": m.get("family"),
        "reduced": m.get("reduced"),
        "phases": m.get("phases", ["fwd"]),
        "budget_bits": plan.budget_bits,
        "validated_bits": m.get("validated_bits"),
        # per-workload end-to-end scores (repro.workloads) this plan was
        # accepted on, plus which searched sites the validators widened
        "validation": validation_summary(m),
        "validation_upgrades": m.get("validation_upgrades", []),
        "modeled_energy_j": m.get("modeled_energy_j"),
        # the measured fwd/bwd energy split (bwd is 0/absent for plans
        # searched before the phase-aware namespaces existed)
        "modeled_energy_fwd_j": m.get("modeled_energy_fwd_j"),
        "modeled_energy_bwd_j": m.get("modeled_energy_bwd_j"),
        "baseline_energy_j": m.get("baseline_energy_j"),
        "energy_vs_baseline": m.get("energy_vs_baseline"),
        # training-memory / comms byte axes (absent for gemm-only plans)
        "bytes_resident_vs_fp32": m.get("bytes_resident_vs_fp32"),
        "bytes_moved_vs_fp32": m.get("bytes_moved_vs_fp32"),
        "n_sites": len(plan.sites),
        # live-monitor coverage: GEMM sites with a serialized calibration
        # envelope (repro.obs compares live traffic against these bounds)
        "n_envelope_sites": len((m.get("envelope") or {}).get("sites", {})),
        "n_bwd_sites": sum(s.phase == "bwd" for s in plan.sites),
        "n_aux_sites": sum(s.kind != "gemm" for s in plan.sites),
        "sites": [s.site for s in plan.sites],
        "fingerprint": m.get("fingerprint"),
        "trace": m.get("trace"),
        # where this plan was searched/validated; absent = single-device
        # (pre-provenance zoo entries)
        "provenance": m.get("provenance"),
    }


def rebuild_manifest(out_dir: str) -> dict:
    """Regenerate MANIFEST.json from the plan files on disk (idempotent, so
    parallel --jobs children don't race on it — only the parent writes)."""
    from repro.numerics import load_plan
    plans = {}
    for fn in sorted(os.listdir(out_dir)):
        if not fn.endswith(".json") or fn == "MANIFEST.json":
            continue
        arch_id = fn[:-len(".json")]
        plans[arch_id] = manifest_entry(arch_id,
                                        load_plan(os.path.join(out_dir, fn)))
    doc = {"kind": MANIFEST_KIND, "version": MANIFEST_VERSION,
           "generated_by": "scripts/refresh_plans.py", "plans": plans}
    path = os.path.join(out_dir, "MANIFEST.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"[manifest] {len(plans)} plans -> {path}")
    return doc


def _spawn(arch_id: str, args) -> tuple:
    """Child process for --jobs fan-out (the calibration hook is process-
    global, so parallelism must be process-level, not threads)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--only", arch_id,
           "--budget", str(args.budget), "--out", args.out, "--no-manifest",
           "--phases", args.phases, "--validators", args.validators]
    for flag in ("reduced", "recalibrate", "check"):
        if getattr(args, flag):
            cmd.append(f"--{flag}")
    env = dict(os.environ)
    t0 = time.time()
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=3600)
        rc, out = r.returncode, r.stdout + "\n" + r.stderr
    except subprocess.TimeoutExpired as e:
        # one slow arch is that arch's failure, not the whole sweep's
        rc = -1
        partial = e.stdout if isinstance(e.stdout, str) else ""
        out = f"[{arch_id}] timed out after {e.timeout:.0f}s\n{partial}"
    if rc != 0:
        sys.stderr.write(out)
    return arch_id, rc, time.time() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", action="append", default=None,
                    help="restrict to these arch ids (repeatable)")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced() configs (CPU-sized; what CI checks in)")
    ap.add_argument("--budget", type=float, default=10.0)
    ap.add_argument("--resume", action="store_true",
                    help="skip archs whose plan file already exists")
    ap.add_argument("--recalibrate", action="store_true",
                    help="ignore saved traces, re-run calibration forwards")
    ap.add_argument("--phases", default="fwd,bwd",
                    help="comma list of site namespaces to calibrate+search: "
                         "'fwd,bwd' (default: a value_and_grad step gives "
                         "gradient GEMMs their own traced, searched "
                         "assignments) or 'fwd' (matches pre-phase traces)")
    ap.add_argument("--validators", default="grad,logits,repro",
                    help="comma list of repro.workloads validators gating "
                         "the search end-to-end ('none' disables; the "
                         "ill-conditioned 'solve' workload is opt-in)")
    ap.add_argument("--jobs", type=int, default=1,
                    help="process-parallel arch fan-out (CPU only: an "
                         "accelerator serves one process at a time)")
    ap.add_argument("--check", action="store_true",
                    help="recompute and compare against the checked-in plan "
                         "instead of writing (CI reproducibility gate)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--no-manifest", action="store_true",
                    help="skip the MANIFEST rebuild (used by --jobs children)")
    ap.add_argument("--schedules", action="store_true",
                    help="refresh the GemmPlan schedule zoo "
                         "(<out>/schedules/<backend>.json) instead of the "
                         "precision-plan sweep")
    ap.add_argument("--envelopes", action="store_true",
                    help="derive meta['envelope'] for checked-in plans from "
                         "their saved traces (no recalibration/search)")
    args = ap.parse_args(argv)
    args.out = os.path.abspath(args.out)
    if args.jobs > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("--jobs > 1 starts one JAX process per arch; run it "
                         "with JAX_PLATFORMS=cpu (a chip takes one process)")
    if args.schedules:
        refresh_schedules(args)
        return
    if args.envelopes:
        refresh_envelopes(args)
        return
    bad = set(args.phases.split(",")) - {"fwd", "bwd"}
    if bad:
        raise SystemExit(f"--phases: unknown namespaces {sorted(bad)} "
                         "(expected a comma list of fwd,bwd)")

    from repro.configs import ARCH_IDS
    archs = list(args.only) if args.only else list(ARCH_IDS)
    unknown = [a for a in archs if a not in ARCH_IDS]
    if unknown:
        raise SystemExit(f"unknown arch ids {unknown}; known: {ARCH_IDS}")
    if args.resume:
        archs = [a for a in archs
                 if not os.path.exists(os.path.join(args.out, f"{a}.json"))]
        if not archs:
            print("[refresh] nothing to do (--resume: all plans exist)")
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    drifted: list = []
    if args.jobs > 1 and len(archs) > 1:
        with ThreadPoolExecutor(max_workers=args.jobs) as ex:
            for arch_id, rc, dt in ex.map(lambda a: _spawn(a, args), archs):
                status = "ok" if rc == 0 else f"FAIL rc={rc}"
                print(f"[refresh] {arch_id}: {status} ({dt:.0f}s)",
                      flush=True)
                failures += rc != 0
    else:
        for arch_id in archs:
            try:
                refresh_arch(arch_id, args)
            except CheckDrift as e:         # readable per-arch drift report
                failures += 1
                drifted.append(e)
                print(f"[{e.arch_id}] --check FAILED: recomputed plan "
                      f"diverges from the checked-in one:")
                for line in e.lines:
                    print(f"    - {line}")
            except Exception as e:          # keep sweeping, report at exit
                failures += 1
                import traceback
                print(f"[refresh] {arch_id}: FAIL {type(e).__name__}: {e}")
                traceback.print_exc()

    if drifted:
        print(f"[check] {len(drifted)} arch(es) drifted: "
              + ", ".join(e.arch_id for e in drifted))
    if not args.no_manifest and not args.check:
        rebuild_manifest(args.out)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
