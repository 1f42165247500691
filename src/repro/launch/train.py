"""End-to-end training driver.

Local (CPU) example:
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
        --steps 50 --batch 8 --seq 64 --ckpt /tmp/ckpt
On a real fleet the same driver runs with --mesh pod/multipod (the mesh is
only built when requested so CPU runs stay single-device).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.accumulator import AccumulatorSpec
from repro.core.dispatch import policy_from_plan, use_policy
from repro.data.synthetic import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models.layers import Distribution, LOCAL
from repro.core.qformat import parse_quant
from repro.train.loop import Trainer, make_train_step
from repro.train.optimizer import adamw, cosine_schedule, state_quant_from_policy


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--fdp-grad", action="store_true",
                    help="fixed-point (order-invariant) grad accumulation")
    ap.add_argument("--precision-plan", default=None,
                    help="train under a repro.numerics PrecisionPlan JSON "
                         "(v3 plans may also assign optimizer-state and "
                         "collective formats — honored automatically)")
    ap.add_argument("--opt-precision", default=None,
                    help="store Adam moments block-scaled: 'fp32', "
                         "'BITSxBLOCK' ('8x64'), or 'M,V' per-moment "
                         "('8x64,8x32'); overrides the plan's @state sites")
    ap.add_argument("--mesh", default=None,
                    help="RxC (data x model) device mesh, e.g. 2x4")
    ap.add_argument("--profile", default="fsdp",
                    choices=["fsdp", "ddp", "decode_tp"],
                    help="sharding profile when --mesh is set")
    ap.add_argument("--log", default=None)
    args = ap.parse_args(argv)

    from repro.core.schedules import preload_schedules
    enable_compile_cache()
    n_sched = preload_schedules()
    if n_sched:
        print(f"[train] schedule zoo: {n_sched} GEMM schedules preloaded")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    fdp_spec = AccumulatorSpec(ovf=10, msb=10, lsb=-20) if args.fdp_grad else None
    policy = (policy_from_plan(args.precision_plan)
              if args.precision_plan else None)
    # optimizer-state formats: --opt-precision wins, else the plan's
    # opt.m@state / opt.v@state assignments (state_quant_from_policy)
    squant = state_quant_from_policy(policy)
    if args.opt_precision:
        parts = [p.strip() for p in args.opt_precision.split(",")]
        if len(parts) not in (1, 2):
            raise SystemExit("--opt-precision takes 'FMT' or 'M_FMT,V_FMT'")
        cfgs = [parse_quant(p) for p in parts]
        if len(cfgs) == 1:
            cfgs = cfgs * 2
        squant = {m: c for m, c in zip(("mu", "nu"), cfgs)
                  if c.mode == "block"} or None
    opt = adamw(lr=cosine_schedule(args.lr, warmup=10, total=args.steps),
                state_quant=squant)
    if squant:
        print("[train] quantized optimizer state: "
              + ", ".join(f"{m}={c.tag()}" for m, c in sorted(squant.items())))
    dist, place = LOCAL, None
    if args.mesh:
        from repro.launch import sharding as shd
        mesh = shd.make_mesh(args.mesh)
        dist = shd.distribution_for(mesh, args.profile,
                                    numerics_policy=policy)

        def place(carry):
            params, opt_state = carry
            ps = shd.param_shardings(cfg, params, mesh, profile=args.profile)
            oss = shd.opt_state_shardings(cfg, opt_state, ps, mesh,
                                          profile=args.profile)
            return jax.device_put(params, ps), jax.device_put(opt_state, oss)

    step_fn = make_train_step(cfg, opt, dist, remat="none",
                              microbatches=args.microbatches,
                              fdp_grad_spec=fdp_spec, donate=False,
                              numerics_policy=policy)
    data_src = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=0)

    def data(step):
        tb = data_src.batch(step)
        batch = {"tokens": tb.tokens, "targets": tb.targets,
                 "loss_mask": tb.loss_mask}
        if cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (args.batch, cfg.n_patches, cfg.d_model), jnp.float32)
        if cfg.family == "encdec":
            batch["frames"] = jax.random.normal(
                jax.random.key(step), (args.batch, cfg.enc_seq, cfg.d_model))
        return batch

    trainer = Trainer(cfg, opt, data, step_fn, args.ckpt,
                      save_every=args.save_every, place_state=place)
    # the step carries the policy itself (make_train_step numerics_policy);
    # keep the ambient context too so any dispatch outside the jitted step
    # (debug probes, future eval hooks) agrees with it.
    ctx = use_policy(policy) if policy is not None else contextlib.nullcontext()
    t0 = time.time()
    with ctx:
        trainer.run(args.steps)
    dt = time.time() - t0
    losses = [m["loss"] for m in trainer.metrics_log]
    plan_note = f" plan={policy.name}" if policy is not None else ""
    if args.log:
        with open(args.log, "w") as f:
            json.dump(trainer.metrics_log, f)
    if not losses:
        # resumed from a checkpoint that already reached --steps: a no-op
        # run is a successful (idempotent) outcome, not a crash (the --log
        # file above still gets written — as an empty list — so sweep
        # runners never read a stale log from a previous run)
        print(f"[train] {args.arch}: checkpoint at {args.ckpt} already "
              f"covers {args.steps} steps; nothing to do")
        return
    print(f"[train] {args.arch}{' (reduced)' if args.reduced else ''}: "
          f"{args.steps} steps in {dt:.1f}s;{plan_note} "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "training did not reduce loss"


if __name__ == "__main__":
    main()
