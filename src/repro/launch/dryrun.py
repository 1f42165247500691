import os
# a CPU tool: 512 placeholder host devices stand in for the production mesh,
# and it never takes a chip another process may hold
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh (16x16 single pod / 2x16x16 multi-pod) with 512 host
placeholder devices, and extract the roofline terms from the compiled module.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
Each cell writes a JSON with memory_analysis, cost_analysis, and the summed
collective bytes (parsed from the post-SPMD HLO, scan-body collectives
multiplied by their while-loop trip counts).
"""  # noqa: E402

import argparse
import dataclasses
import json
import re
import sys
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, all_arch_names
from repro.models import SHAPES, shape_applicable
from repro.models import transformer as T
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.layers import Distribution
from repro.train.loop import make_loss_fn
from repro.train.optimizer import adamw

from .mesh import make_production_mesh, dp_axes_of
from .sharding import (batch_shardings, cache_shardings, opt_state_shardings,
                       param_shardings)

# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per chip (aggregate link budget)


def _dist(mesh, joint_tp: bool = False) -> Distribution:
    return Distribution(mesh=mesh, dp_axes=dp_axes_of(mesh), tp_axis="model",
                        joint_tp=joint_tp)


def _abstract_batch(cfg: ModelConfig, shape: ShapeConfig):
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "decode":
        batch = {"tokens": sds((B, 1), jnp.int32)}
    else:
        batch = {"tokens": sds((B, S), jnp.int32)}
        if shape.kind == "train":
            batch["targets"] = sds((B, S), jnp.int32)
            batch["loss_mask"] = sds((B, S), jnp.float32)
    if cfg.family == "vlm" and shape.kind != "decode":
        # image prefix is part of the sequence budget
        n_text = S - cfg.n_patches
        batch["tokens"] = sds((B, n_text), jnp.int32)
        if shape.kind == "train":
            batch["targets"] = sds((B, n_text), jnp.int32)
            batch["loss_mask"] = sds((B, n_text), jnp.float32)
        batch["patches"] = sds((B, cfg.n_patches, cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec" and shape.kind != "decode":
        batch["frames"] = sds((B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    return batch


def input_specs(arch: str, shape_name: str = "train_4k"):
    """Public API: ShapeDtypeStruct stand-ins for every model input of a
    given (architecture, shape) cell — weak-type-correct, shardable, no
    device allocation."""
    cfg = get_config(arch) if isinstance(arch, str) else arch
    return _abstract_batch(cfg, SHAPES[shape_name])


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, moe_impl="tp",
               remat="block", profile: str = "auto", kv_cache: str = "bf16"):
    """Returns (jitted_fn, example_args_avals) ready to lower.

    profile: parameter-sharding profile (launch.sharding.param_specs);
    "auto" = decode_tp for decode cells, fsdp otherwise."""
    if profile == "auto":
        profile = "decode_tp" if shape.kind == "decode" else "fsdp"
    dist = _dist(mesh, joint_tp=(profile == "decode_tp"))
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    aparams = T.init_abstract(cfg)
    pshard = param_shardings(cfg, aparams, mesh, profile=profile)
    bshard = batch_shardings(cfg, shape, mesh)
    abatch = _abstract_batch(cfg, shape)
    bshard = {k: bshard[k] for k in abatch}

    if shape.kind == "train":
        opt = adamw(lr=1e-4)
        aopt = jax.eval_shape(opt.init, aparams)
        oshard = opt_state_shardings(cfg, aopt, pshard, mesh, profile=profile)
        loss_fn = make_loss_fn(cfg, dist, remat=remat, moe_impl=moe_impl)

        def train_step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
            return params, opt_state, metrics

        fn = jax.jit(
            train_step,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard,
                           NamedSharding(mesh, P())),
            donate_argnums=(0, 1),
        )
        return fn, (aparams, aopt, abatch)

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            logits = T.forward(params, cfg, batch, dist, remat=remat,
                               moe_impl=moe_impl)
            return logits[:, -1, :]                     # next-token logits

        dp = dist.dp
        fn = jax.jit(prefill_step,
                     in_shardings=(pshard, bshard),
                     out_shardings=NamedSharding(mesh, P(dp, "model")))
        return fn, (aparams, abatch)

    # decode
    acache = jax.eval_shape(
        lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len,
                             quantized=(kv_cache == "int8")))
    cshard = cache_shardings(cfg, shape, mesh, acache, profile=profile)

    def serve_step(params, cache, tokens):
        logits, cache = T.decode_step(params, cfg, cache, tokens, dist,
                                      moe_impl=moe_impl)
        return logits, cache

    fn = jax.jit(serve_step,
                 in_shardings=(pshard, cshard, bshard["tokens"]),
                 out_shardings=(NamedSharding(mesh, P()), cshard),
                 donate_argnums=(1,))
    return fn, (aparams, acache, _abstract_batch(cfg, shape)["tokens"])


# ---------------------------------------------------------------------------
# HLO collective analysis (exact: call graph + known_trip_count)
# ---------------------------------------------------------------------------
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_OP_RE = re.compile(
    r"=\s+(\([^=]*?\)|\S+)\s+(all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(-start)?\(")
_TRIP_RE = re.compile(r'known_trip_count[":{ ]+n["\s:]+"?(\d+)')

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3": 1,
                "f8e5m2": 1, "s16": 2, "u16": 2}


def _shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO shape string like 'bf16[16,128]' or a tuple of them."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _parse_computations(hlo_text: str):
    """Split compiled HLO into computations; return (comps, entry_name)."""
    comps, cur, entry = {}, None, None
    for line in hlo_text.splitlines():
        ls = line.strip()
        if (ls.startswith("%") or ls.startswith("ENTRY")) and \
                ls.endswith("{") and "(" in ls:
            name = ls.split()[1] if ls.startswith("ENTRY") else ls.split()[0]
            name = name.lstrip("%").split("(")[0].strip()
            cur = name
            comps[cur] = []
            if ls.startswith("ENTRY"):
                entry = cur
        elif cur is not None:
            comps[cur].append(line)
    return comps, entry


def collective_bytes(hlo_text: str, loop_trip_counts: dict | None = None):
    """Exact per-device collective payload bytes of a compiled module.

    Builds the computation call graph (while bodies with their
    ``known_trip_count``, fusions/calls/conditionals with x1) and propagates
    execution multipliers from the entry, so a collective inside the layer
    scan counts n_layers times, one inside a nested scan counts the product,
    etc. Returns (total_bytes, per_kind dict, details list).
    """
    comps, entry = _parse_computations(hlo_text)
    default_trip = (loop_trip_counts or {}).get("default", 1)

    edges = {}
    for cname, lines in comps.items():
        out = []
        for ln in lines:
            trip = None
            mt = _TRIP_RE.search(ln)
            if mt:
                trip = int(mt.group(1))
            mb = re.search(r"body=%?([\w.\-]+)", ln)
            if mb:
                out.append((mb.group(1), trip or default_trip))
            for pat in (r"condition=%?([\w.\-]+)", r"calls=%?([\w.\-]+)",
                        r"to_apply=%?([\w.\-]+)"):
                for m in re.finditer(pat, ln):
                    out.append((m.group(1), 1))
            bc = re.search(r"branch_computations=\{([^}]*)\}", ln)
            if bc:
                for n in bc.group(1).split(","):
                    out.append((n.strip().lstrip("%"), 1))
        edges[cname] = out

    mult = {c: 0 for c in comps}
    if entry:
        mult[entry] = 1
    changed, iters = True, 0
    while changed and iters < 64:          # call graph is a DAG; converges
        changed, iters = False, iters + 1
        for caller, m_c in list(mult.items()):
            if not m_c:
                continue
            for callee, trip in edges.get(caller, []):
                new = m_c * trip
                if callee in mult and new > mult[callee]:
                    mult[callee] = new
                    changed = True

    per_kind, details, total = {}, [], 0
    for cname, lines in comps.items():
        m_c = max(mult.get(cname, 0), 1) if mult.get(cname, 0) else 1
        m_c = mult.get(cname, 0) or 1
        for ln in lines:
            m = _COLL_OP_RE.search(ln)
            if not m:
                continue
            nbytes = _shape_bytes(m.group(1)) * m_c
            kind = m.group(2)
            total += nbytes
            per_kind[kind] = per_kind.get(kind, 0) + nbytes
            details.append({"comp": cname, "kind": kind,
                            "bytes": nbytes, "mult": m_c})
    return total, per_kind, details


def _call_multipliers(comps, entry, default_trip=1):
    """Execution-count multiplier per computation from the call graph
    (while bodies x known_trip_count, everything else x1). Also returns the
    set of fusion-internal computations (targets of calls=)."""
    edges, fusion_targets = {}, set()
    for cname, lines in comps.items():
        out = []
        for ln in lines:
            mt = _TRIP_RE.search(ln)
            trip = int(mt.group(1)) if mt else None
            mb = re.search(r"body=%?([\w.\-]+)", ln)
            if mb:
                out.append((mb.group(1), trip or default_trip))
            for pat in (r"condition=%?([\w.\-]+)", r"to_apply=%?([\w.\-]+)"):
                for m in re.finditer(pat, ln):
                    out.append((m.group(1), 1))
            for m in re.finditer(r"calls=%?([\w.\-]+)", ln):
                out.append((m.group(1), 1))
                fusion_targets.add(m.group(1))
            bc = re.search(r"branch_computations=\{([^}]*)\}", ln)
            if bc:
                for n in bc.group(1).split(","):
                    out.append((n.strip().lstrip("%"), 1))
        edges[cname] = out
    mult = {c: 0 for c in comps}
    if entry:
        mult[entry] = 1
    changed, iters = True, 0
    while changed and iters < 64:
        changed, iters = False, iters + 1
        for caller, m_c in list(mult.items()):
            if not m_c:
                continue
            for callee, trip in edges.get(caller, []):
                new = m_c * trip
                if callee in mult and new > mult[callee]:
                    mult[callee] = new
                    changed = True
    return mult, fusion_targets


_OP_RE = re.compile(r"^\s*%?([\w.\-]+)\s*=\s*((?:\([^=]*?\)|\S+))\s+(\w[\w\-]*)\(")
_DOT_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _shape_dims(shape_str):
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return None, []
    dims = [int(d) for d in m.group(2).split(",") if d]
    return m.group(1), dims


def hlo_cost(hlo_text: str, default_trip: int = 1):
    """Exact-ish per-device (flops, dot_traffic_bytes) of a compiled module.

    flops: every dot op (2 x output elements x contraction size), weighted by
    its computation's execution count — fixing XLA cost_analysis's
    loop-body-counted-once behaviour.
    dot_traffic_bytes: lhs+rhs+out bytes of every dot, likewise weighted — a
    matmul-traffic lower bound on HBM movement (the memory roofline term is
    max(this, XLA's whole-module bytes-accessed)).
    """
    comps, entry = _parse_computations(hlo_text)
    mult, _fusion_targets = _call_multipliers(comps, entry, default_trip)

    flops = 0
    dot_bytes = 0
    for cname, lines in comps.items():
        m_c = mult.get(cname, 0)
        if not m_c:
            continue
        syms = {}
        for ln in lines:
            mo = _OP_RE.match(ln)
            if mo:
                syms[mo.group(1)] = mo.group(2)
        for ln in lines:
            mo = _OP_RE.match(ln)
            if not mo:
                continue
            _name, out_shape, op = mo.groups()
            if op != "dot":
                continue
            args = re.findall(r"%([\w.\-]+)", ln.split("(", 1)[1])
            cd = _DOT_DIMS_RE.search(ln)
            lhs_shape = syms.get(args[0]) if args else None
            rhs_shape = syms.get(args[1]) if len(args) > 1 else None
            csize = 1
            if cd and lhs_shape:
                _, dims = _shape_dims(lhs_shape)
                for d in cd.group(1).split(","):
                    if d and int(d) < len(dims):
                        csize *= dims[int(d)]
            out_elems = 1
            _, odims = _shape_dims(out_shape)
            for d in odims:
                out_elems *= d
            flops += 2 * out_elems * csize * m_c
            b = _shape_bytes(out_shape)
            for s in (lhs_shape, rhs_shape):
                if s:
                    b += _shape_bytes(s)
            dot_bytes += b * m_c
    return flops, dot_bytes


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, multi_pod: bool, moe_impl="tp",
             remat="block", profile: str = "auto", kv_cache: str = "bf16",
             precision_plan: str | None = None):
    if precision_plan:
        # a numerics plan changes what lowers (native sites stay MXU dots,
        # simulate/pallas sites lower their FDP limb algebra), so the whole
        # build+compile runs under the plan's policy
        from repro.core.dispatch import policy_from_plan, use_policy
        with use_policy(policy_from_plan(precision_plan)):
            return run_cell(arch, shape_name, multi_pod, moe_impl=moe_impl,
                            remat=remat, profile=profile, kv_cache=kv_cache)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = len(mesh.devices.reshape(-1))
    fn, avals = build_cell(cfg, shape, mesh, moe_impl=moe_impl, remat=remat,
                           profile=profile, kv_cache=kv_cache)
    lowered = fn.lower(*avals)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):        # newer jax: one dict per device
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    scan_len = {"dense": cfg.n_layers, "moe": cfg.n_layers,
                "vlm": cfg.n_layers, "ssm": cfg.n_layers,
                "encdec": cfg.n_layers + cfg.n_enc_layers,
                "hybrid": cfg.n_layers}[cfg.family]
    coll_total, coll_kinds, _ = collective_bytes(
        hlo, {"default": scan_len})

    # exact per-device flops from the compiled HLO with while-loop trip-count
    # multipliers (XLA's cost_analysis counts loop bodies once); memory term
    # = max(XLA whole-module bytes-accessed, matmul-traffic bound)
    flops, dot_bytes = hlo_cost(hlo, default_trip=scan_len)
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    flops = float(max(flops, xla_flops))
    bytes_accessed = float(max(dot_bytes, xla_bytes))
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_active = cfg.active_param_count()
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens

    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_accessed / HBM_BW
    t_coll = coll_total / ICI_BW
    dominant = max((("compute", t_compute), ("memory", t_memory),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "kind": shape.kind,
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "per_device_total": int(mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
        },
        "cost": {"flops": flops, "bytes_accessed": bytes_accessed,
                 "xla_flops_no_trip": xla_flops,
                 "xla_bytes_no_trip": xla_bytes},
        "collectives": {"total_bytes": int(coll_total), "by_kind": coll_kinds},
        "roofline": {
            "t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dominant,
            "model_flops_total": float(model_flops),
            "model_flops_per_chip": float(model_flops / n_chips),
            "useful_flops_ratio": float(
                (model_flops / n_chips) / flops) if flops else None,
        },
    }
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--moe-impl", default="tp", choices=["tp", "ep"])
    ap.add_argument("--remat", default="block")
    ap.add_argument("--param-profile", default="auto",
                    choices=["auto", "fsdp", "ddp", "decode_tp"])
    ap.add_argument("--kv-cache", default="bf16", choices=["bf16", "int8"])
    ap.add_argument("--precision-plan", default=None,
                    help="lower under a repro.numerics PrecisionPlan JSON")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    # schedules warm the plan cache so plan-lowered cells never autotune
    # mid-sweep
    from repro.core.schedules import preload_schedules
    n_sched = preload_schedules()
    if n_sched:
        print(f"[dryrun] schedule zoo: {n_sched} GEMM schedules preloaded")

    cells = []
    if args.all:
        for arch in all_arch_names():
            for shape in SHAPES:
                for mp in (False, True):
                    cells.append((arch, shape, mp))
    else:
        cells = [(args.arch, args.shape, args.multi_pod)]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape, mp in cells:
        tag = (f"{arch}_{shape}_{'pod2' if mp else 'pod1'}_{args.moe_impl}_"
               f"{args.remat}")
        if args.param_profile != "auto":
            tag += f"_{args.param_profile}"
        if args.kv_cache != "bf16":
            tag += f"_kv{args.kv_cache}"
        if args.precision_plan:
            tag += "_planned"
        path = os.path.join(args.out, tag + ".json")
        if args.skip_existing and os.path.exists(path):
            print(f"[dryrun] {tag}: cached")
            continue
        try:
            res = run_cell(arch, shape, mp, moe_impl=args.moe_impl,
                           remat=args.remat, profile=args.param_profile,
                           kv_cache=args.kv_cache,
                           precision_plan=args.precision_plan)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            if "skipped" in res:
                print(f"[dryrun] {tag}: SKIP ({res['skipped']})")
            else:
                r = res["roofline"]
                print(f"[dryrun] {tag}: OK mem/dev="
                      f"{res['memory']['per_device_total']/2**30:.2f}GiB "
                      f"t_comp={r['t_compute_s']*1e3:.1f}ms "
                      f"t_mem={r['t_memory_s']*1e3:.1f}ms "
                      f"t_coll={r['t_collective_s']*1e3:.1f}ms "
                      f"dom={r['dominant']}")
        except Exception as e:
            failures += 1
            print(f"[dryrun] {tag}: FAIL {type(e).__name__}: {e}")
            traceback.print_exc()
            with open(path + ".fail", "w") as f:
                f.write(traceback.format_exc())
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
