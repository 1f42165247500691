"""Sharding rules: parameter pytree -> PartitionSpec tree, per (arch, shape).

Strategy (DESIGN.md §5):
  * FSDP/ZeRO-3: every weight matrix shards its d_model-sized axis over
    "data"; per-layer slices are all-gathered just-in-time inside the layer
    scan (XLA SPMD inserts the gather on the scan body's slice).
  * TP: d_ff / vocab / d_inner / expert-ffn shard over "model".
  * SP: activations between blocks are sequence-sharded over "model"
    (constraints in the model code).
  * Decode caches shard (batch over dp when divisible) + head_dim over
    "model" (head_dim is a multiple of 16 for every assigned arch); the
    single-sequence long-context cells shard kv-heads over "data".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import auto_mesh

PROFILES = ("fsdp", "ddp", "decode_tp")


def parse_mesh(spec: str) -> tuple:
    """Parse an ``RxC`` CLI mesh spec ("2x4" -> (2, 4); "8" -> (8, 1))."""
    parts = spec.lower().replace("×", "x").split("x")
    if len(parts) == 1:
        parts = parts + ["1"]
    if len(parts) != 2 or not all(p.strip().isdigit() for p in parts):
        raise ValueError(f"bad mesh spec {spec!r}; expected RxC like 2x4")
    return int(parts[0]), int(parts[1])


def make_mesh(shape) -> jax.sharding.Mesh:
    """(data, model) mesh over the available devices; shape may be a
    ``parse_mesh`` tuple or an ``RxC`` string."""
    if isinstance(shape, str):
        shape = parse_mesh(shape)
    r, c = shape
    n = jax.device_count()
    if r * c != n:
        raise ValueError(f"mesh {r}x{c} wants {r * c} devices, have {n}")
    return auto_mesh((r, c), ("data", "model"))


def distribution_for(mesh, profile: str = "fsdp", numerics_policy=None):
    """The Distribution a launch profile runs the model under, with the
    deployed plan's NumericsPolicy riding along (threaded into shard_map'd
    train/serve steps by make_train_step / serve)."""
    from repro.models.layers import Distribution
    if profile not in PROFILES:
        raise ValueError(f"unknown profile {profile!r}; one of {PROFILES}")
    return Distribution(mesh=mesh, dp_axes=("data",), tp_axis="model",
                        joint_tp=profile == "decode_tp",
                        numerics_policy=numerics_policy)


def _leaf_spec(path: str, ndim: int, extra_lead: int) -> P:
    """PartitionSpec for a parameter leaf; ``extra_lead`` = # stacked layer
    dims to leave unsharded (1 for scanned layers, 2 for hybrid groups)."""
    lead = (None,) * extra_lead

    def pad(spec):                     # right-pad with None to ndim
        spec = lead + spec
        return P(*(spec + (None,) * (ndim - len(spec))))

    name = path.split("/")[-1]
    # --- non-layer params (extra_lead == 0) -------------------------------
    if name == "embed":
        return P("model", "data")
    if name == "lm_head":
        return P("data", "model")
    # --- norms / scalars / biases ------------------------------------------
    if "norm" in name or name in ("A_log", "D", "dt_bias", "bq", "bk", "bv"):
        if name == "norm" and ndim - extra_lead == 1:
            return pad(("model",) if _is_ssm_norm(path) else (None,))
        return pad((None,) * (ndim - extra_lead))
    # --- attention ----------------------------------------------------------
    if name in ("wq", "wk", "wv"):
        return pad(("data", None))
    if name == "wo":
        return pad((None, "data"))
    # --- dense MLP -----------------------------------------------------------
    if name in ("w_in", "w_gate") and ndim - extra_lead == 2:
        return pad(("data", "model"))
    if name == "w_out" and ndim - extra_lead == 2:
        return pad(("model", "data"))
    # --- MoE ------------------------------------------------------------------
    if name == "router":
        return pad(("data", None))
    if name in ("w_in", "w_gate") and ndim - extra_lead == 3:
        return pad((None, "data", "model"))
    if name == "w_out" and ndim - extra_lead == 3:
        return pad((None, "model", "data"))
    # --- SSM -------------------------------------------------------------------
    if name in ("in_x", "in_z"):
        return pad(("data", "model"))
    if name in ("in_B", "in_C", "in_dt"):
        return pad(("data", None))
    if name == "conv_x":
        return pad((None, "model"))
    if name in ("conv_B", "conv_C"):
        return pad((None, None))
    if name == "out":
        return pad(("model", "data"))
    return pad((None,) * (ndim - extra_lead))


def _is_ssm_norm(path: str) -> bool:
    return path.endswith("ssm/norm")


def _lead_of(path: str, cfg) -> int:
    """How many stacked leading dims a leaf has."""
    parts = path.split("/")
    if parts[0] in ("layers", "enc_layers", "dec_layers"):
        return 2 if (cfg.family == "hybrid" and parts[0] == "layers") else 1
    return 0


def param_specs(cfg, abstract_params, profile: str = "fsdp", mesh=None):
    """PartitionSpec pytree matching the params pytree.

    Profiles (§Perf):
      fsdp      — ZeRO-3: weights sharded over data (largest axis) + TP over
                  model; per-layer just-in-time gathers. Right for models
                  whose weights don't fit replicated.
      ddp       — weights replicated (embed/lm_head stay vocab-TP), optimizer
                  state sharded over data (ZeRO-1). Right for small models
                  where per-step weight gathers dominate the collective term.
      decode_tp — weights-stay-put serving: every projection sharded over the
                  JOINT (data, model) axes on a 256-divisible dim, so decode
                  reads weights in place with zero gathers (activations are
                  tiny and psum'd).
    """

    def visit(tree, prefix):
        if isinstance(tree, dict):
            return {k: visit(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in tree.items()}
        lead = _lead_of(prefix, cfg)
        if profile == "ddp":
            return _leaf_spec_ddp(prefix, tree.ndim, lead)
        if profile == "decode_tp":
            return _leaf_spec_decode_tp(prefix, tree, lead, mesh)
        return _leaf_spec(prefix, tree.ndim, lead)

    return visit(abstract_params, "")


def _leaf_spec_ddp(path: str, ndim: int, lead: int) -> P:
    name = path.split("/")[-1]
    if name == "embed":
        return P("model", None)
    if name == "lm_head":
        return P(None, "model")
    return P(*([None] * ndim))


def _leaf_spec_decode_tp(path: str, leaf, lead: int, mesh) -> P:
    name = path.split("/")[-1]
    joint = tuple(a for a in mesh.axis_names)        # all axes combined
    n_joint = 1
    for a in joint:
        n_joint *= mesh.shape[a]
    shape = leaf.shape
    spec = [None] * leaf.ndim
    if name in ("embed", "lm_head"):
        v_dim = 0 if name == "embed" else 1
        if shape[v_dim] % n_joint == 0:
            spec[v_dim] = joint
        else:
            spec[v_dim] = "model"
        return P(*spec)
    if leaf.ndim - lead < 2:                          # norms/bias/scalars
        return P(*spec)
    # prefer col-parallel on the last dim, else row-parallel, else model-only
    for dims, axes in (((-1,), joint), ((-2,), joint),
                       ((-1,), "model"), ((-2,), "model")):
        d = dims[0]
        n = n_joint if axes == joint else mesh.shape["model"]
        if shape[d] % n == 0:
            spec[d] = axes
            return P(*spec)
    return P(*spec)


def param_shardings(cfg, abstract_params, mesh, profile: str = "fsdp"):
    specs = param_specs(cfg, abstract_params, profile=profile, mesh=mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def opt_state_shardings(cfg, abstract_opt_state, param_shardings_tree, mesh,
                        profile: str = "fsdp"):
    """fsdp/decode_tp: mu/nu shadow the param shardings. ddp (ZeRO-1): mu/nu
    shard over data on each leaf's first data-divisible dim even though the
    params are replicated. Scalars replicated."""
    rep = NamedSharding(mesh, P())
    n_data = mesh.shape["data"]

    def zero1(leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return rep
        for d, size in enumerate(leaf.shape):
            if size % n_data == 0 and size >= n_data:
                spec = [None] * leaf.ndim
                spec[d] = "data"
                return NamedSharding(mesh, P(*spec))
        return rep

    def shadow(node, params_node):
        return jax.tree.map(
            lambda l, s: s if hasattr(l, "ndim") and l.ndim > 0 else rep,
            node, params_node)

    out = {}
    for k, v in abstract_opt_state.items():
        if k in ("mu", "nu"):
            out[k] = (jax.tree.map(zero1, v) if profile == "ddp"
                      else shadow(v, param_shardings_tree))
        else:
            out[k] = jax.tree.map(lambda _: rep, v)
    return out


# ---------------------------------------------------------------------------
# Batch / cache shardings per shape kind
# ---------------------------------------------------------------------------
def batch_shardings(cfg, shape, mesh):
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp = dp if len(dp) > 1 else dp[0]
    B = shape.global_batch
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape[a]
    bspec = dp if B % dp_size == 0 else None

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    out = {"tokens": ns(bspec, "model"), "targets": ns(bspec, "model"),
           "loss_mask": ns(bspec, "model")}
    if shape.kind == "decode":
        out = {"tokens": ns(bspec, None)}
    if cfg.family == "vlm":
        out["patches"] = ns(bspec, None, None)
    if cfg.family == "encdec":
        out["frames"] = ns(bspec, None, None)
    return out


def cache_shardings(cfg, shape, mesh, abstract_cache, profile: str = "fsdp"):
    """Decode-cache shardings (see module docstring).

    decode_tp profile: the KV cache shards its SEQUENCE dim over "model"
    (flash-decode partition): scores stay seq-sharded, the softmax reduces
    with tiny scalar psums and the PV contraction psums one (B,H,hd) vector
    per layer — instead of psumming (B,H,S)-sized score tensors when the
    head_dim is the sharded contraction. The size-1 cache write at position
    `len` lowers to a masked in-place update on the owning shard."""
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp = dp if len(dp) > 1 else dp[0]
    B = shape.global_batch
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape[a]
    b_ok = B % dp_size == 0
    bspec = dp if b_ok else None
    head_axis = None if b_ok else "data"   # B=1 cells: kv heads over data

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    def visit(path, leaf):
        name = path[-1]
        nd = leaf.ndim
        spec = [None] * nd
        if name in ("k", "v"):
            # (..., B, Hkv, S, hd): hd over model (fsdp) or seq over model
            # (decode_tp flash-decode); batch over dp (or kv heads over data
            # for the B=1 long-context cells when divisible)
            if profile == "decode_tp" and _div(leaf.shape[-2],
                                               mesh.shape["model"]):
                spec[-2] = "model"
            elif _div(cfg.head_dim, mesh.shape["model"]):
                spec[-1] = "model"
            if b_ok:
                spec[-4] = bspec
            elif cfg.n_kv_heads % mesh.shape["data"] == 0:
                spec[-3] = "data"
            return ns(*spec)
        if name in ("k_scale", "v_scale"):
            # (..., B, Hkv, S): follow the cache's batch/seq sharding
            if profile == "decode_tp" and _div(leaf.shape[-1],
                                               mesh.shape["model"]):
                spec[-1] = "model"
            if b_ok:
                spec[-3] = bspec
            return ns(*spec)
        if name == "state":      # (..., B, g, e, p, n): e over model
            if _div(cfg.ssm_heads // cfg.ssm_groups, mesh.shape["model"]):
                spec[-3] = "model"
            if b_ok:
                spec[-5] = bspec
            return ns(*spec)
        if name.startswith("conv_"):  # (..., B, w-1, C)
            if name == "conv_x" and _div(cfg.d_inner, mesh.shape["model"]):
                spec[-1] = "model"
            if b_ok:
                spec[-3] = bspec
            return ns(*spec)
        if name == "len":
            return ns()
        return ns(*spec)

    return _map_with_path(visit, abstract_cache)


def _div(a, b):
    return a % b == 0


def _map_with_path(fn, tree, path=()):
    if isinstance(tree, dict):
        return {k: _map_with_path(fn, v, path + (k,)) for k, v in tree.items()}
    return fn(path, tree)
