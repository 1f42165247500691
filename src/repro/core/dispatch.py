"""BLAS-style transparent dispatch — the OpenBLAS-swap analogue.

High-level model code never calls ``jnp.dot`` directly; it calls
``repro.core.dispatch.gemm(a, b, site="attn_qk")``.  A ``NumericsPolicy``
(installed via context manager, like re-linking OpenBLAS at runtime) maps each
*call-site* to a ``GemmConfig`` ⟨format, accumulator, execution target⟩, so an
unmodified model can be re-run under any numerics without touching its code —
the paper's "runtime execution flow".

Site identity is structured: a ``GemmSite(name, phase, operand)`` names not
just the call-site but the *computation stage* running through it. Model code
keeps passing plain strings ("attn_qk" parses to the forward site); the
dispatch entry points carry a ``jax.custom_vjp`` so the two backward GEMMs of
every site (dL/dA = G·Bᵀ, dL/dB = Aᵀ·G) dispatch as first-class sites of
their own — ``attn_qk@bwd.dA`` / ``attn_qk@bwd.dB`` — with their own policy
lookup, tracing, and plan assignments. Gradients have very different dynamic
range and cancellation behavior than forwards; phase-aware identity is what
lets the tailoring search treat them that way.

Modes:
    native   - MXU fast path: inputs cast to the format's dtype,
               jnp.dot(..., preferred_element_type=f32). Default everywhere;
               this is what the multi-pod dry-run lowers.
    simulate - bit-exact ⟨ovf,msb,lsb⟩ FDP (repro.core.fdp).
    pallas   - the Pallas TPU kernel (interpret on CPU).

Batched inputs (ndim > 2) are supported in all modes (simulate/pallas vmap
over leading dims; native uses dot_general via jnp.matmul semantics).

Autodiff support is *reverse-mode only*: the custom_vjp that makes backward
GEMMs first-class sites has no defjvp, so ``jax.jvp``/``jacfwd`` through the
dispatch entry points raise (forward-mode was never meaningful for the FDP
modes anyway — their integer limb algebra has no useful tangents).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.registry import default_registry as _obs_registry

from .accumulator import SAFE_CHUNK, AccumulatorSpec
from .formats import BF16, FP32, FloatFormat, PositFormat, get_format

Array = jax.Array


# ---------------------------------------------------------------------------
# Structured site identity
# ---------------------------------------------------------------------------
PHASES = ("fwd", "bwd")
OPERANDS = ("", "dA", "dB")


@dataclasses.dataclass(frozen=True)
class GemmSite:
    """Structured identity of one GEMM computation stage.

    ``name`` is the model-level call-site ("attn_qk"), ``phase`` the autodiff
    stage ("fwd" | "bwd") and ``operand`` which backward GEMM this is
    ("dA" for the input/activation gradient G·Bᵀ, "dB" for the weight
    gradient Aᵀ·G; empty for forward). The canonical string form is what
    every registry (``sites_seen``, calibration traces, precision plans)
    keys on:

        fwd:  "attn_qk"
        bwd:  "attn_qk@bwd.dA", "attn_qk@bwd.dB"
    """

    name: str
    phase: str = "fwd"
    operand: str = ""

    def __post_init__(self):
        if self.phase not in PHASES:
            raise ValueError(f"bad site phase {self.phase!r}")
        if self.operand not in OPERANDS:
            raise ValueError(f"bad site operand {self.operand!r}")
        if self.phase == "fwd" and self.operand:
            raise ValueError("forward sites carry no operand tag")
        if "@" in self.name or "." in self.name:
            raise ValueError(f"site name {self.name!r} may not contain @ or .")

    @property
    def key(self) -> str:
        """Canonical string key (forward sites stay plain names, so every
        pre-existing string-keyed artifact reads unchanged)."""
        if self.phase == "fwd":
            return self.name
        return (f"{self.name}@{self.phase}.{self.operand}"
                if self.operand else f"{self.name}@{self.phase}")

    @property
    def scope(self) -> str:
        """The ``jax.named_scope`` its contraction runs under, spelled to
        survive into the compiled HLO's ``op_name`` metadata (a scope's
        ``@...`` is cut there): ``site.attn_qk.fwd``,
        ``site.attn_qk.bwd.dA``. Profiler op time maps back to the site
        through it."""
        return ".".join(filter(None, ("site", self.name, self.phase,
                                      self.operand)))

    def bwd(self, operand: str) -> "GemmSite":
        return GemmSite(self.name, "bwd", operand)

    @classmethod
    def parse(cls, site: Union[str, "GemmSite"]) -> "GemmSite":
        """String shim: model call-sites keep passing plain names."""
        if isinstance(site, GemmSite):
            return site
        if "@" not in site:
            return cls(site)
        name, _, rest = site.partition("@")
        phase, _, operand = rest.partition(".")
        return cls(name, phase, operand)


def _parse_pattern(pat: str) -> tuple:
    """Pattern grammar ``NAME[@PHASE[.OPERAND]]``: NAME may end in ``*``
    (prefix match, bare ``*`` matches everything); PHASE/OPERAND may be
    ``*``. A pattern with no ``@`` is *forward-only* — exactly the v1
    semantics, so pre-phase plans never silently capture gradient GEMMs."""
    if "@" in pat:
        name, _, rest = pat.partition("@")
        phase, _, op = rest.partition(".")
        return name, phase, (op or "*")
    return pat, "fwd", "*"


def _match_score(pat: str, site: GemmSite) -> Optional[int]:
    """Specificity of a pattern against a site, or None on no match.
    Exact name beats prefix wildcard; exact phase beats ``*``; exact operand
    beats ``*`` — so ``attn_qk@bwd.dA`` > ``attn_qk@bwd`` > ``attn_*@bwd``
    > ``*@bwd`` for a backward site, and forward lookups behave exactly as
    the flat-string v1 dispatch did."""
    name, phase, op = _parse_pattern(pat)
    if name == site.name:
        score = 8
    elif name.endswith("*") and site.name.startswith(name[:-1]):
        score = 2
    else:
        return None
    if phase == site.phase:
        score += 4
    elif phase != "*":
        return None
    if op == site.operand:
        score += 1
    elif op != "*":
        return None
    return score


@dataclasses.dataclass(frozen=True)
class GemmConfig:
    fmt: FloatFormat | PositFormat = BF16
    acc: Optional[AccumulatorSpec] = None      # None => native fp32 accumulate
    mode: str = "native"                       # native | simulate | pallas

    def __post_init__(self):
        if self.mode not in ("native", "simulate", "pallas"):
            raise ValueError(self.mode)
        if self.mode != "native" and self.acc is None:
            raise ValueError(f"mode={self.mode} requires an AccumulatorSpec")

    def tag(self) -> str:
        acc = (f"<{self.acc.ovf},{self.acc.msb},{self.acc.lsb}>"
               if self.acc else "fp32acc")
        return f"{self.fmt.name}/{acc}/{self.mode}"


def widen_config(cfg: GemmConfig) -> GemmConfig:
    """The gradient-safe fallback for sites with no explicit bwd assignment:
    full-precision inputs, and for FDP modes the paper's ⟨30,30,-30⟩ 91-bit
    accumulator (overflow-free and effectively exact on any sane gradient
    range). Backward GEMMs cancel harder and swing wider than their forward
    twins, so an unassigned bwd site must *widen*, never inherit."""
    if cfg.mode == "native":
        return GemmConfig(FP32, None, "native")
    return GemmConfig(FP32, AccumulatorSpec.paper_91bit(), cfg.mode)


@dataclasses.dataclass(frozen=True)
class NumericsPolicy:
    """Call-site -> GemmConfig mapping. ``default`` covers unlisted sites.

    Patterns are phase-aware (see ``_parse_pattern``): plain names and
    trailing-``*`` prefixes match *forward* sites only; ``name@bwd``,
    ``name@bwd.dA`` and the wildcard fallback ``*@bwd`` address backward
    sites. The most specific matching pattern wins; ties go to the earliest
    override (``with_override`` prepends)."""

    default: GemmConfig = GemmConfig()
    overrides: tuple = ()                      # tuple[(pattern, GemmConfig)]
    name: str = "default"
    # Non-GEMM precision assignments keyed by qformat site keys
    # ("opt.m@state", "grad_psum@coll") mapping to qformat.QuantConfig.
    # Kept out of ``overrides`` on purpose: aux keys don't parse as
    # GemmSites, and GemmConfig consumers must never see them.
    aux: tuple = ()                            # tuple[(site_key, QuantConfig)]

    def lookup(self, site: Union[str, GemmSite]) -> GemmConfig:
        s = GemmSite.parse(site)
        best, best_score = None, -1
        for pat, cfg in self.overrides:
            sc = _match_score(pat, s)
            if sc is not None and sc > best_score:
                best, best_score = cfg, sc
        return best if best is not None else self.default

    def aux_lookup(self, site_key: str):
        """QuantConfig for an aux (state/collective) site key, or None when
        the policy leaves that site at its fp32 default."""
        for key, cfg in self.aux:
            if key == site_key:
                return cfg
        return None

    def with_override(self, pattern: str, cfg: GemmConfig) -> "NumericsPolicy":
        return dataclasses.replace(
            self, overrides=((pattern, cfg),) + tuple(self.overrides))

    def with_aux(self, site_key: str, cfg) -> "NumericsPolicy":
        kept = tuple((k, c) for k, c in self.aux if k != site_key)
        return dataclasses.replace(self, aux=((site_key, cfg),) + kept)


MXU_BF16 = NumericsPolicy(GemmConfig(BF16, None, "native"), name="mxu_bf16")
MXU_FP32 = NumericsPolicy(GemmConfig(FP32, None, "native"), name="mxu_fp32")
# The paper's flagship uniform numerics: every site through the bit-exact
# ⟨30,30,-30⟩ FDP. This is the accuracy oracle the tailoring search in
# ``repro.numerics`` compares candidate plans against.
FDP91 = NumericsPolicy(
    GemmConfig(FP32, AccumulatorSpec(ovf=30, msb=30, lsb=-30), "simulate"),
    name="fdp91_uniform")

_state = threading.local()
_UNSET = object()


def current_policy() -> NumericsPolicy:
    return getattr(_state, "policy", MXU_BF16)


@contextlib.contextmanager
def use_policy(policy: NumericsPolicy):
    """Swap the *per-thread* numerics (the LD_PRELOAD moment).

    Exception-safe and re-entrant: the previous state is restored even when
    the body raises, and a thread that never entered a policy context goes
    back to the process default (rather than having the default pinned onto
    it). The underlying state is ``threading.local`` so a policy installed
    in one thread never leaks into another.
    """
    if not isinstance(policy, NumericsPolicy):
        raise TypeError(f"use_policy expects a NumericsPolicy, got {policy!r}")
    prev = getattr(_state, "policy", _UNSET)
    _state.policy = policy
    try:
        yield policy
    finally:
        if prev is _UNSET:
            del _state.policy
        else:
            _state.policy = prev


# ---------------------------------------------------------------------------
# Site registry (introspection/report)
# ---------------------------------------------------------------------------
# Guarded by its own lock: sites are recorded at trace time from whatever
# thread is staging the computation (the thread-pool serving tests trace
# concurrently), and test fixtures reset it between cases so assertions
# never depend on which test dispatched first.
_SITES_SEEN: set = set()
_SITES_LOCK = threading.Lock()


def sites_seen() -> frozenset:
    """All GEMM call-site keys dispatched so far (canonical strings;
    backward sites appear as ``name@bwd.dA`` / ``name@bwd.dB``)."""
    with _SITES_LOCK:
        return frozenset(_SITES_SEEN)


def reset_sites_seen() -> None:
    """Clear the process-global site registry (test isolation)."""
    with _SITES_LOCK:
        _SITES_SEEN.clear()


def _note_site(key: str) -> None:
    with _SITES_LOCK:
        _SITES_SEEN.add(key)


# ---------------------------------------------------------------------------
# Calibration tracing hook (repro.numerics)
# ---------------------------------------------------------------------------
# When a hook is installed (see repro.numerics.trace.calibrate), every
# dispatched GEMM reports (site_key, cfg, a, b, out) so the tailoring
# subsystem can record per-site operand statistics. Backward GEMMs report
# under their own phase-qualified keys, so a calibration run that includes a
# ``value_and_grad`` step profiles gradient exponent ranges and cancellation
# separately from the forward pass. The hook runs at *trace* time, so it may
# stage jnp ops / jax.debug.callback into the computation; it must be
# None-checked here to keep the production path zero-cost.
_TRACE_HOOK = None          # composed view over the slots below; None-checked
_PRIMARY_HOOK = None        # the calibration slot (set_trace_hook)
_EXTRA_HOOKS: list = []     # additive observers (repro.obs monitors)


def _recompose_hooks() -> None:
    global _TRACE_HOOK
    hooks = ([_PRIMARY_HOOK] if _PRIMARY_HOOK is not None else []) \
        + list(_EXTRA_HOOKS)
    if not hooks:
        _TRACE_HOOK = None
    elif len(hooks) == 1:
        _TRACE_HOOK = hooks[0]
    else:
        def _fanout(site_key, cfg, a, b, out, _hooks=tuple(hooks)):
            for h in _hooks:
                h(site_key, cfg, a, b, out)
        _TRACE_HOOK = _fanout


def set_trace_hook(hook):
    """Install (or clear, with None) the *primary* calibration hook. Returns
    the previously installed primary hook so callers can restore it. Extra
    hooks installed via ``add_trace_hook`` (live monitors) are a separate
    channel and keep firing across set/restore pairs."""
    global _PRIMARY_HOOK
    prev = _PRIMARY_HOOK
    _PRIMARY_HOOK = hook
    _recompose_hooks()
    return prev


def add_trace_hook(hook):
    """Install an *additional* trace hook alongside the calibration slot —
    the seam ``repro.obs.monitor`` uses, so production monitoring and a
    concurrent ``calibrate()`` co-exist. Returns a zero-arg remover."""
    _EXTRA_HOOKS.append(hook)
    _recompose_hooks()

    def _remove():
        try:
            _EXTRA_HOOKS.remove(hook)
        except ValueError:
            pass
        _recompose_hooks()
    return _remove


def _maybe_trace(site_key, cfg, a, b, out):
    if _TRACE_HOOK is not None:
        _TRACE_HOOK(site_key, cfg, a, b, out)
    return out


# ---------------------------------------------------------------------------
# GemmPlan: cached block-size plans for the Pallas execution engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """Block sizes for one (shape, fmt, spec, backend) problem instance.

    ``source`` records provenance: "heuristic" (shape-derived table),
    "measured" (autotuned on this host) or "override" (register_plan).
    """

    bm: int
    bn: int
    bk: int
    source: str = "heuristic"

    @property
    def tile(self) -> tuple:
        return (self.bm, self.bn, self.bk)

    def fit(self, m: int, n: int, k: int) -> "GemmPlan":
        """Clamp this plan to one problem instance under the TPU tiling
        rule, and bk to the SAFE_CHUNK carry-headroom bound. The ONE place
        a deployable schedule is constructed — the kernel wrappers, the
        autotuner, and the persisted zoo all fit through here, so illegal
        schedules cannot exist.

        The kernels' blocks are A (bm, bk), B (bk, bn) and O (bm, bn): bm
        only ever sits on sublanes, bn and bk also sit on lanes. So bm is a
        multiple of 8, and bn and bk are multiples of 128 — each block dim
        stopping at the problem dim rounded up to 8, which the wrappers pad
        to, so a clamped block is the whole padded array dim."""
        bm = _fit_dim(self.bm, m, SUBLANES)
        bn = _fit_dim(self.bn, n, LANES)
        bk = _fit_dim(min(self.bk, SAFE_CHUNK), k, LANES)
        if (bm, bn, bk) == (self.bm, self.bn, self.bk):
            return self
        return dataclasses.replace(self, bm=bm, bn=bn, bk=bk)


# The TPU's (sublane, lane) vector tile for 32-bit data: a block's last two
# dims must be multiples of these, or equal to the array's dims.
SUBLANES, LANES = 8, 128


def _fit_dim(block: int, dim: int, align: int) -> int:
    """One block dim under the tiling rule: the whole dim rounded up to 8
    when the block covers it, else the block rounded down to ``align``
    (at least ``align``)."""
    full = _ceil8(dim)
    if block >= full:
        return full
    return min(full, max(align, block - block % align))


@dataclasses.dataclass(frozen=True)
class PlanCacheStats:
    """Typed snapshot of the process-global GemmPlan cache counters.

    ``persisted_loads`` counts entries installed from a ScheduleZoo file —
    a warm process serving entirely out of a checked-in zoo shows
    ``misses == 0`` and ``persisted_loads > 0``.

    .. deprecated:: the counters now live in the unified obs registry
       (``repro_plan_cache_ops_total{op=...}`` / ``repro_plan_cache_size``);
       this class and :func:`plan_cache_stats` are thin views kept for one
       release — read ``repro.obs.default_registry().snapshot()`` instead.
    """

    size: int
    hits: int
    misses: int
    autotuned: int
    persisted_loads: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


_PLAN_CACHE: dict = {}
_PLAN_LOCK = threading.Lock()

# Plan-cache counters are registry-backed (repro.obs is stdlib-only at this
# layer): one source of truth for hits/misses/autotunes across the legacy
# stats() views and the Prometheus/JSON exposition.
_PLAN_OPS = _obs_registry().counter(
    "repro_plan_cache_ops_total",
    "GemmPlan cache operations (hit/miss/autotuned/persisted_load)", ("op",))
_PLAN_SIZE = _obs_registry().gauge(
    "repro_plan_cache_size", "resident GemmPlan cache entries")


def _plan_stats_inc(op: str, n: int = 1) -> None:
    _PLAN_OPS.inc(n, op=op)

# Candidate tiles for the measured path (fitted to the problem size).
AUTOTUNE_CANDIDATES = (
    (8, 128, 512), (32, 128, 512), (128, 128, 512), (128, 128, 1024),
    (128, 256, 512), (256, 256, 512),
)


def _ceil8(x: int) -> int:
    return max(8, -(-x // 8) * 8)


def _heuristic_plan(batch: int, m: int, n: int, k: int) -> GemmPlan:
    """Shape-derived default tile: 128x128 output tiles with a deep K block
    (large bk amortizes the once-per-block carry normalization), fitted so
    the M/N blocks stop at the problem size and padding work stays bounded."""
    del batch
    return GemmPlan(128, 128, 1024, source="heuristic").fit(m, n, k)


def _plan_key(batch, m, n, k, fmt, spec, backend):
    return (batch, m, n, k, fmt.name, spec, backend)


def plan_gemm(m: int, n: int, k: int, *, fmt, spec: AccumulatorSpec,
              batch: int = 1, backend: Optional[str] = None,
              autotune: bool = False) -> GemmPlan:
    """Resolve (and cache) the block-size plan for one GEMM problem.

    The cache is keyed by (batch, M, N, K, fmt, spec, backend) so a compiled
    pallas_call is reused across calls with the same signature. ``autotune``
    measures AUTOTUNE_CANDIDATES on synthetic data and caches the winner —
    upgrading a previously cached *heuristic* entry in place (measured and
    override entries are never re-measured); the default is the heuristic
    table (no compilation at plan time).
    """
    backend = backend or jax.default_backend()
    key = _plan_key(batch, m, n, k, fmt, spec, backend)
    with _PLAN_LOCK:
        cached = _PLAN_CACHE.get(key)
    if cached is not None and (
            not autotune or cached.source in ("measured", "override")):
        _plan_stats_inc("hits")
        return cached
    if autotune:
        plan = _measure_plan(m, n, k, fmt=fmt, spec=spec)
        _plan_stats_inc("autotuned")
        _plan_stats_inc("misses")
        with _PLAN_LOCK:
            _PLAN_CACHE[key] = plan
            _PLAN_SIZE.set(len(_PLAN_CACHE))
        return plan
    plan = _heuristic_plan(batch, m, n, k)
    _plan_stats_inc("misses")
    with _PLAN_LOCK:
        plan = _PLAN_CACHE.setdefault(key, plan)
        _PLAN_SIZE.set(len(_PLAN_CACHE))
        return plan


def register_plan(m: int, n: int, k: int, plan: GemmPlan, *, fmt,
                  spec: AccumulatorSpec, batch: int = 1,
                  backend: Optional[str] = None) -> None:
    """Pin a plan (e.g. from an offline sweep) for a problem signature."""
    backend = backend or jax.default_backend()
    key = _plan_key(batch, m, n, k, fmt, spec, backend)
    with _PLAN_LOCK:
        _PLAN_CACHE[key] = dataclasses.replace(plan, source="override")
        _PLAN_SIZE.set(len(_PLAN_CACHE))


def plan_cache_stats() -> PlanCacheStats:
    """Deprecated thin view over the obs-registry plan-cache counters (see
    ``PlanCacheStats``); kept so existing callers/tests read unchanged."""
    with _PLAN_LOCK:
        size = len(_PLAN_CACHE)
    return PlanCacheStats(
        size=size,
        hits=int(_PLAN_OPS.value(op="hits")),
        misses=int(_PLAN_OPS.value(op="misses")),
        autotuned=int(_PLAN_OPS.value(op="autotuned")),
        persisted_loads=int(_PLAN_OPS.value(op="persisted_loads")))


def clear_plan_cache() -> None:
    with _PLAN_LOCK:
        _PLAN_CACHE.clear()
        _PLAN_SIZE.set(0)
    _PLAN_OPS.clear()


# Candidate timing discipline (shared with benchmarks/bench_gemm.py and the
# regression gate's --min-seconds floor): best of MEASURE_REPS samples, each
# amortized over enough calls to clear the sub-ms timer noise floor.
MEASURE_REPS = 3
MEASURE_MIN_SECONDS = 1e-3


def _time_candidate(fn, *, reps: int = MEASURE_REPS,
                    min_seconds: float = MEASURE_MIN_SECONDS) -> float:
    """Best-of-``reps`` seconds per call for ``fn`` (already compiled/warm).
    A single post-warmup sample is noise below ~1 ms on this timer, so each
    sample loops the call until it clears ``min_seconds`` of wall time."""
    import time

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    dt = max(time.perf_counter() - t0, 1e-9)
    inner = max(1, math.ceil(min_seconds / dt))
    best = dt if inner == 1 else float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def _measure_plan(m: int, n: int, k: int, *, fmt,
                  spec: AccumulatorSpec) -> GemmPlan:
    """Time AUTOTUNE_CANDIDATES on random operands and return the winner."""
    from repro.kernels import ops as kops

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    if isinstance(fmt, PositFormat):
        a, b = fmt.from_float(a), fmt.from_float(b)

    heur = _heuristic_plan(1, m, n, k)
    cands = {GemmPlan(*t).fit(m, n, k).tile
             for t in AUTOTUNE_CANDIDATES + (heur.tile,)}
    best, best_t = heur.tile, float("inf")
    for tile in sorted(cands):
        plan = GemmPlan(*tile)
        fn = lambda: kops.fdp_gemm(a, b, spec=spec, fmt=fmt, plan=plan)
        jax.block_until_ready(fn())              # compile + warm
        dt = _time_candidate(fn)
        if dt < best_t:
            best, best_t = tile, dt
    return GemmPlan(*best, source="measured")


def _plan_for_operands(a: Array, b: Array, cfg: GemmConfig,
                       autotune: bool = False) -> GemmPlan:
    """Plan lookup from jnp.matmul-shaped operands (1-D promotion, broadcast
    batch dims). Safe under jit tracing: only static shapes are consulted, and
    autotune (which executes kernels) is disabled for tracers."""
    m = a.shape[-2] if a.ndim >= 2 else 1
    k = a.shape[-1]
    n = b.shape[-1] if b.ndim >= 2 else 1
    batch_dims = jnp.broadcast_shapes(
        a.shape[:-2] if a.ndim > 2 else (), b.shape[:-2] if b.ndim > 2 else ())
    batch = math.prod(batch_dims) if batch_dims else 1
    if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
        autotune = False
    return plan_gemm(m, n, k, fmt=cfg.fmt, spec=cfg.acc, batch=batch,
                     autotune=autotune)


# ---------------------------------------------------------------------------
# Dispatch core
# ---------------------------------------------------------------------------
def _dispatch(site: GemmSite, cfg: GemmConfig, a: Array, b: Array, *,
              plan: Optional[GemmPlan] = None) -> Array:
    """Run one matmul as one *site*: register the key, execute under the
    resolved config, report to the calibration hook. Every entry point —
    forward and backward — funnels through here so phase-qualified sites are
    first-class everywhere (``sites_seen``, traces, plans). The
    contraction runs under the site's ``named_scope`` (``GemmSite.scope``)."""
    _note_site(site.key)
    with jax.named_scope(site.scope):
        out = _execute(cfg, a, b, plan=plan)
    return _maybe_trace(site.key, cfg, a, b, out)


def _execute(cfg: GemmConfig, a: Array, b: Array, *,
             plan: Optional[GemmPlan] = None) -> Array:
    """Run one matmul under a resolved GemmConfig (the mode switch, without
    policy lookup or trace reporting — shared by gemm/ragged_gemm)."""
    if cfg.mode == "native":
        dt = cfg.fmt.jnp_dtype
        return jnp.matmul(a.astype(dt), b.astype(dt),
                          preferred_element_type=jnp.float32)

    # FDP modes: float inputs are rounded onto the format's grid first (the
    # paper's format front end — bf16 under a wide accumulator really sees
    # bf16 operands); posit carriers are already bit patterns.
    if isinstance(cfg.fmt, FloatFormat):
        a, b = cfg.fmt.quantize(a), cfg.fmt.quantize(b)

    if cfg.mode == "simulate":
        from . import fdp
        f = lambda x, y: fdp.fdp_gemm(x, y, cfg.acc, cfg.fmt)
        return _batched_apply(f, a, b)

    # pallas: plan-cached block sizes, native batched grid for N-D inputs
    from repro.kernels import ops as kops
    plan = plan or _plan_for_operands(a, b, cfg)
    return kops.fdp_gemm_nd(a, b, spec=cfg.acc, fmt=cfg.fmt, plan=plan)


def _unbroadcast(x: Array, shape: tuple) -> Array:
    """Sum a cotangent down to a (numpy-broadcast) primal operand shape."""
    shape = tuple(shape)
    if x.shape == shape:
        return x
    extra = x.ndim - len(shape)
    if extra:
        x = jnp.sum(x, axis=tuple(range(extra)))
    axes = tuple(i for i, (xs, ps) in enumerate(zip(x.shape, shape))
                 if ps == 1 and xs != 1)
    if axes:
        x = jnp.sum(x, axis=axes, keepdims=True)
    return x


# -- sharded contraction: cross-device reduction under the site's spec ------
def _execute_reduce(cfg: GemmConfig, a: Array, b: Array, axis_name) -> Array:
    """One K-sharded matmul: local partial contraction + cross-device
    reduction over ``axis_name``, under a resolved GemmConfig.

    native mode reduces the local f32 partials with a float psum (order-
    dependent, like any stock all-reduce). FDP modes reduce the accumulator
    *register*: local limbs from ``fdp.fdp_gemm_limbs``, an exact integer
    ``fdp_psum`` across devices, then the single read-out rounding — so the
    sharded result is bit-identical to the unsharded ``fdp_gemm``, for any
    mesh shape or reduction order (the paper's property lifted to the
    collective layer). pallas mode routes its cross-device reduction through
    the same simulate limb path: the Pallas kernel computes final floats, not
    registers, and the two are validated bit-identical — the limb psum is the
    semantics both implement.
    """
    if cfg.mode == "native":
        return jax.lax.psum(_execute(cfg, a, b), axis_name)

    if a.ndim != 2 or b.ndim != 2:
        raise NotImplementedError(
            "sharded FDP contraction (reduce_axis=...) supports 2-D operands")
    if isinstance(cfg.fmt, FloatFormat):
        a, b = cfg.fmt.quantize(a), cfg.fmt.quantize(b)
    from . import fdp
    from repro.parallel.collectives import fdp_psum  # deferred: imports us
    limbs = fdp.fdp_gemm_limbs(a, b, cfg.acc, cfg.fmt)
    return _acc_to_float(cfg.acc, fdp_psum(limbs, axis_name, cfg.acc))


def _acc_to_float(spec: AccumulatorSpec, limbs: Array) -> Array:
    from . import accumulator as acc_mod
    return acc_mod.to_float(spec, limbs)


def _dispatch_reduce(site: GemmSite, cfg: GemmConfig, a: Array, b: Array,
                     axis_name) -> Array:
    _note_site(site.key)
    with jax.named_scope(site.scope):
        out = _execute_reduce(cfg, a, b, axis_name)
    return _maybe_trace(site.key, cfg, a, b, out)


# -- gemm: policy-dispatched matmul with phase-aware gradient dispatch ------
@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gemm_vjp(ctx, a, b):
    site, pol, plan, reduce_axis = ctx
    if reduce_axis is not None:
        return _dispatch_reduce(site, pol.lookup(site), a, b, reduce_axis)
    return _dispatch(site, pol.lookup(site), a, b, plan=plan)


def _gemm_vjp_fwd(ctx, a, b):
    return _gemm_vjp(ctx, a, b), (a, b)


def _gemm_vjp_bwd(ctx, res, g):
    """The two backward GEMMs of a site, dispatched as sites of their own:
    dL/dA = G·Bᵀ under ``<site>@bwd.dA`` and dL/dB = Aᵀ·G under
    ``<site>@bwd.dB``. The policy captured at the forward call resolves both
    (deterministic: fwd and bwd of one computation always agree on the
    policy, even if the ambient context changed between them).

    A K-sharded forward (``reduce_axis`` set) needs NO backward collectives:
    with the cotangent g replicated (the psum output is), dA_loc = G·B_locᵀ
    and dB_loc = A_locᵀ·G are already exactly the local shards of the full
    gradients — so both backward GEMMs dispatch as ordinary local sites."""
    site, pol, _plan, _reduce_axis = ctx
    a, b = res
    # jnp.matmul 1-D promotion: lift to 2-D, compute, drop the unit dims.
    # Insert the N axis before the M axis so the 1-D x 1-D (vector dot)
    # case — where g is 0-d — lifts cleanly to (1, 1).
    a2 = a[None, :] if a.ndim == 1 else a
    b2 = b[:, None] if b.ndim == 1 else b
    g2 = g
    if b.ndim == 1:
        g2 = g2[..., None]
    if a.ndim == 1:
        g2 = g2[..., None, :]

    da_site, db_site = site.bwd("dA"), site.bwd("dB")
    da_cfg, db_cfg = pol.lookup(da_site), pol.lookup(db_site)

    da = _dispatch(da_site, da_cfg, g2, jnp.swapaxes(b2, -1, -2))
    da = _unbroadcast(da, a2.shape).reshape(a.shape).astype(a.dtype)

    if b2.ndim == 2:
        # weight gradient: one flattened Aᵀ·G GEMM over all leading dims
        # (bit-matches the autodiff contraction order: row-major = batch-major)
        af = a2.reshape(-1, a2.shape[-1])
        gf = g2.reshape(-1, g2.shape[-1])
        db = _dispatch(db_site, db_cfg, jnp.swapaxes(af, -1, -2), gf)
    else:
        db = _dispatch(db_site, db_cfg, jnp.swapaxes(a2, -1, -2), g2)
        db = _unbroadcast(db, b2.shape)
    db = db.reshape(b.shape).astype(b.dtype)
    return da, db


_gemm_vjp.defvjp(_gemm_vjp_fwd, _gemm_vjp_bwd)


def gemm(a: Array, b: Array, *, site: Union[str, GemmSite] = "generic",
         policy: Optional[NumericsPolicy] = None,
         plan: Optional[GemmPlan] = None,
         reduce_axis=None) -> Array:
    """Policy-dispatched matmul. Contracts a's last dim with b's second-to-last
    (jnp.matmul semantics). Output f32 (simulate/pallas) or f32/bf16 (native,
    preferred_element_type=f32 then cast by caller if desired).

    Differentiating through this call dispatches the two backward GEMMs as
    ``<site>@bwd.dA`` / ``<site>@bwd.dB`` under the same policy (see
    ``_gemm_vjp_bwd``). ``plan`` overrides the cached/heuristic block sizes
    for the forward call (pallas mode only; backward calls resolve their own).

    ``reduce_axis`` makes the contraction *sharding-aware*: inside
    shard_map/pmap with the K dim sharded over that mesh axis, each device
    contracts its local K-shard and the cross-device reduction runs under the
    site's resolved config — FDP sites through the exact limb-summed
    ``fdp_psum`` (bit-identical to single-device), native sites through a
    plain float psum. The output is replicated over ``reduce_axis``.
    """
    pol = policy or current_policy()
    return _gemm_vjp((GemmSite.parse(site), pol, plan, reduce_axis), a, b)


# -- grouped attention einsums ----------------------------------------------
_ROW_PAD = _obs_registry().counter(
    "repro_dispatch_row_pad_total",
    "native grouped attention contractions traced with one row per (batch, "
    "kv head), given a zero second row to stay a dot", ("site",))


def _grouped_native(site: GemmSite, cfg: GemmConfig, subscripts: str,
                    lhs: Array, rhs: Array) -> Array:
    """The native einsum of ``grouped_qk``/``grouped_av``: lhs
    (B,Kh,G,Sq,x) against rhs (B,Kh,Sk,hd), f32 out.

    With one row per (batch, kv head), ``G * Sq == 1`` (MHA decode), XLA
    rewrites the dot into an f32 multiply-reduce whose operand layouts make
    the TPU relay out the whole K/V slice it reads, at every call. A zero
    second row on the G axis keeps the contraction a dot that reads K/V as
    it lies; the real row is sliced back out. It runs at ``HIGHEST``, as
    the multiply-reduce it replaces ran in full f32."""
    dt = cfg.fmt.jnp_dtype
    lhs, rhs = lhs.astype(dt), rhs.astype(dt)
    with jax.named_scope(site.scope):
        if lhs.shape[2] * lhs.shape[3] != 1:
            return jnp.einsum(subscripts, lhs, rhs,
                              preferred_element_type=jnp.float32)
        _ROW_PAD.inc(site=site.key)
        lhs = jnp.pad(lhs, ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0)))
        out = jnp.einsum(subscripts, lhs, rhs,
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)
        return out[:, :, :1]


def _grouped_qk_execute(site: GemmSite, cfg: GemmConfig,
                        q: Array, k: Array) -> Array:
    """q (B,Kh,G,Sq,hd) x k (B,Kh,Sk,hd) -> (B,Kh,G,Sq,Sk).

    Native mode uses a real einsum so sequence-parallel sharding on Sq
    survives (a reshape that merges (G, Sq) would force XLA to replicate the
    sequence dim). Simulate/pallas modes run the flattened 2D dispatch."""
    _note_site(site.key)
    if cfg.mode == "native":
        out = _grouped_native(site, cfg, "bkgqd,bksd->bkgqs", q, k)
        if _TRACE_HOOK is not None:
            # report in jnp.matmul shape so the profiler sees the real
            # contraction: (B,Kh,G*Sq,hd) x (B,Kh,hd,Sk)
            B_, Kh_, G_, Sq_, hd_ = q.shape
            _maybe_trace(site.key, cfg, q.reshape(B_, Kh_, G_ * Sq_, hd_),
                         jnp.swapaxes(k, -1, -2),
                         out.reshape(B_, Kh_, G_ * Sq_, -1))
        return out
    B, Kh, G, Sq, hd = q.shape
    qf = q.reshape(B, Kh, G * Sq, hd)
    out = _dispatch(site, cfg, qf, jnp.swapaxes(k, -1, -2))
    return out.reshape(B, Kh, G, Sq, k.shape[2])


def _grouped_av_execute(site: GemmSite, cfg: GemmConfig,
                        p: Array, v: Array) -> Array:
    """p (B,Kh,G,Sq,Sk) x v (B,Kh,Sk,hd) -> (B,Kh,G,Sq,hd)."""
    _note_site(site.key)
    if cfg.mode == "native":
        out = _grouped_native(site, cfg, "bkgqs,bksd->bkgqd", p, v)
        if _TRACE_HOOK is not None:
            B_, Kh_, G_, Sq_, Sk_ = p.shape
            _maybe_trace(site.key, cfg, p.reshape(B_, Kh_, G_ * Sq_, Sk_), v,
                         out.reshape(B_, Kh_, G_ * Sq_, -1))
        return out
    B, Kh, G, Sq, Sk = p.shape
    pf = p.reshape(B, Kh, G * Sq, Sk)
    out = _dispatch(site, cfg, pf, v)
    return out.reshape(B, Kh, G, Sq, v.shape[-1])


def _grouped_dright(site: GemmSite, cfg: GemmConfig,
                    lhs: Array, rhs: Array) -> Array:
    """The shared dK/dV backward contraction
    ``bkgqx,bkgqy->bkxy`` (sum over heads-in-group and query positions) —
    dK = dright(g, q), dV = dright(p, g)."""
    _note_site(site.key)
    if cfg.mode == "native":
        dt = cfg.fmt.jnp_dtype
        with jax.named_scope(site.scope):
            out = jnp.einsum("bkgqx,bkgqy->bkxy", lhs.astype(dt),
                             rhs.astype(dt),
                             preferred_element_type=jnp.float32)
        if _TRACE_HOOK is not None:
            B_, Kh_, G_, Sq_, X_ = lhs.shape
            _maybe_trace(site.key, cfg,
                         jnp.swapaxes(lhs.reshape(B_, Kh_, G_ * Sq_, X_),
                                      -1, -2),
                         rhs.reshape(B_, Kh_, G_ * Sq_, -1), out)
        return out
    B, Kh, G, Sq, X = lhs.shape
    lf = jnp.swapaxes(lhs.reshape(B, Kh, G * Sq, X), -1, -2)
    rf = rhs.reshape(B, Kh, G * Sq, -1)
    return _dispatch(site, cfg, lf, rf)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_qk_vjp(ctx, q, k):
    site, pol = ctx
    return _grouped_qk_execute(site, pol.lookup(site), q, k)


def _grouped_qk_vjp_fwd(ctx, q, k):
    return _grouped_qk_vjp(ctx, q, k), (q, k)


def _grouped_qk_vjp_bwd(ctx, res, g):
    site, pol = ctx
    q, k = res
    dq_site, dk_site = site.bwd("dA"), site.bwd("dB")
    # dQ = einsum("bkgqs,bksd->bkgqd", g, k) — the grouped_av contraction
    dq = _grouped_av_execute(dq_site, pol.lookup(dq_site), g, k)
    # dK = einsum("bkgqs,bkgqd->bksd", g, q)
    dk = _grouped_dright(dk_site, pol.lookup(dk_site), g, q)
    return dq.astype(q.dtype), dk.astype(k.dtype)


_grouped_qk_vjp.defvjp(_grouped_qk_vjp_fwd, _grouped_qk_vjp_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_av_vjp(ctx, p, v):
    site, pol = ctx
    return _grouped_av_execute(site, pol.lookup(site), p, v)


def _grouped_av_vjp_fwd(ctx, p, v):
    return _grouped_av_vjp(ctx, p, v), (p, v)


def _grouped_av_vjp_bwd(ctx, res, g):
    site, pol = ctx
    p, v = res
    dp_site, dv_site = site.bwd("dA"), site.bwd("dB")
    # dP = einsum("bkgqd,bksd->bkgqs", g, v) — the grouped_qk contraction
    dp = _grouped_qk_execute(dp_site, pol.lookup(dp_site), g, v)
    # dV = einsum("bkgqs,bkgqd->bksd", p, g)
    dv = _grouped_dright(dv_site, pol.lookup(dv_site), p, g)
    return dp.astype(p.dtype), dv.astype(v.dtype)


_grouped_av_vjp.defvjp(_grouped_av_vjp_fwd, _grouped_av_vjp_bwd)


def grouped_qk(q: Array, k: Array, *, site: Union[str, GemmSite] = "attn_qk",
               policy: Optional[NumericsPolicy] = None) -> Array:
    """GQA score einsum  q (B,Kh,G,Sq,hd) x k (B,Kh,Sk,hd) -> (B,Kh,G,Sq,Sk).
    Backward dispatches ``<site>@bwd.dA`` (dQ) / ``<site>@bwd.dB`` (dK)."""
    pol = policy or current_policy()
    return _grouped_qk_vjp((GemmSite.parse(site), pol), q, k)


def grouped_av(p: Array, v: Array, *, site: Union[str, GemmSite] = "attn_av",
               policy: Optional[NumericsPolicy] = None) -> Array:
    """GQA value einsum  p (B,Kh,G,Sq,Sk) x v (B,Kh,Sk,hd) -> (B,Kh,G,Sq,hd).
    Backward dispatches ``<site>@bwd.dA`` (dP) / ``<site>@bwd.dB`` (dV)."""
    pol = policy or current_policy()
    return _grouped_av_vjp((GemmSite.parse(site), pol), p, v)


# -- grouped (expert) GEMM --------------------------------------------------
def _segment_ids(group_sizes: Array, n_rows: int) -> Array:
    """Segment id per sorted row from the group-size prefix sums; rows beyond
    sum(group_sizes) get id E (no group)."""
    bounds = jnp.cumsum(group_sizes)
    return jnp.sum(jnp.arange(n_rows)[:, None] >= bounds[None, :], axis=1)


def _fit_ragged(plan: GemmPlan, axis: str, n_rows: int, n_groups: int
                ) -> GemmPlan:
    """Clamp the plan's token-axis block to the mean segment size.

    The sorted-segment walk revisits one boundary tile per group, so its MAC
    count is ~(T + (E-1)·block)·d·f: a block sized for a dense GEMM (128)
    with many experts burns the entire O(T) advantage on boundary tiles.
    Blocking only changes the summation grouping — exact limb accumulation
    keeps the result bit-identical for any clamp. The kernel wrappers fit
    the result, so a token block that also sits on lanes (the wgrad's bk)
    stays a multiple of 128."""
    block = min(getattr(plan, axis),
                _ceil8(max(1, n_rows // max(1, n_groups))))
    if block == getattr(plan, axis):
        return plan
    return dataclasses.replace(plan, **{axis: block})


def _ragged_execute(site: GemmSite, cfg: GemmConfig, x: Array, w: Array,
                    group_sizes: Array) -> Array:
    """The mode switch of ``ragged_gemm`` (shared by fwd and the dx backward,
    which is the same ragged contraction against transposed weights)."""
    _note_site(site.key)
    E, d, f = w.shape
    with jax.named_scope(site.scope):
        if cfg.mode == "native":
            dt = cfg.fmt.jnp_dtype
            out = jax.lax.ragged_dot(x.astype(dt), w.astype(dt), group_sizes,
                                     preferred_element_type=jnp.float32)
        elif cfg.mode == "pallas":
            # Sorted-segment kernel: rows are already sorted by group, so the
            # Pallas grid walks contiguous segments with a per-tile expert-weight
            # index map — O(T·d·f) MACs instead of the reference path's T×E.
            # Exact integer limb accumulation is order-invariant, so the result
            # is bit-identical to the reference grouped path below.
            from repro.kernels import ops as kops
            if isinstance(cfg.fmt, FloatFormat):
                x, w = cfg.fmt.quantize(x), cfg.fmt.quantize(w)
            plan = plan_gemm(x.shape[0], f, d, fmt=cfg.fmt, spec=cfg.acc)
            plan = _fit_ragged(plan, "bm", x.shape[0], E)
            out = kops.fdp_ragged_gemm(x, w, group_sizes, spec=cfg.acc,
                                       fmt=cfg.fmt, plan=plan)
        else:
            seg = _segment_ids(group_sizes, x.shape[0])              # (T,)
            per_expert = jax.vmap(lambda we: _execute(cfg, x, we))(w)  # (E,T,f)
            out = jnp.take_along_axis(
                per_expert, jnp.minimum(seg, E - 1)[None, :, None], axis=0)[0]
            # rows beyond sum(group_sizes) (padding) belong to no group: zero
            # them like the native ragged_dot path, so flipping a site between
            # native and FDP candidates never changes padded-row outputs
            out = jnp.where((seg < E)[:, None], out, 0.0)
    # report as one (T, d) x (d, f) call: k/m from x, n and weight stats from
    # the flattened expert stack (the sample decoder reshapes (-1, d, f) and
    # keeps group 0's block)
    return _maybe_trace(site.key, cfg, x, w.reshape(E * d, f), out)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ragged_vjp(ctx, x, w, group_sizes):
    site, pol = ctx
    return _ragged_execute(site, pol.lookup(site), x, w, group_sizes)


def _ragged_vjp_fwd(ctx, x, w, group_sizes):
    return _ragged_vjp(ctx, x, w, group_sizes), (x, w, group_sizes)


def _ragged_vjp_bwd(ctx, res, g):
    site, pol = ctx
    x, w, group_sizes = res
    E, d, f = w.shape
    dx_site, dw_site = site.bwd("dA"), site.bwd("dB")
    # dX: the same ragged contraction against transposed per-expert weights
    # (row t of g against w[seg(t)]ᵀ) — a first-class ragged site.
    dx = _ragged_execute(dx_site, pol.lookup(dx_site), g,
                         jnp.swapaxes(w, -1, -2), group_sizes)
    # dW[e] = X_eᵀ · G_e. pallas mode runs the sorted-segment wgrad kernel
    # (token-block tiles routed to their expert's output block — O(T·d·f)
    # MACs, bit-identical to the masked reference by exact order-invariant
    # limb accumulation). simulate/native keep the per-expert masked Aᵀ·G
    # reference (T×E work): JAX's own ragged_dot transpose lowers to an
    # E-batched dot_general contracting the full token dim anyway, so even
    # native configs are not asymptotically worse than autodiff here.
    dw_cfg = pol.lookup(dw_site)
    _note_site(dw_site.key)
    with jax.named_scope(dw_site.scope):
        if dw_cfg.mode == "pallas":
            from repro.kernels import ops as kops
            xq, gq = x, g
            if isinstance(dw_cfg.fmt, FloatFormat):
                xq, gq = dw_cfg.fmt.quantize(x), dw_cfg.fmt.quantize(g)
            plan = plan_gemm(d, f, x.shape[0], fmt=dw_cfg.fmt, spec=dw_cfg.acc)
            plan = _fit_ragged(plan, "bk", x.shape[0], E)
            dw = kops.fdp_ragged_dw(xq, gq, group_sizes, num_groups=E,
                                    spec=dw_cfg.acc, fmt=dw_cfg.fmt, plan=plan)
        else:
            seg = _segment_ids(group_sizes, x.shape[0])
            masks = seg[None, :] == jnp.arange(E)[:, None]           # (E, T)

            def per_expert(m):
                xm = jnp.where(m[:, None], x, jnp.zeros((), x.dtype))
                return _execute(dw_cfg, jnp.swapaxes(xm, -1, -2), g)   # (d, f)

            dw = jax.vmap(per_expert)(masks)                         # (E, d, f)
    _maybe_trace(dw_site.key, dw_cfg, jnp.swapaxes(x, -1, -2), g,
                 dw.reshape(E * d, f))
    zeros_gs = np.zeros(group_sizes.shape, dtype=jax.dtypes.float0)
    return dx.astype(x.dtype), dw.astype(w.dtype), zeros_gs


_ragged_vjp.defvjp(_ragged_vjp_fwd, _ragged_vjp_bwd)


def ragged_gemm(x: Array, w: Array, group_sizes: Array, *,
                site: Union[str, GemmSite] = "moe_expert",
                policy: Optional[NumericsPolicy] = None) -> Array:
    """Grouped (expert) GEMM: ``x (T, d)`` rows sorted by group, ``w (E, d, f)``
    per-group weights, ``group_sizes (E,)`` rows per group. Output ``(T, f)``
    f32 — row t contracts against its group's weight matrix.

    Native mode stays on the fused ``jax.lax.ragged_dot`` fast path (operands
    cast onto the policy format's grid, f32 accumulate — same front end as
    ``gemm``). pallas mode runs the sorted-segment Pallas kernel: the grid
    walks contiguous per-group segments with a scalar-prefetched expert index
    map, so the exact ⟨ovf,msb,lsb⟩ datapath does O(T·d·f) MACs like the
    native path (bit-identical to the reference below — exact limb
    accumulation is order-invariant). simulate mode keeps the reference
    grouped path as the oracle: one dispatched GEMM per group over the full
    token block, rows selected by segment id — T×E work, every expert MAC
    through the site's exact datapath.

    Tracing reports one aggregate call: operand stats over all tokens and all
    group weights, MACs = T·d·f (each sorted row hits exactly one expert).
    Backward dispatches ``<site>@bwd.dA`` (token grads, a ragged contraction
    against transposed weights) and ``<site>@bwd.dB`` (per-expert weight
    grads) as their own sites.
    """
    pol = policy or current_policy()
    return _ragged_vjp((GemmSite.parse(site), pol), x, w, group_sizes)


def _batched_apply(f, a: Array, b: Array) -> Array:
    """Apply a 2D (M,K)x(K,N) kernel over arbitrary leading batch dims with
    numpy broadcasting between a and b batch dims (vmap for the batched
    leaf; the Pallas path has its own native batched grid in kernels.ops)."""
    from repro.kernels.ops import matmul_batching
    return matmul_batching(f, jax.vmap(f))(a, b)


def policy_from_plan(path) -> NumericsPolicy:
    """Load a serialized ``repro.numerics`` PrecisionPlan and return the
    NumericsPolicy it deploys (the ``--precision-plan`` entry point)."""
    from repro.numerics import load_plan       # deferred: numerics imports us
    return load_plan(path).to_policy()


def quantize_inputs(x: Array, site: Union[str, GemmSite] = "generic",
                    policy: Optional[NumericsPolicy] = None) -> Array:
    """Round an activation/weight onto the policy format's grid (keeps f32
    carrier for posit formats)."""
    pol = policy or current_policy()
    cfg = pol.lookup(site)
    fmt = cfg.fmt
    if isinstance(fmt, PositFormat):
        return fmt.to_float(fmt.from_float(x))
    return x.astype(fmt.jnp_dtype).astype(x.dtype)
