"""Training loop substrate: loss, train step (with microbatched gradient
accumulation — optionally in the paper's fixed-point grid for bitwise
order-invariant accumulation), and a fault-tolerant Trainer driver."""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.accumulator import AccumulatorSpec
from repro.core.dispatch import NumericsPolicy, use_policy
from repro.models import layers as L
from repro.models import transformer as T

from .optimizer import Optimizer, apply_updates


def make_loss_fn(cfg, dist: L.Distribution = L.LOCAL, *, z_loss: float = 0.0,
                 remat: str = "block", moe_impl: str = "tp",
                 loss_chunk: int = 512):
    """Next-token CE over batch {"tokens","targets","loss_mask", extras}.

    The CE is computed in sequence chunks with a checkpointed step so the
    (B, S, vocab) logits tensor is never materialized — each chunk's logits
    are recomputed from the hidden states during backward (vocab-TP friendly).
    """

    def loss_fn(params, batch):
        hidden = T.forward(params, cfg, batch, dist, remat=remat,
                           moe_impl=moe_impl, return_hidden=True)
        if cfg.family == "vlm":                 # text positions only
            hidden = hidden[:, cfg.n_patches:]
        targets = batch["targets"]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(targets.shape, jnp.float32)
        B, S, d = hidden.shape
        ck = min(loss_chunk, S)
        pad = (-S) % ck
        if pad:
            hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
            targets = jnp.pad(targets, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nc = hidden.shape[1] // ck
        hc = jnp.moveaxis(hidden.reshape(B, nc, ck, d), 1, 0)
        tc = jnp.moveaxis(targets.reshape(B, nc, ck), 1, 0)
        mc = jnp.moveaxis(mask.reshape(B, nc, ck), 1, 0)
        head = params["lm_head"]

        def chunk_step(carry, xs):
            nll_sum, zsum, correct = carry
            h, t, m = xs
            # keep lm_head vocab-TP: gather the (small) h chunk over tp, NOT
            # the (huge) vocab-sharded head — logits stay vocab-sharded and
            # the logsumexp reduces with a psum (§Perf hillclimb #2)
            h = dist.constrain(h, dist.dp, None, None)
            logits = L.dense(h.astype(jnp.float32), head.astype(jnp.float32),
                             "lm_head")
            logits = dist.constrain(logits, dist.dp, None, dist.tp_axis)
            logits = logits[..., :cfg.vocab_size]
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
            nll_sum = nll_sum + jnp.sum((lse - gold) * m)
            zsum = zsum + jnp.sum(jnp.square(lse) * m)
            correct = correct + jnp.sum((logits.argmax(-1) == t) * m)
            return (nll_sum, zsum, correct), None

        (nll_sum, zsum, correct), _ = jax.lax.scan(
            jax.checkpoint(chunk_step),
            (jnp.float32(0), jnp.float32(0), jnp.float32(0)), (hc, tc, mc))
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = nll_sum / denom
        if z_loss:
            loss = loss + z_loss * zsum / denom
        acc = correct / denom
        return loss, {"loss": loss, "accuracy": acc}

    return loss_fn


def make_train_step(cfg, opt: Optimizer, dist: L.Distribution = L.LOCAL, *,
                    remat: str = "block", microbatches: int = 1,
                    fdp_grad_spec: Optional[AccumulatorSpec] = None,
                    z_loss: float = 0.0, moe_impl: str = "tp",
                    donate: bool = True,
                    numerics_policy: Optional[NumericsPolicy] = None):
    """Returns jitted ((params, opt_state), batch) -> ((params, opt_state),
    metrics).

    microbatches > 1: gradients accumulated over a scan of microbatches.
    fdp_grad_spec: accumulate microbatch gradients on the paper's fixed-point
    grid (int32) — bitwise identical results for ANY microbatch split.
    numerics_policy: trace the whole step (forward AND the value_and_grad
    backward) under this policy, so a PrecisionPlan's phase-qualified bwd
    assignments (``attn_qk@bwd.dA``) actually dispatch in training — no
    reliance on an ambient ``use_policy`` context being live at first call.
    Defaults to the policy riding on ``dist`` (launch profiles put the
    deployed plan's policy there — see ``launch.sharding.distribution_for``),
    so the same plan survives into shard_map'd mesh runs unchanged.
    """
    if numerics_policy is None:
        numerics_policy = getattr(dist, "numerics_policy", None)
    loss_fn = make_loss_fn(cfg, dist, z_loss=z_loss, remat=remat,
                           moe_impl=moe_impl)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def single(params, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        return grads, metrics

    def accumulate(params, batch):
        # split leading batch dim into microbatches
        def split(x):
            b = x.shape[0]
            assert b % microbatches == 0
            return x.reshape(microbatches, b // microbatches, *x.shape[1:])

        mb = jax.tree.map(split, batch)
        lsb = fdp_grad_spec.lsb if fdp_grad_spec else 0
        scale = 2.0 ** lsb

        def quant(g):
            return jnp.round(g.astype(jnp.float32) / scale).astype(jnp.int32)

        def body(acc, b1):
            grads, metrics = single(params, b1)
            if fdp_grad_spec is not None:
                acc = jax.tree.map(lambda a, g: a + quant(g), acc, grads)
            else:
                acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                   acc, grads)
            return acc, metrics

        zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape,
                                jnp.int32 if fdp_grad_spec else jnp.float32),
            params)
        acc, ms = jax.lax.scan(body, zero, mb)
        if fdp_grad_spec is not None:
            grads = jax.tree.map(
                lambda a, p: (a.astype(jnp.float32) * scale / microbatches
                              ).astype(p.dtype), acc, params)
        else:
            grads = jax.tree.map(lambda a, p: (a / microbatches).astype(p.dtype),
                                 acc, params)
        metrics = jax.tree.map(lambda m: m.mean(), ms)
        return grads, metrics

    def step(carry, batch):
        params, opt_state = carry
        # policy context at *trace* time: dispatch lookups (fwd and bwd —
        # custom_vjp rules trace inside the same context) resolve under the
        # plan's policy, and a later retrace (new shapes, donated buffers)
        # re-applies it instead of depending on the ambient thread state.
        # The obs span brackets the trace (step compilation shows up in
        # --trace-out timelines); execution cost lives in the Trainer's
        # per-step span/histogram.
        from repro.obs.spans import span as _span
        ctx = (use_policy(numerics_policy) if numerics_policy is not None
               else contextlib.nullcontext())
        with _span("train.step_trace", microbatches=microbatches,
                   policy=getattr(numerics_policy, "name", None)), ctx:
            if microbatches > 1:
                grads, metrics = accumulate(params, batch)
            else:
                grads, metrics = single(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = opt_state["grad_norm"]
        return (params, opt_state), metrics

    return jax.jit(step, donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# Mesh-sharded data parallelism with exact gradient reduction
# ---------------------------------------------------------------------------
def sharded_value_and_grad(loss_fn, axis_names, *,
                           fdp_grad_spec: Optional[AccumulatorSpec] = None,
                           grad_quant=None):
    """Data-parallel value_and_grad for shard_map bodies: local gradients,
    cross-device mean over ``axis_names`` (a name or tuple of names).

    With ``fdp_grad_spec``, each device's local gradient is quantized onto
    the fixed-point grid and the mean runs as an integer psum with ONE
    dequantize against a constant denominator — bitwise identical for any
    reduction order or mesh factorization of the same device set (integer
    addition is associative and commutative). Without a spec, a plain float
    psum (fast, order-dependent). Loss/aux metrics reduce with float pmean
    either way — they are diagnostics, not part of the bit-equality contract.

    ``grad_quant`` (a block-mode ``qformat.QuantConfig``) instead sends the
    gradient mean through ``parallel.collectives.quantized_psum`` — a
    block-scaled low-bit payload that moves ~``bits/32`` of the fp32 wire
    bytes (the ``grad_psum@coll`` precision site). ``fdp_grad_spec`` takes
    precedence: the repro-certified fixed-point path stays bit-exact and a
    plan that pins it is never silently downgraded. Error feedback is a
    stateful deployment concern — carry it with
    ``parallel.collectives.QuantizedGradReducer``, not here.
    """

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def fn(params, batch):
        (loss, aux), grads = grad_fn(params, batch)
        n = jax.lax.axis_size(axis_names)
        if fdp_grad_spec is not None:
            scale = 2.0 ** fdp_grad_spec.lsb

            def one(g):
                q = jnp.round(g.astype(jnp.float32) / scale).astype(jnp.int32)
                s = jax.lax.psum(q, axis_names)
                return (s.astype(jnp.float32) * scale / n).astype(g.dtype)
        elif grad_quant is not None and grad_quant.mode == "block":
            from repro.parallel.collectives import quantized_psum

            def one(g):
                return quantized_psum(g, axis_names, grad_quant, mean=True)
        else:
            def one(g):
                return (jax.lax.psum(g, axis_names) / n).astype(g.dtype)

        grads = jax.tree.map(one, grads)
        loss = jax.lax.pmean(loss, axis_names)
        aux = jax.tree.map(lambda m: jax.lax.pmean(m, axis_names), aux)
        return (loss, aux), grads

    return fn


def make_mesh_train_step(cfg, opt: Optimizer, dist: L.Distribution, *,
                         remat: str = "none", z_loss: float = 0.0,
                         fdp_grad_spec: Optional[AccumulatorSpec] = None,
                         numerics_policy: Optional[NumericsPolicy] = None,
                         grad_quant=None):
    """Train step sharded over the FLATTENED mesh (pure data parallelism):
    the global batch is split over ALL mesh axes jointly, each device runs
    the full (unsharded) model on its slice under the plan's policy, and
    gradients reduce through ``sharded_value_and_grad``.

    Per-device shapes depend only on the joint device COUNT, never on the
    mesh factorization — so every device's local compute is bit-identical on
    1x8, 2x4 and 8x1 meshes of the same 8 devices, and with ``fdp_grad_spec``
    the cross-device gradient reduction is an exact integer psum: one step
    produces bit-identical logits, loss-gradients and updated params for any
    mesh reshape (the contract ``repro.workloads.mesh`` validates and the
    ``mesh_reshape_logits`` distributed check guards). PrecisionPlans apply
    unchanged: ``use_policy`` resolves at trace time, inside shard_map.

    ``grad_quant=None`` reads the collective format off the policy's
    ``grad_psum@coll`` aux assignment (searched plans wire themselves);
    ``fdp_grad_spec`` still takes precedence inside
    ``sharded_value_and_grad``, preserving the mesh-reshape bit-identity
    contract on the repro path.

    Returns jitted ((params, opt_state), global_batch) -> ((params,
    opt_state), metrics); params/opt_state replicated, batch global.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core import qformat
    from repro.parallel.compat import shard_map_unchecked

    if numerics_policy is None:
        numerics_policy = dist.numerics_policy
    if grad_quant is None and numerics_policy is not None:
        grad_quant = numerics_policy.aux_lookup(qformat.GRAD_PSUM_SITE.key)
    mesh = dist.mesh
    axes = tuple(mesh.axis_names)
    loss_fn = make_loss_fn(cfg, L.LOCAL, z_loss=z_loss, remat=remat)
    vg = sharded_value_and_grad(loss_fn, axes, fdp_grad_spec=fdp_grad_spec,
                                grad_quant=grad_quant)

    def body(carry, batch):
        params, opt_state = carry
        (loss, metrics), grads = vg(params, batch)
        # grads/params replicated after the psum: the update runs identically
        # on every device, so the new state stays (bitwise) replicated
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = opt_state["grad_norm"]
        return (params, opt_state), metrics

    sharded = shard_map_unchecked(
        body, mesh=mesh,
        in_specs=((P(), P()), P(axes)),
        out_specs=((P(), P()), P()))

    def step(carry, batch):
        from repro.obs.spans import span as _span
        ctx = (use_policy(numerics_policy) if numerics_policy is not None
               else contextlib.nullcontext())
        with _span("train.mesh_step_trace", axes=",".join(axes),
                   policy=getattr(numerics_policy, "name", None)), ctx:
            return sharded(carry, batch)

    return jax.jit(step)


# ---------------------------------------------------------------------------
# Fault-tolerant driver
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StragglerMonitor:
    """EWMA step-time outlier detector. On a real fleet the `on_straggler`
    hook would trigger re-scheduling; here it records and logs."""

    factor: float = 3.0
    alpha: float = 0.1
    ewma: float = 0.0
    events: list = dataclasses.field(default_factory=list)

    def record(self, step: int, dt: float) -> bool:
        if self.ewma == 0.0:
            self.ewma = dt
            return False
        is_straggler = dt > self.factor * self.ewma
        if is_straggler:
            self.events.append((step, dt, self.ewma))
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * dt
        return is_straggler


class Trainer:
    """Checkpointed, restartable training driver.

    Fault tolerance: every step runs under a catch-and-restore guard; a crash
    (or injected failure) rolls back to the last durable checkpoint and
    replays. Data is a pure function of step, so replay is exact.
    """

    def __init__(self, cfg, opt, data, step_fn, checkpoint_dir: str,
                 save_every: int = 50, keep: int = 3,
                 failure_injector: Optional[Callable[[int], None]] = None,
                 place_state: Optional[Callable] = None):
        from repro.checkpoint.store import CheckpointStore
        self.cfg, self.opt, self.data, self.step_fn = cfg, opt, data, step_fn
        self.store = CheckpointStore(checkpoint_dir, keep=keep)
        self.save_every = save_every
        self.monitor = StragglerMonitor()
        self.failure_injector = failure_injector
        self.place_state = place_state
        self.metrics_log: list = []
        from repro.obs.registry import default_registry
        self._m_step = default_registry().histogram(
            "repro_train_step_seconds", "Trainer per-step wall time")
        self._m_restarts = default_registry().counter(
            "repro_train_restarts_total", "fault-tolerant restore events")

    def init_or_restore(self, key):
        from repro.models import init as minit
        restored = self.store.load_latest()
        if restored is not None:
            step, carry = restored[0], (restored[1]["params"],
                                        restored[1]["opt_state"])
        else:
            params = minit(self.cfg, key)
            step, carry = 0, (params, self.opt.init(params))
        if self.place_state is not None:
            # launch profiles device_put the (params, opt_state) carry onto
            # their mesh shardings here — both at cold start and on every
            # post-failure restore, so replay resumes sharded
            carry = self.place_state(carry)
        return step, carry

    def run(self, n_steps: int, key=None, max_restarts: int = 3):
        key = key if key is not None else jax.random.key(0)
        step, carry = self.init_or_restore(key)
        restarts = 0
        from repro.obs.spans import span as _span
        while step < n_steps:
            try:
                t0 = time.perf_counter()
                if self.failure_injector is not None:
                    self.failure_injector(step)
                batch = self.data(step)
                with _span("train.step", step=step):
                    carry, metrics = self.step_fn(carry, batch)
                dt = time.perf_counter() - t0
                self.monitor.record(step, dt)
                self._m_step.observe(dt)
                self.metrics_log.append(
                    {k: float(v) for k, v in metrics.items()} | {"step": step})
                step += 1
                if step % self.save_every == 0 or step == n_steps:
                    self.store.save(step, {"params": carry[0],
                                           "opt_state": carry[1]})
            except (RuntimeError, InjectedFailure) as e:  # node failure
                restarts += 1
                if restarts > max_restarts:
                    raise
                self._m_restarts.inc()
                step, carry = self.init_or_restore(key)
        return carry


class InjectedFailure(RuntimeError):
    pass
