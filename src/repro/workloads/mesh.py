"""Mesh-reshape stability: the same bits on every factorization of a mesh.

The FDP's associativity property makes one kernel's result independent of its
K-reduction order; this workload lifts the claim to a whole device mesh. Each
deployed site's GEMM is run K-sharded over the FLATTENED (data, model) axes of
every factorization of the available devices (8 -> 1x8, 2x4, 4x2, 8x1) with
the cross-device reduction dispatched through ``gemm(..., reduce_axis=...)``
— FDP sites through the exact limb-summed ``fdp_psum``, native sites through
a stock float psum — and scored in bits of agreement against the UNSHARDED
single-device result. FDP sites land bit-identical by construction; native
sites measure their real topology drift.

When the context is model-bound and more than one device is visible, the
workload also runs the end-to-end contract: forward logits and loss-gradients
of one data-parallel training step (``sharded_value_and_grad`` with
fixed-point gradient reduction) compared across every mesh shape. Per-device
shapes depend only on the joint device count, so local compute is common-mode
and the comparison isolates exactly the collective layer.

Registered as "mesh" — opt-in (like "solve"): ``search(validators=...)`` and
``refresh_plans.py --validators grad,logits,repro,mesh`` act on it; it is not
in DEFAULT_VALIDATORS, so the existing plan zoo needs no regeneration (its
reports simply carry no ``mesh`` provenance = single-device).
"""

from __future__ import annotations

import numpy as np

from .base import (PROBE_SEQ, ValidationReport, Validator, WorkloadContext,
                   make_probe_batch, probed_sites, register)

MESH_CAP_BITS = 53.0

# fixed-point grid for the cross-device gradient mean in the end-to-end
# probe (same spec the train CLI's --fdp-grad uses)
_GRAD_OVF, _GRAD_MSB, _GRAD_LSB = 10, 10, -20


def mesh_shapes(n_devices: int) -> list:
    """Every (R, C) factorization of ``n_devices`` (8 -> 1x8, 2x4, 4x2,
    8x1; 1 -> the degenerate 1x1)."""
    return [(r, n_devices // r) for r in range(1, n_devices + 1)
            if n_devices % r == 0]


def _agreement_bits(ref: np.ndarray, others) -> float:
    """Bits of agreement between ``ref`` and each of ``others`` (the
    K-reorder stability formula, applied across mesh shapes)."""
    dev = max((float(np.max(np.abs(o - ref))) for o in others), default=0.0)
    if dev == 0.0:
        return MESH_CAP_BITS
    scale = float(np.max(np.abs(ref)))
    if scale == 0.0:
        return 0.0
    return float(np.clip(-np.log2(dev / scale), 0.0, MESH_CAP_BITS))


@register
class MeshReshapeStability(Validator):

    name = "mesh"
    phases = ("fwd", "bwd")

    def __init__(self, *, cfg=None, params=None, m: int = 8, n: int = 8,
                 k: int = 256, seed: int = 0, threshold: float = 10.0):
        import jax

        rng = np.random.default_rng(seed)
        self.a = rng.standard_normal((m, k)).astype(np.float32)
        self.b = rng.standard_normal((k, n)).astype(np.float32)
        self.cfg, self.params, self.seed = cfg, params, seed
        self.threshold = float(threshold)
        self.shapes = mesh_shapes(jax.device_count())

    @classmethod
    def from_context(cls, ctx: WorkloadContext) -> "MeshReshapeStability":
        # model binding is optional: without it the workload still probes
        # every deployed site's K-sharded contraction
        return cls(cfg=ctx.cfg, params=ctx.params, seed=ctx.seed,
                   threshold=ctx.budget_bits)

    # -- per-site K-sharded contraction probe -------------------------------
    def _site_bits(self, site: str, policy) -> float:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.core.dispatch import gemm
        from repro.launch.mesh import auto_mesh
        from repro.parallel.compat import shard_map_unchecked

        a, b = jnp.asarray(self.a), jnp.asarray(self.b)
        ref = np.asarray(gemm(a, b, site=site, policy=policy), np.float64)
        axes = ("data", "model")
        outs = []
        for r, c in self.shapes:
            mesh = auto_mesh((r, c), axes)

            def f(al, bl):
                return gemm(al, bl, site=site, policy=policy,
                            reduce_axis=axes)

            out = shard_map_unchecked(
                f, mesh=mesh, in_specs=(P(None, axes), P(axes, None)),
                out_specs=P())(a, b)
            outs.append(np.asarray(out, np.float64))
        return _agreement_bits(ref, outs)

    # -- end-to-end: logits + loss-gradients across mesh shapes -------------
    def _model_bits(self, policy) -> dict:
        import jax
        from jax.sharding import PartitionSpec as P

        from repro.core.accumulator import AccumulatorSpec
        from repro.core.dispatch import use_policy
        from repro.models import forward
        from repro.models.layers import LOCAL
        from repro.launch.mesh import auto_mesh
        from repro.parallel.compat import shard_map_unchecked
        from repro.train.loop import make_loss_fn, sharded_value_and_grad

        n = jax.device_count()
        batch = make_probe_batch(self.cfg, batch_size=n, seq=PROBE_SEQ,
                                 seed=self.seed + 1, with_targets=True)
        axes = ("data", "model")
        grad_spec = AccumulatorSpec(ovf=_GRAD_OVF, msb=_GRAD_MSB,
                                    lsb=_GRAD_LSB)
        loss_fn = make_loss_fn(self.cfg, LOCAL, remat="none")
        vg = sharded_value_and_grad(loss_fn, axes, fdp_grad_spec=grad_spec)
        cfg = self.cfg

        def body(params, batch):
            logits = forward(params, cfg, batch, LOCAL, remat="none")
            _, grads = vg(params, batch)
            return logits, grads

        logits_all, grads_all = [], []
        for r, c in self.shapes:
            mesh = auto_mesh((r, c), axes)
            sharded = shard_map_unchecked(
                body, mesh=mesh, in_specs=(P(), P(axes)),
                out_specs=(P(axes), P()))
            with use_policy(policy):
                logits, grads = jax.jit(sharded)(self.params, batch)
                jax.block_until_ready((logits, grads))
            logits_all.append(np.asarray(logits, np.float64))
            grads_all.append(np.concatenate(
                [np.asarray(g, np.float64).ravel()
                 for g in jax.tree.leaves(grads)]))
        return {
            "logits_bits": _agreement_bits(logits_all[0], logits_all[1:]),
            "grad_bits": _agreement_bits(grads_all[0], grads_all[1:]),
        }

    def run(self, policy) -> ValidationReport:
        sites = probed_sites(policy) or ["workload_probe"]
        attribution = {s: self._site_bits(s, policy) for s in sites}
        details = {"mesh_shapes": ",".join(f"{r}x{c}"
                                           for r, c in self.shapes),
                   "n_sites_probed": len(sites),
                   "bit_identical_sites":
                       sum(v >= MESH_CAP_BITS for v in attribution.values())}

        import jax
        model_bound = (self.cfg is not None and self.params is not None
                       and jax.device_count() > 1)
        if model_bound:
            mb = self._model_bits(policy)
            details.update(mb)
            # whole-namespace deficits the upgrade loop can act on: forward
            # sites move the logits, backward sites move the gradients
            attribution["*"] = mb["logits_bits"]
            attribution["*@bwd"] = mb["grad_bits"]

        weakest = min(attribution, key=attribution.get)
        details["weakest_site"] = weakest
        return ValidationReport(
            workload=self.name, score=attribution[weakest],
            threshold=self.threshold, site_attribution=dict(attribution),
            details=details,
            mesh=details["mesh_shapes"])
