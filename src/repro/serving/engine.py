"""Bucketed AOT engine pool — the saxml ``ServableMethod`` shape.

A serving deployment cannot compile one executable per request shape, and it
cannot keep every (plan x shape x method) executable resident either. This
module does what saxml's servable models do: a small *sorted* table of
(batch-slots, sequence-length) **buckets**, per-(plan, bucket, method)
AOT-compiled executables created **lazily** on first traffic, padded-shape
dispatch to the smallest fitting bucket, and **LRU eviction** under a
live-engine cap so the pool's device footprint stays bounded no matter how
many plans the router serves.

Methods (the saxml trio):
    ``generate`` - fixed-slot continuous batching (``ContinuousBatcher``)
    ``stream``   - same engine shape, tokens delivered through per-request
                   ``on_token`` callbacks as each decode step lands
    ``score``    - teacher-forced log-probability of the prompt, one padded
                   whole-batch forward per bucket

Every engine warms up under its plan's ``NumericsPolicy`` (the plan-zoo
contract: numerics bind at trace time) and exposes ``trace_count`` so tests
can prove padded dispatch reuses the bucket executable instead of retracing.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import NumericsPolicy, use_policy
from repro.launch.batching import ContinuousBatcher, Request
from repro.models import forward
from repro.obs.registry import default_registry
from repro.obs.spans import span

METHODS = ("score", "generate", "stream")


class AdmissionError(RuntimeError):
    """The request can never be served by this pool/frontend: no bucket fits
    its ``prompt + max_new``, or the queue is at its backpressure cap."""


@dataclasses.dataclass(frozen=True, order=True)
class Bucket:
    """One (slots, padded sequence length) serving shape. Ordering is by
    sequence capacity first — ``bucket_for`` picks the smallest fit."""

    max_len: int
    n_slots: int

    def __post_init__(self):
        if self.n_slots < 1 or self.max_len < 4:
            raise ValueError(f"degenerate bucket {self.label}")

    @property
    def label(self) -> str:
        return f"{self.n_slots}x{self.max_len}"

    @property
    def capacity(self) -> int:
        """Positions a request may consume (the engine keeps one sentinel)."""
        return self.max_len - 1


def parse_buckets(spec: str) -> tuple:
    """``"2x32,4x64"`` -> sorted (Bucket(32,2), Bucket(64,4)). The textual
    order is slots x len (the saxml batch-size-table convention)."""
    buckets = []
    for part in spec.split(","):
        ns, _, ml = part.strip().partition("x")
        buckets.append(Bucket(max_len=int(ml), n_slots=int(ns)))
    return tuple(sorted(set(buckets)))


class GenerateEngine:
    """A ``ContinuousBatcher`` bound to one (plan, bucket): the ``generate``
    and ``stream`` executables. Streaming is the same compiled step — tokens
    leave through ``Request.on_token`` as they land."""

    def __init__(self, cfg, params, bucket: Bucket,
                 policy: Optional[NumericsPolicy], method: str,
                 eos_id: Optional[int] = None):
        self.bucket, self.method = bucket, method
        self.batcher = ContinuousBatcher(
            cfg, params, n_slots=bucket.n_slots, max_len=bucket.max_len,
            eos_id=eos_id, warmup=policy if policy is not None else True)

    @property
    def trace_count(self) -> int:
        return self.batcher.trace_count

    def idle(self) -> bool:
        return (not self.batcher.queue
                and all(r is None for r in self.batcher.active))

    def cache_remaining(self) -> int:
        return self.batcher.cache_remaining()

    def recycle_if_exhausted(self, need: int) -> None:
        """Fresh KV room for a request needing ``need`` positions — only
        possible while drained; the compiled step survives the reset."""
        if self.idle() and self.batcher.cache_remaining() < need:
            self.batcher.reset_cache()

    def admit(self, req: Request) -> None:
        self.batcher.submit(req)

    def step(self) -> bool:
        return self.batcher.step()


class ScoreEngine:
    """Teacher-forced prompt log-probability, AOT-compiled at the bucket
    shape: one padded (n_slots, max_len) forward, per-row masked sum of
    next-token log-probs."""

    def __init__(self, cfg, params, bucket: Bucket,
                 policy: Optional[NumericsPolicy]):
        self.bucket = bucket
        self.method = "score"
        self.params = params
        self.trace_count = 0

        def fn(params, tokens, mask):
            self.trace_count += 1            # python side effect: trace only
            batch = {"tokens": tokens}
            if cfg.family == "vlm":
                batch["patches"] = jnp.zeros(
                    (bucket.n_slots, cfg.n_patches, cfg.d_model))
            if cfg.family == "encdec":
                batch["frames"] = jnp.zeros(
                    (bucket.n_slots, cfg.enc_seq, cfg.d_model))
            logits = forward(params, cfg, batch)
            # keep the text positions (vlm prepends patch positions)
            logits = logits[:, -tokens.shape[1]:, :cfg.vocab_size]
            logp = jax.nn.log_softmax(logits, axis=-1)
            lp = jnp.take_along_axis(
                logp[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]
            return jnp.sum(lp * mask[:, 1:], axis=-1)

        tok0 = jnp.zeros((bucket.n_slots, bucket.max_len), jnp.int32)
        mask0 = jnp.zeros((bucket.n_slots, bucket.max_len), jnp.float32)
        ctx = use_policy(policy) if policy is not None else _nullctx()
        with ctx:
            self._fn = jax.jit(fn).lower(params, tok0, mask0).compile()

    def idle(self) -> bool:
        return True                          # one-shot: no resident state

    def score_batch(self, prompts: Sequence[Sequence[int]]) -> list:
        """Score up to ``n_slots`` prompts in one padded executable call."""
        if len(prompts) > self.bucket.n_slots:
            raise ValueError(f"{len(prompts)} prompts > bucket "
                             f"{self.bucket.label}")
        toks = np.zeros((self.bucket.n_slots, self.bucket.max_len), np.int32)
        mask = np.zeros((self.bucket.n_slots, self.bucket.max_len),
                        np.float32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
            mask[i, :len(p)] = 1.0
        out = np.asarray(self._fn(self.params, jnp.asarray(toks),
                                  jnp.asarray(mask)))
        return [float(out[i]) for i in range(len(prompts))]


def _nullctx():
    import contextlib
    return contextlib.nullcontext()


class BucketedEnginePool:
    """Lazy (plan, bucket, method) -> engine cache with LRU eviction.

    ``max_live`` bounds resident engines; eviction only takes *idle* engines
    (a live engine holds in-flight KV state), so the pool may transiently
    exceed the cap when every engine is mid-generation — it shrinks back on
    the next miss. All bookkeeping is exposed via ``stats()``:
    compiles/hits/evictions plus per-bucket dispatch counts (the bench's
    bucket hit rate)."""

    def __init__(self, cfg, params, buckets: Union[str, Sequence[Bucket]],
                 max_live: int = 4, eos_id: Optional[int] = None):
        if isinstance(buckets, str):
            buckets = parse_buckets(buckets)
        self.buckets = tuple(sorted(set(buckets)))
        if not self.buckets:
            raise ValueError("pool needs at least one bucket")
        self.cfg, self.params, self.eos_id = cfg, params, eos_id
        self.max_live = max_live
        self._engines: OrderedDict = OrderedDict()
        self._stats = {"compiles": 0, "hits": 0, "evictions": 0}
        self._bucket_hits: dict = {b.label: 0 for b in self.buckets}
        # process-wide mirror of the per-instance counters (the dicts above
        # stay this pool's exact source of truth)
        self._m_ops = default_registry().counter(
            "repro_engine_pool_ops_total",
            "bucketed engine pool events", ("op",))
        self._m_resident = default_registry().gauge(
            "repro_engine_pool_resident", "engines resident in the pool")

    def bucket_for(self, prompt_len: int, max_new: int) -> Bucket:
        """Smallest bucket whose capacity fits ``prompt + max_new`` (padded
        dispatch: the request runs at the bucket shape, reusing its
        executable)."""
        need = prompt_len + max_new
        for b in self.buckets:
            if need <= b.capacity:
                return b
        raise AdmissionError(
            f"request needs {need} positions; largest bucket is "
            f"{self.buckets[-1].label} (capacity {self.buckets[-1].capacity})")

    def get(self, plan, bucket: Bucket, method: str):
        """The engine for (plan, bucket, method), compiling on first use.
        ``plan`` is a ``RoutedPlan`` (anything with ``.name``/``.policy()``)."""
        if method not in METHODS:
            raise ValueError(f"unknown method {method!r}; have {METHODS}")
        if bucket not in self.buckets:
            raise ValueError(f"bucket {bucket.label} not in this pool")
        key = (plan.name, bucket, method)
        eng = self._engines.get(key)
        if eng is not None:
            self._engines.move_to_end(key)
            self._stats["hits"] += 1
            self._m_ops.inc(op="hits")
            self._bucket_hits[bucket.label] += 1
            return eng
        self._evict_idle()
        policy = plan.policy()
        with span("serving.aot_compile", plan=plan.name, bucket=bucket.label,
                  method=method):
            if method == "score":
                eng = ScoreEngine(self.cfg, self.params, bucket, policy)
            else:
                eng = GenerateEngine(self.cfg, self.params, bucket, policy,
                                     method, eos_id=self.eos_id)
        self._engines[key] = eng
        self._stats["compiles"] += 1
        self._m_ops.inc(op="compiles")
        self._m_resident.set(float(len(self._engines)))
        self._bucket_hits[bucket.label] += 1
        return eng

    def _evict_idle(self) -> None:
        """Drop least-recently-used *idle* engines until under the cap."""
        while len(self._engines) >= self.max_live:
            victim = next((k for k, e in self._engines.items() if e.idle()),
                          None)
            if victim is None:
                return                       # everything is mid-generation
            del self._engines[victim]
            self._stats["evictions"] += 1
            self._m_ops.inc(op="evictions")
            self._m_resident.set(float(len(self._engines)))

    def live(self) -> dict:
        return dict(self._engines)

    def stats(self) -> dict:
        """Per-instance pool bookkeeping (exact counts for this pool).

        .. deprecated:: the process-wide scrape surface is the ``repro.obs``
           registry (``repro_engine_pool_ops_total`` /
           ``repro_engine_pool_resident``); this dict remains the exact
           per-instance view.
        """
        from repro.core.dispatch import plan_cache_stats
        total = sum(self._bucket_hits.values())
        return {**self._stats, "resident": len(self._engines),
                "bucket_hits": dict(self._bucket_hits),
                "bucket_hit_rate": (self._stats["hits"] / total
                                    if total else 0.0),
                # GemmPlan cache counters (process-global): the serving-tier
                # health signal for the schedule zoo — warm pools show
                # misses == 0, persisted_loads > 0
                "plans": plan_cache_stats().as_dict()}
