"""Continuous batching for the serving path.

A slot-based scheduler in the vLLM style, shaped for JAX: the decode step is
compiled ONCE for a fixed (n_slots, max_len) cache; requests stream in and
out of slots between steps (host-side bookkeeping). The cache, the step's
second argument, is donated to the jitted step: the KV stack is updated in
place and the step's output cache takes over its buffers, so the engine
holds one cache, never two. Finished slots are refilled immediately — the
decode batch never drains while work is queued.

This is the production serving loop for the framework; `examples/serve_batch`
uses the simple whole-batch variant, `tests/test_serving.py` exercises this
scheduler end-to-end.
"""

from __future__ import annotations

import contextlib
import dataclasses
from collections import deque
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import NumericsPolicy, policy_from_plan, use_policy
from repro.models import decode_step, init_cache
from repro.models.layers import LOCAL
from repro.obs.registry import default_registry
from repro.obs.spans import phase, span

# A slot-step is one live slot in one engine step, named by what it fed:
#   prefill       a prompt token, sampling nothing
#   prefill_last  the prompt's last token, sampling the first output
#   decode        the previous output, sampling the next one
# so, summed over requests, Request.prefill_tokens = prefill + prefill_last
# and Request.decode_tokens = prefill_last + decode.
SLOT_KINDS = ("prefill", "prefill_last", "decode")


def _resolve_policy(policy) -> Optional[NumericsPolicy]:
    """Normalize the engine's numerics argument: a NumericsPolicy passes
    through, a PrecisionPlan deploys itself, a str/path loads a plan JSON."""
    if policy is None or isinstance(policy, NumericsPolicy):
        return policy
    if hasattr(policy, "to_policy"):               # PrecisionPlan duck-type
        return policy.to_policy()
    if isinstance(policy, (str, bytes)) or hasattr(policy, "__fspath__"):
        return policy_from_plan(policy)
    raise TypeError(
        f"policy must be a NumericsPolicy, PrecisionPlan, or plan path; "
        f"got {type(policy).__name__}")


class CacheExhausted(RuntimeError):
    """The engine's global KV write cursor can no longer fit any queued
    request. The cursor (``cache["len"]``) is shared across slots and never
    rewinds, so once the queue head's ``prompt + max_new`` exceeds
    ``cache_remaining()`` nothing will ever be admitted again — call
    ``reset_cache()`` between drained generations, or serve through the
    ``repro.serving`` frontend, whose admission control parks requests and
    recycles engines instead of stalling."""


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list              # token ids
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # scheduling evidence, recorded by ContinuousBatcher.step: how many
    # engine steps this request was live in, and how its token budget split
    # between prefill (prompt tokens fed) and decode (tokens generated).
    # The serving tier's per-class stats read these; tests assert on them.
    steps: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    # streaming hook: called with each freshly decoded token id, from inside
    # the engine step that produced it (the serving tier's `stream` method)
    on_token: Optional[Callable[[int], None]] = None


class ContinuousBatcher:
    """Fixed-slot continuous batching engine.

    The cache is allocated for n_slots sequences of max_len. Prompt tokens
    are fed through the same decode_step (one token per step per slot —
    chunked prefill); slots whose request finished are re-assigned without
    recompiling anything.
    """

    def __init__(self, cfg, params, n_slots: int = 4, max_len: int = 128,
                 dist=LOCAL, eos_id: Optional[int] = None,
                 warmup: Union[bool, NumericsPolicy, str, object] = False,
                 policy=None):
        self.cfg, self.params, self.dist = cfg, params, dist
        self.n_slots, self.max_len = n_slots, max_len
        self.eos_id = eos_id
        assert cfg.family in ("dense", "moe", "vlm"), \
            "continuous batching engine supports KV-cache families"
        # ``warmup`` doubles as the numerics argument: passing a
        # NumericsPolicy / PrecisionPlan / plan path both installs the policy
        # AND warms up under it (the common plan-serving call shape).
        if not isinstance(warmup, bool):
            if policy is not None:
                raise TypeError(
                    "pass the numerics either as warmup=<plan/policy> or as "
                    "policy=..., not both — silently preferring one would "
                    "bake the other's formats out of the compiled step")
            policy = warmup
            warmup = True
        self.policy = _resolve_policy(policy)
        self.queue: deque[Request] = deque()
        self.active: list[Optional[Request]] = [None] * n_slots
        # per-slot progress: how many prompt tokens already fed
        self._fed = np.zeros(n_slots, dtype=np.int64)
        # the write cursor cache["len"] is global; each slot masks its
        # attention to [start[slot], len) so reused slots never see the
        # previous occupant's KV. ``_len`` mirrors the cursor host-side so
        # admission control never forces a device sync.
        self.cache = self._empty_cache()
        self._len = 0
        self._start = np.zeros(n_slots, dtype=np.int32)
        # traced exactly once per engine when warmed up — the regression
        # guard for "warmup must compile under the serving policy"
        self.trace_count = 0
        # what this engine did, counted where it happens (plain ints, with
        # process-wide registry mirrors): engine steps run, and slot-steps
        # by SLOT_KINDS
        self.steps_run = 0
        self.slot_steps = dict.fromkeys(SLOT_KINDS, 0)
        reg = default_registry()
        self._m_steps = reg.counter(
            "repro_batcher_steps_total", "engine steps run by batchers")
        self._m_slot_steps = reg.counter(
            "repro_batcher_slot_steps_total",
            "live slots fed by batchers' engine steps", ("kind",))

        # the weights are an argument, not a closure: a closed-over array
        # becomes a constant of the executable, and every (plan, bucket)
        # engine of a pool would carry its own copy of the model. The cache
        # is donated: after a step only the returned cache is valid.
        def _step_fn(p, c, t):
            self.trace_count += 1            # python side effect: trace-time only
            return decode_step(p, cfg, c, t, dist)

        self._step = jax.jit(_step_fn, donate_argnums=(1,))
        if warmup:
            # AOT-compile the decode step before the first request arrives.
            # Tracing it resolves every GEMM call-site's GemmPlan (the plan
            # cache is keyed on static shapes), so serving never pays plan
            # resolution or compilation inside the request loop. Numerics
            # policies bind at *trace* time (dispatch.gemm looks the site up
            # while tracing), so warmup must happen inside the policy context
            # — a warmup under the wrong policy would bake the wrong formats
            # into the compiled step and silently ignore the plan at serve
            # time. This is the ROADMAP "batching under plans" fix.
            tok0 = jnp.zeros((n_slots, 1), jnp.int32)
            with self._policy_ctx():
                self._step = self._step.lower(params, self.cache,
                                              tok0).compile()

    def _policy_ctx(self):
        return use_policy(self.policy) if self.policy is not None \
            else contextlib.nullcontext()

    def cache_remaining(self) -> int:
        """Writable KV positions left before the global write cursor hits the
        cache wall. The cursor advances one position per engine step (shared
        by every slot) and never rewinds, so this is the budget any newly
        admitted request's ``prompt + max_new`` must fit inside."""
        return max(0, self.max_len - 1 - self._len)

    def reset_cache(self) -> None:
        """Reclaim KV room without recompiling: reallocate the cache and
        rewind the cursor. The compiled decode step is shape-stable — the
        cache is data — so this is the cheap lifecycle move for long-running
        engines. Only legal while no slot is live (a live slot's KV would be
        destroyed mid-generation)."""
        if any(r is not None for r in self.active):
            raise RuntimeError("reset_cache with live slots would destroy "
                               "in-flight generations; drain first")
        with phase("batcher.reset_cache"):
            # the old cache goes first, so two never share the device
            self.cache = None
            self.cache = self._empty_cache()
            self._len = 0
            self._start[:] = 0

    def _empty_cache(self) -> dict:
        cache = init_cache(self.cfg, self.n_slots, self.max_len,
                           dtype=jnp.float32)
        cache["start"] = jnp.zeros((self.n_slots,), jnp.int32)
        return cache

    def stats(self):
        """Typed ``PlanCacheStats`` for the process-global GemmPlan cache —
        the serving-health counters (a warm engine over a preloaded schedule
        zoo shows ``misses == 0``, ``persisted_loads > 0``).

        .. deprecated:: a view over the ``repro.obs`` registry
           (``repro_plan_cache_ops_total`` / ``repro_plan_cache_size``);
           scrape the registry for monitoring."""
        from repro.core import dispatch
        return dispatch.plan_cache_stats()

    def step_hlo_text(self) -> Optional[str]:
        """The compiled decode step's optimized HLO text (``None`` for an
        engine that was not warmed up, whose step compiles lazily). A
        profiler's op events name the step's instructions; this text maps
        each to its ``op_name``, whose ``site.*`` scope names the GEMM site
        it ran for."""
        as_text = getattr(self._step, "as_text", None)
        return as_text() if as_text is not None else None

    def numerics_info(self) -> dict:
        """GemmPlan cache + call-site report for this engine's decode step
        (introspection: what the dispatch layer planned for serving)."""
        from repro.core import dispatch
        return {"plans": self.stats().as_dict(),
                "sites": sorted(dispatch.sites_seen()),
                "policy": self.policy.name if self.policy else None}

    def submit(self, req: Request):
        self.queue.append(req)

    def _fill_slots(self):
        changed = False
        for i in range(self.n_slots):
            if self.active[i] is None and self.queue:
                head = self.queue[0]
                if len(head.prompt) + head.max_new > self.cache_remaining():
                    # the cursor has outrun the cache: admitting this request
                    # would silently truncate its generation (the historical
                    # bug). Refuse the slot and leave it queued — FIFO, so
                    # later smaller requests never starve the head.
                    break
                self.active[i] = self.queue.popleft()
                self._fed[i] = 0
                self._start[i] = self._len
                changed = True
        if changed:
            self.cache["start"] = jnp.asarray(self._start)

    def _next_tokens(self):
        toks = np.zeros((self.n_slots, 1), dtype=np.int32)
        for i, req in enumerate(self.active):
            if req is None:
                continue
            if self._fed[i] < len(req.prompt):        # still prefilling
                toks[i, 0] = req.prompt[self._fed[i]]
            elif req.out:
                toks[i, 0] = req.out[-1]
            else:
                toks[i, 0] = req.prompt[-1]
        return jnp.asarray(toks)

    def step(self):
        """One engine step: feed one token per active slot. Its parts are
        profiler phases (``batcher.fill`` ... ``batcher.deliver``)."""
        with phase("batcher.step"):
            with phase("batcher.fill"):
                self._fill_slots()
            if all(r is None for r in self.active):
                return False
            with phase("batcher.prepare"):
                toks = self._next_tokens()
            # non-warmed engines trace lazily on the first step; entering the
            # policy context here keeps that trace (and any retrace) under the
            # same numerics the warmup path compiles with
            with phase("batcher.launch"), self._policy_ctx():
                logits, self.cache = self._step(self.params, self.cache, toks)
            self._len += 1
            with phase("batcher.sample"):
                nxt = np.asarray(
                    jnp.argmax(logits[:, 0, :self.cfg.vocab_size], -1))
            with phase("batcher.deliver"):
                self._deliver(nxt)
        return True

    def _deliver(self, nxt) -> None:
        """After a step: advance every live slot, hand out its sampled
        token, free finished slots, and count the step's slot-steps."""
        fed = dict.fromkeys(SLOT_KINDS, 0)
        for i, req in enumerate(self.active):
            if req is None:
                continue
            self._fed[i] += 1
            req.steps += 1
            if self._fed[i] < len(req.prompt):
                req.prefill_tokens += 1
                fed["prefill"] += 1
                continue                                # still prefilling
            if self._fed[i] == len(req.prompt):
                req.prefill_tokens += 1
                fed["prefill_last"] += 1
            else:
                fed["decode"] += 1
            req.out.append(int(nxt[i]))
            req.decode_tokens += 1
            if req.on_token is not None:
                req.on_token(req.out[-1])
            hit_eos = self.eos_id is not None and req.out[-1] == self.eos_id
            # the cursor wall: the next feed would write past the cache.
            # Admission control (cache_remaining) guarantees this never fires
            # for admitted requests; it stays as the last-ditch guard.
            at_wall = self._len >= self.max_len - 1
            if len(req.out) >= req.max_new or hit_eos or at_wall:
                req.done = True
                self.active[i] = None                   # slot freed
        self.steps_run += 1
        self._m_steps.inc()
        for kind, n in fed.items():
            if n:
                self.slot_steps[kind] += n
                self._m_slot_steps.inc(n, kind=kind)

    def run(self, max_steps: int = 10_000) -> None:
        """Drive until the queue and all slots drain (or max_steps).

        Raises ``CacheExhausted`` when the queue is non-empty but nothing can
        ever be admitted (the global cursor has outrun the cache) — loud
        refusal instead of the old silent truncation."""
        with span("serving.batcher_run", n_slots=self.n_slots,
                  max_len=self.max_len) as sp:
            steps = 0
            for _ in range(max_steps):
                if not self.step():
                    if self.queue:
                        head = self.queue[0]
                        raise CacheExhausted(
                            f"{len(self.queue)} queued request(s) can no "
                            f"longer fit: head needs "
                            f"{len(head.prompt) + head.max_new} positions, "
                            f"cache_remaining()={self.cache_remaining()} "
                            f"of max_len={self.max_len}")
                    break
                steps += 1
            sp.annotate(steps=steps)


def serve_requests(cfg, params, requests: list[Request], n_slots: int = 4,
                   max_len: int = 128, dist=LOCAL, warmup=False,
                   policy=None) -> list[Request]:
    """Convenience: run a list of requests to completion."""
    eng = ContinuousBatcher(cfg, params, n_slots, max_len, dist,
                            warmup=warmup, policy=policy)
    for r in requests:
        eng.submit(r)
    eng.run()
    return requests
