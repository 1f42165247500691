"""The dense decoder against its plain reference, on the CPU in float32.

The reference is the benchmark's own (``bench/references/dense_decoder.py``,
loaded by path; it imports nothing of the program), and the weights come
from the benchmark's ``bench/weights.py``, so the q/k/v biases are drawn
(0.1 normal) and not the zeros ``init`` gives. Two toys of the two
configurations the benchmark serves, each d 64, head_dim 16, d_ff 128,
vocab 256, 2 layers:

- ``mha``: Qwen1.5 (qwen2), 4/4 heads, q/k/v bias, no qk_norm, rope 5e6;
- ``gqa``: Qwen3, 4/2 heads, qk_norm, no bias, rope 1e6.

The program runs under ``MXU_FP32`` (native float32 sites), which on the
CPU is a float32 matmul, so both sides compute in float32 and differ only
in the order of their sums and in how norms, softmax and rotary angles
are spelled.
"""

import dataclasses
import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.dispatch import MXU_FP32, use_policy
from repro.launch.batching import ContinuousBatcher, Request
from repro.models import forward, init

BENCH = Path(__file__).resolve().parents[1] / "bench"
SEED = 2**31 + 4242

# Logits reach ~4 here. Float32 on both sides: the widest gap seen is
# ~2e-6, on the full forward and through the cache alike (the cache also
# turns its rotary angles at the batcher's global cursor, not from 0).
# 2e-5 leaves ten times that for another BLAS's order of summation; the
# program without its q/k/v biases misses by ~2 (test_biases_are_seen).
ATOL = 2e-5

COMMON = {"hidden_size": 64, "head_dim": 16, "intermediate_size": 128,
          "vocab_size": 256, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
          "num_attention_heads": 4}
TOYS = {
    "mha": dict(COMMON, arch="qwen1.5-4b", num_key_value_heads=4,
                attention_bias=True, qk_norm=False, rope_theta=5e6),
    "gqa": dict(COMMON, arch="qwen3-0.6b", num_key_value_heads=2,
                attention_bias=False, qk_norm=True, rope_theta=1e6),
}


def _load(rel: str):
    path = BENCH / rel
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load("references/dense_decoder.py")
weights = _load("weights.py")


def _model_config(doc: dict):
    return dataclasses.replace(
        get_config(doc["arch"]),
        n_layers=doc["num_hidden_layers"], d_model=doc["hidden_size"],
        n_heads=doc["num_attention_heads"],
        n_kv_heads=doc["num_key_value_heads"],
        d_ff=doc["intermediate_size"], vocab_size=doc["vocab_size"],
        head_dim=doc["head_dim"], qkv_bias=doc["attention_bias"],
        qk_norm=doc["qk_norm"], rope_theta=doc["rope_theta"],
        norm_eps=doc["rms_norm_eps"])


@functools.lru_cache(maxsize=None)
def _toy(name: str):
    """-> (config file's keys, program config, weights, jitted reference)"""
    doc = TOYS[name]
    cfg = _model_config(doc)
    abstract = jax.eval_shape(lambda: init(cfg, jax.random.key(0)))
    params = weights.make(abstract, SEED)
    ref = jax.jit(lambda p, t: reference.forward(p, doc, t))
    return doc, cfg, params, ref


@pytest.fixture(params=sorted(TOYS))
def toy(request):
    return _toy(request.param)


def _tokens(n: int, vocab: int, salt: int) -> np.ndarray:
    rng = np.random.default_rng([SEED, salt])
    return rng.integers(0, vocab, n).astype(np.int32)


def _forward_gap(cfg, params, ref, toks, ref_params=None) -> float:
    """Widest |program - reference| logit over ``toks``; the reference
    reads ``ref_params`` where given, else the program's weights."""
    with use_policy(MXU_FP32):
        got = forward(params, cfg, {"tokens": jnp.asarray(toks)[None]})
    want = ref(params if ref_params is None else ref_params,
               jnp.asarray(toks))
    return float(jnp.max(jnp.abs(got[0, :, :cfg.vocab_size] - want)))


def test_forward_matches_reference(toy):
    _, cfg, params, ref = toy
    assert _forward_gap(cfg, params, ref, _tokens(24, cfg.vocab_size, 0)) \
        <= ATOL


def test_cached_decode_matches_reference(toy):
    """Prompts fed one token a step, then decoding, through the batcher's
    compiled ``decode_step`` on the stacked cache: two slots, three
    requests, so the third is admitted into a freed slot mid-stream, with
    its ``start`` past 0 and its rotary positions at the global cursor.
    Every logit row the step returned, prompt and output positions alike,
    matches the reference's full forward over that request's sequence."""
    _, cfg, params, ref = toy
    eng = ContinuousBatcher(cfg, params, n_slots=2, max_len=32,
                            warmup=MXU_FP32)
    rows = []                       # (request, position fed, logits row)
    compiled = eng._step

    def recording(p, c, t):
        fed = [(r, int(eng._fed[i])) for i, r in enumerate(eng.active)]
        logits, c = compiled(p, c, t)
        out = np.asarray(logits[:, 0, :cfg.vocab_size])
        rows.extend((r, pos, out[i]) for i, (r, pos) in enumerate(fed)
                    if r is not None)
        return logits, c

    eng._step = recording
    reqs = [Request(i, _tokens(n, cfg.vocab_size, 1 + i).tolist(), max_new=m)
            for i, (n, m) in enumerate([(5, 4), (7, 3), (4, 5)])]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    assert int(eng._start.max()) > 0          # a slot was reused
    for r in reqs:
        seq = r.prompt + r.out
        want = np.asarray(ref(params, jnp.asarray(seq, jnp.int32)))
        got = {pos: row for q, pos, row in rows if q is r}
        assert sorted(got) == list(range(len(seq) - 1))
        gap = max(float(np.max(np.abs(row - want[pos])))
                  for pos, row in got.items())
        assert gap <= ATOL, (r.uid, gap)


def test_biases_are_seen():
    """With the program's q/k/v biases zeroed and the reference's kept,
    the forward comparison of the MHA toy (the one with biases) fails its
    tolerance by far: the comparison sees the bias path."""
    _, cfg, params, ref = _toy("mha")
    attn = dict(params["layers"]["attn"])
    for b in ("bq", "bk", "bv"):
        attn[b] = jnp.zeros_like(attn[b])
    zeroed = dict(params, layers=dict(params["layers"], attn=attn))
    toks = _tokens(24, cfg.vocab_size, 0)
    assert _forward_gap(cfg, zeroed, ref, toks, params) > 1000 * ATOL
