"""The decode step writes one row of the stacked KV cache in place.

``decode_step`` carries the stacked cache through its layer scan and writes
each layer's new row into the stack; the batcher donates the cache to its
compiled step, so the output cache takes over the input's buffers. Both are
pure data movement: logits and every cache leaf must equal, bit for bit, the
older formulation below, which scanned the cache as ``xs`` and returned each
layer's updated slice as ``ys``."""

import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.dispatch import FDP91, MXU_FP32, use_policy
from repro.launch import batching
from repro.launch.batching import ContinuousBatcher, Request
from repro.models import LOCAL, decode_step, init, init_cache
from repro.models import transformer as T

KV_KEYS = ("k", "v", "k_scale", "v_scale")


def scan_xs_ys_decode_step(params, cfg, cache, tokens, dist=LOCAL):
    """The decoder-only step as it was: each layer's cache slice in as a
    scan input, its updated slice out as a scan output."""
    x = T._embed(params, cfg, tokens, dist)
    ln = cache["len"]
    pos = ln + jnp.zeros((x.shape[0], 1), jnp.int32)
    keys = [k for k in KV_KEYS if k in cache["layers"]]

    def body(h, lc):
        kv = {k: lc[k] for k in keys} | {"len": ln, "start": cache["start"]}
        h, nc = T._decoder_block(h, lc["p"], cfg, dist, positions=pos,
                                 kv_cache=kv)
        return h, {k: nc[k] for k in keys}

    h, layers = jax.lax.scan(body, x, {"p": params["layers"],
                                       **cache["layers"]})
    new = {"len": ln + 1, "layers": layers, "start": cache["start"]}
    return T._logits(params, cfg, h, dist), new


def _filled_cache(cfg, n_slots, max_len, quantized, key):
    """A cache already holding noise, with a cursor past 0 and slots that
    start at different positions, so the steps read real rows."""
    cache = init_cache(cfg, n_slots, max_len, dtype=jnp.float32,
                       quantized=quantized)
    keys = jax.random.split(key, len(KV_KEYS))
    for k, kk in zip(KV_KEYS, keys):
        if k not in cache["layers"]:
            continue
        leaf = cache["layers"][k]
        if leaf.dtype == jnp.int8:
            cache["layers"][k] = jax.random.randint(
                kk, leaf.shape, -127, 128, jnp.int32).astype(jnp.int8)
        else:
            cache["layers"][k] = jax.random.uniform(
                kk, leaf.shape, jnp.float32, 0.01, 1.0)
    cache["len"] = jnp.asarray(5, jnp.int32)
    cache["start"] = jnp.asarray([0, 3, 5][:n_slots], jnp.int32)
    return cache


CASES = {
    "dense-native": ("qwen3-0.6b", False, MXU_FP32),
    "dense-fdp91": ("qwen3-0.6b", False, FDP91),
    "int8-cache": ("qwen3-0.6b", True, MXU_FP32),
    "moe": ("dbrx-132b", False, MXU_FP32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_decode_steps_match_the_scanned_slices(case):
    arch, quantized, policy = CASES[case]
    cfg = get_config(arch).reduced()
    params = init(cfg, jax.random.key(0))
    cache = _filled_cache(cfg, 3, 16, quantized, jax.random.key(1))
    new_step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t))
    old_step = jax.jit(lambda p, c, t: scan_xs_ys_decode_step(p, cfg, c, t))
    toks = jax.random.randint(jax.random.key(2), (3, 3), 0, cfg.vocab_size)
    c_new = c_old = cache
    with use_policy(policy):
        for t in range(toks.shape[1]):
            lg_new, c_new = new_step(params, c_new, toks[:, t:t + 1])
            lg_old, c_old = old_step(params, c_old, toks[:, t:t + 1])
            np.testing.assert_array_equal(np.asarray(lg_new),
                                          np.asarray(lg_old))
            assert jax.tree.structure(c_new) == jax.tree.structure(c_old)
            for a, b in zip(jax.tree.leaves(c_new), jax.tree.leaves(c_old)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(c_new["len"]) == 5 + toks.shape[1]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen3-0.6b").reduced()
    return cfg, init(cfg, jax.random.key(0))


def _head(hlo: str):
    """(parameter shapes in order, parameter numbers an output may take
    over) from the module's header line."""
    head = hlo.split("\n", 1)[0]
    layout = re.search(r"entry_computation_layout=\{\((.*?)\)->", head)
    shapes = re.findall(r"(\w+\[[\d,]*\])(?:\{[\d,]*\})?",
                        re.sub(r"/\*.*?\*/", "", layout.group(1)))
    # input_output_alias={ {1}: (14, {}, may-alias), ... }
    aliased = {int(n) for n in re.findall(r"\{[\d,]*\}: \((\d+), \{", head)}
    return shapes, aliased


_INSTR = re.compile(r"%([\w.-]+) = (\w+\[[\d,]*\])\S* ([\w-]+)\(([^)]*)\)")


def _instructions(hlo: str) -> list:
    """(name, result shape, opcode, operand names) of every instruction
    with an array result."""
    return [(n, shape, op, [a.strip().lstrip("%") for a in
                            re.sub(r"/\*.*?\*/", "", args).split(",")])
            for n, shape, op, args in _INSTR.findall(hlo)]


def _size(shape: str) -> int:
    return int(np.prod([int(d) for d in re.findall(r"\d+", shape[
        shape.index("["):])]))


def _hlo_shape(leaf) -> str:
    name = {"float32": "f32", "int32": "s32", "int8": "s8"}[leaf.dtype.name]
    return f"{name}[{','.join(map(str, leaf.shape))}]"


def _serve(cfg, params, reqs, n_slots=2, max_len=48):
    eng = ContinuousBatcher(cfg, params, n_slots=n_slots, max_len=max_len,
                            warmup=MXU_FP32)
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng


def _requests():
    return [Request(i, [3 + i, 11, 4 + i, 9][:2 + i % 3], max_new=3 + i % 2)
            for i in range(5)]


def test_warmed_batcher_step_aliases_the_cache(tiny):
    """The compiled step lets the output cache take over every input cache
    leaf, copies no whole stack, and writes into the stack only rows of
    one layer and one position."""
    cfg, params = tiny
    eng = ContinuousBatcher(cfg, params, n_slots=2, max_len=32,
                            warmup=MXU_FP32)
    hlo = eng.step_hlo_text()
    leaves = jax.tree.leaves(eng.cache)
    shapes, aliased = _head(hlo)
    # the parameters are the weights, the cache's leaves, then the tokens
    first = len(shapes) - 1 - len(leaves)
    assert shapes[first:-1] == [_hlo_shape(x) for x in leaves]
    assert set(range(first, len(shapes) - 1)) <= aliased
    k = eng.cache["layers"]["k"]
    stack = {_hlo_shape(k)}
    instrs = _instructions(hlo)
    assert not [i for i in instrs if i[1] in stack
                and i[2] in ("copy", "broadcast")]
    shape_of = {n: shape for n, shape, _, _ in instrs}
    writes = [shape_of[args[1]] for _, shape, op, args in instrs
              if op == "dynamic-update-slice" and shape in stack]
    row = k.shape[1] * k.shape[2] * k.shape[4]
    assert len(writes) == 2 and {_size(w) for w in writes} == {row}, writes


def test_donated_batcher_serves_what_an_undonated_one_does(tiny, monkeypatch):
    cfg, params = tiny
    donated = _requests()
    eng = _serve(cfg, params, donated)
    assert eng.trace_count == 1
    # a slot refilled after reset_cache runs the same executable
    eng.reset_cache()
    again = _requests()
    for r in again:
        eng.submit(r)
    eng.run()
    assert eng.trace_count == 1

    real_jit = jax.jit
    monkeypatch.setattr(batching.jax, "jit",
                        lambda f, **kw: real_jit(f))
    kept = _requests()
    ref = _serve(cfg, params, kept)
    monkeypatch.undo()
    assert not _head(ref.step_hlo_text())[1]
    for a, b, c in zip(donated, again, kept):
        assert a.done and b.done and c.done
        assert a.out == b.out == c.out


def test_reset_cache_lets_the_old_cache_go_first(tiny, monkeypatch):
    """reset_cache drops the old cache before it makes the new one, so the
    device never holds two caches at once."""
    cfg, params = tiny
    eng = _serve(cfg, params, _requests())
    old = [weakref.ref(x) for x in jax.tree.leaves(eng.cache)]
    real_init = batching.init_cache
    alive = []

    def init_cache(*a, **kw):
        alive.append([r() is not None for r in old])
        return real_init(*a, **kw)

    monkeypatch.setattr(batching, "init_cache", init_cache)
    eng.reset_cache()
    assert alive == [[False] * len(old)]
