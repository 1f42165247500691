"""Compile rehearsal for TPU v5e: the Pallas FDP kernels at qwen3-0.6b widths
compile for a described (not attached) v5e chip, every tile the plan
layer can hand them meets the TPU tiling rule, and the decode step updates
its donated KV cache in place.

Nothing runs on a chip here: the TPU compiler refuses what the chip would
refuse (misaligned blocks, gathers Mosaic cannot lower, VMEM overruns), and
``memory_analysis`` sizes the program. The topology is described inside a
fixture, never at import, so every pytest-xdist worker collects the same
tests and only the worker that runs this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.accumulator import SAFE_CHUNK, AccumulatorSpec
from repro.core.dispatch import (AUTOTUNE_CANDIDATES, LANES, SUBLANES,
                                 GemmPlan, _heuristic_plan)
from repro.core.formats import BF16, FP32
from repro.kernels import ops as kops

SPEC = AccumulatorSpec.paper_91bit()

# qwen3-0.6b: d_model 1024, 16 query / 8 kv heads of 128, d_ff 3072,
# vocab 151936; decode runs 8 slots (one sublane tile of rows)
D, DFF, HD, VOCAB, SLOTS, CTX = 1024, 3072, 128, 151936, 8, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.memory_analysis()


def _gemm(fmt):
    return lambda a, b: kops.fdp_gemm(a, b, spec=SPEC, fmt=fmt,
                                      interpret=False)


def _batched(a, b):
    return kops.fdp_gemm_batched(a, b, spec=SPEC, interpret=False)


def _ragged(x, w, gs):
    return kops.fdp_ragged_gemm(x, w, gs, spec=SPEC, interpret=False)


def _ragged_dw(x, g, gs):
    return kops.fdp_ragged_dw(x, g, gs, num_groups=gs.shape[0], spec=SPEC,
                              interpret=False)


# (name, fn, operand shapes, dtypes): the decode-step sites, attention's
# batched contractions over (slots x kv heads), and the MoE kernel pair
CASES = [
    ("mlp_in", _gemm(FP32), [(SLOTS, D), (D, DFF)], [jnp.float32] * 2),
    ("mlp_out_bf16", _gemm(BF16), [(SLOTS, DFF), (DFF, D)],
     [jnp.bfloat16] * 2),
    ("prefill_attn_q", _gemm(FP32), [(256, D), (D, 16 * HD)],
     [jnp.float32] * 2),
    ("lm_head", _gemm(FP32), [(SLOTS, D), (D, VOCAB)], [jnp.float32] * 2),
    ("attn_qk", _batched, [(SLOTS * 8, 2, HD), (SLOTS * 8, HD, CTX)],
     [jnp.float32] * 2),
    ("attn_av", _batched, [(SLOTS * 8, 2, CTX), (SLOTS * 8, CTX, HD)],
     [jnp.float32] * 2),
    ("ragged_fwd", _ragged, [(256, D), (8, D, DFF), (8,)],
     [jnp.float32, jnp.float32, jnp.int32]),
    ("ragged_dw", _ragged_dw, [(256, D), (256, DFF), (8,)],
     [jnp.float32, jnp.float32, jnp.int32]),
]


@pytest.mark.parametrize("name,fn,shapes,dtypes", CASES,
                         ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, no_cache, name, fn, shapes,
                                 dtypes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in zip(shapes, dtypes)]
    mem = _compile(fn, *args)
    assert mem.temp_size_in_bytes < 1 << 30, (name, mem)


def _legal(block: int, dim: int, align: int) -> bool:
    """A block dim the TPU lowering accepts once the wrapper pads the array
    to a block multiple: a multiple of ``align``, or the whole padded dim
    (a single block, itself a multiple of 8)."""
    return block % SUBLANES == 0 and (block % align == 0 or block >= dim)


DIMS = (1, 7, 8, 33, 100, 128, 129, 300, 1024, 3072, 151936)


@pytest.mark.parametrize("tile", sorted(set(
    AUTOTUNE_CANDIDATES + ((32, 32, 128), (1, 1, 1), (200, 300, 9000),
                           (8, 64, 96)))))
def test_fitted_tiles_meet_tpu_tiling(tile):
    """Every tile ``GemmPlan.fit`` returns, for any problem, has bm on
    sublanes and bn/bk on lanes (the kernels' A (bm, bk), B (bk, bn) and
    O (bm, bn) blocks; the wgrad's Xᵀ block is (bm, bk) too), within the
    carry headroom."""
    for m in DIMS:
        for n in DIMS:
            for k in DIMS:
                p = GemmPlan(*tile).fit(m, n, k)
                assert _legal(p.bm, m, SUBLANES), (tile, m, n, k, p)
                assert _legal(p.bn, n, LANES), (tile, m, n, k, p)
                assert _legal(p.bk, k, LANES), (tile, m, n, k, p)
                assert p.bk <= SAFE_CHUNK
                assert p.fit(m, n, k) == p


def test_heuristic_plan_is_fitted():
    for m, n, k in ((1, VOCAB, D), (SLOTS, DFF, D), (2, CTX, HD),
                    (256, D, DFF)):
        p = _heuristic_plan(1, m, n, k)
        assert p.fit(m, n, k) == p


# The decode step's KV stack at the chat and solve engines' sizes, cut to
# four layers: (model, policy, slots, max_len). qwen3-0.6b's attention is
# grouped (G = 2); qwen1.5-4b's is MHA (G = 1), one row per (slot, head).
STEPS = {"native": ("qwen3-0.6b", "mxu_fp32", 8, 1024),
         "fdp91": ("qwen3-0.6b", "fdp91", 4, 512),
         "mha_native": ("qwen1.5-4b", "mxu_fp32", 8, 1024)}


def _row_pads() -> float:
    from repro.obs.registry import default_registry
    counter = default_registry().get("repro_dispatch_row_pad_total")
    return 0.0 if counter is None else counter.total()


def _strip_metadata(hlo: str) -> str:
    """The HLO text without op metadata and the source-location tables,
    which name the Python functions and lines that traced each op."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    head, _, tables = hlo.partition("\nFileNames")
    return head + (tables[tables.index("\n%"):] if tables else "")


@pytest.mark.parametrize("name", list(STEPS))
def test_decode_step_writes_kv_rows_in_place_for_v5e(one_chip, no_cache,
                                                     name, monkeypatch):
    """With the cache donated, the compiled step aliases every cache leaf,
    copies no stack, and writes into each stack only one position's row:
    the stacks keep their layout through the layer scan.

    MHA (G = 1): the attention contractions stay dots at ``HIGHEST`` that
    read each layer's K/V slice as it lies, so no slice is copied or relaid
    out and the temporaries are the weight converts. GQA: the one-row path
    never engages, and the step compiles to the instructions of a plain
    einsum."""
    import dataclasses

    from repro.configs import get_config
    from repro.core import dispatch
    from repro.core.dispatch import FDP91, MXU_FP32, use_policy
    from repro.models import decode_step, init, init_cache

    arch, policy, slots, max_len = STEPS[name]
    policy = {"mxu_fp32": MXU_FP32, "fdp91": FDP91}[policy]
    cfg = dataclasses.replace(get_config(arch), n_layers=4)

    def cache():
        c = init_cache(cfg, slots, max_len, dtype=jnp.float32)
        return c | {"start": jnp.zeros((slots,), jnp.int32)}

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), jax.eval_shape(tree))

    params, c = on_chip(lambda: init(cfg, jax.random.key(0))), on_chip(cache)
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)

    def compile_step():
        with use_policy(policy):
            return jax.jit(lambda p, c, t: decode_step(p, cfg, c, t),
                           donate_argnums=(1,)).lower(params, c, tok
                                                      ).compile()

    pads = _row_pads()
    compiled = compile_step()
    pads = _row_pads() - pads
    hlo = compiled.as_text()
    n_params, n_cache = len(jax.tree.leaves(params)), len(jax.tree.leaves(c))
    aliased = {int(n) for n in re.findall(r"\{[\d,]*\}: \((\d+), \{",
                                          hlo.split("\n", 1)[0])}
    assert set(range(n_params, n_params + n_cache)) <= aliased
    k = c["layers"]["k"]
    stack = f"f32[{','.join(map(str, k.shape))}]"
    made = re.findall(rf"= {re.escape(stack)}\S* ([\w-]+)\(", hlo)
    assert "copy" not in made and "custom-call" not in made, made
    rows = re.findall(rf"= {re.escape(stack)}\S* dynamic-update-slice\("
                      rf"%[\w.-]+, %([\w.-]+),", hlo)
    shape_of = dict(re.findall(r"%([\w.-]+) = (\w+\[[\d,]*\])", hlo))
    row = f"f32[1,{slots},{cfg.n_kv_heads},1,{cfg.head_dim}]"
    assert len(rows) == 2 and {shape_of[r] for r in rows} == {row}, rows

    if cfg.n_heads == cfg.n_kv_heads:
        assert pads > 0
        # no op makes a copy of one layer's slice, in any layout
        dims = ",".join(map(str, k.shape[1:]))
        slice_ops = re.findall(rf"%([\w-]+?)(?:\.\d+)? = f32\[(?:1,)?{dims}\]"
                               rf"\S* ([\w-]+)\(", hlo)
        assert not [o for o in slice_ops if o[0].startswith("copy")
                    or o[1].startswith("copy")], slice_ops
        sites = ("site.attn_qk.fwd", "site.attn_av.fwd")
        for site in sites:
            ops = [ln for ln in hlo.splitlines()
                   if f"/{site}/" in ln and " convolution(" in ln]
            assert ops and all("operand_precision={highest,highest}" in ln
                               for ln in ops), (site, ops)
        assert not [ln for ln in hlo.splitlines()
                    if "multiply_reduce_fusion" in ln.split("=")[0]
                    and any(f"/{s}/" in ln for s in sites)]
        weights = sum(x.size * 2 for x in jax.tree.leaves(params["layers"])
                      if x.ndim == 3)     # the stacked matrices, as bf16
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp <= weights + (32 << 20), (temp, weights)
        return
    assert pads == 0
    if policy is MXU_FP32:
        def plain(site, cfg, subscripts, lhs, rhs):
            dt = cfg.fmt.jnp_dtype
            lhs, rhs = lhs.astype(dt), rhs.astype(dt)
            with jax.named_scope(site.scope):
                return jnp.einsum(subscripts, lhs, rhs,
                                  preferred_element_type=jnp.float32)

        monkeypatch.setattr(dispatch, "_grouped_native", plain)
        assert _strip_metadata(compile_step().as_text()) == \
            _strip_metadata(hlo)
