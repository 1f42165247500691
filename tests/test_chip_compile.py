"""Compile rehearsal for TPU v5e: the Pallas FDP kernels at qwen3-0.6b widths
compile for a described (not attached) v5e chip, and every tile the plan
layer can hand them meets the TPU tiling rule.

Nothing runs on a chip here: the TPU compiler refuses what the chip would
refuse (misaligned blocks, gathers Mosaic cannot lower, VMEM overruns), and
``memory_analysis`` sizes the program. The topology is described inside a
fixture, never at import, so every pytest-xdist worker collects the same
tests and only the worker that runs this file loads the TPU library.
"""

import os

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
