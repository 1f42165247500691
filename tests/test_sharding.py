"""Sharding rules: every parameter/cache leaf of every assigned architecture
gets a valid PartitionSpec (sharded dims divisible by their mesh axes) under
every profile — the static half of what the dry-run proves by compiling."""

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, all_arch_names
from repro.launch.sharding import param_specs
from repro.models import transformer as T

MESH = AbstractMesh((16, 16), ("data", "model"))
AXIS = dict(MESH.shape)
AXIS_MP = {"pod": 2, **AXIS}


def _check_tree(specs, shapes, axis_sizes):
    def visit(spec, leaf):
        assert isinstance(spec, P), spec
        assert len(spec) <= leaf.ndim, (spec, leaf.shape)
        for d, s in enumerate(spec):
            if s is None:
                continue
            axes = s if isinstance(s, tuple) else (s,)
            n = 1
            for a in axes:
                n *= axis_sizes[a]
            assert leaf.shape[d] % n == 0, \
                f"dim {d} ({leaf.shape[d]}) not divisible by {axes} ({n})"

    jax.tree.map(visit, specs, shapes,
                 is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", all_arch_names())
@pytest.mark.parametrize("profile", ["fsdp", "ddp", "decode_tp"])
def test_param_specs_divisible(arch, profile):
    cfg = get_config(arch)
    aparams = T.init_abstract(cfg)
    specs = param_specs(cfg, aparams, profile=profile, mesh=MESH)
    # same tree structure
    assert jax.tree.structure(
        jax.tree.map(lambda _: 0, aparams)) == jax.tree.structure(
        jax.tree.map(lambda _: 0, specs, is_leaf=lambda x: isinstance(x, P)))
    _check_tree(specs, aparams, AXIS)


@pytest.mark.parametrize("arch", ["grok-1-314b", "mamba2-1.3b",
                                  "zamba2-2.7b", "whisper-large-v3"])
def test_cache_structs_buildable(arch):
    """init_cache builds an abstract cache for every family (no allocation)."""
    cfg = get_config(arch)
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 8, 128))
    assert "len" in cache
    n_leaves = len(jax.tree.leaves(cache))
    assert n_leaves >= 3


def test_input_specs_public_api():
    from repro.launch.dryrun import input_specs
    b = input_specs("llama3.2-3b", "train_4k")
    assert b["tokens"].shape == (256, 4096)
    b = input_specs("whisper-large-v3", "prefill_32k")
    assert b["frames"].shape == (32, 1500, 1280)
    b = input_specs("paligemma-3b", "train_4k")
    assert b["tokens"].shape[1] + b["patches"].shape[1] == 4096
