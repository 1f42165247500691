"""``shard_map`` with replication checking off, the one form this code uses."""

from __future__ import annotations

import jax


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False``: the FDP collectives and
    the MoE dispatch return per-device values that the varying-axes check
    cannot see are replicated."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
