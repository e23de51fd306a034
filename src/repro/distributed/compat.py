"""``shard_map`` with the repo's default of ``check_vma=False``.  All repo
code imports ``shard_map`` from here."""

from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=check_vma)
