"""`shard_map` with replication checking off, for the installed jax."""
from __future__ import annotations

import jax


def shard_map_compat(body, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
