"""shard_map with the repo's defaults — the one place every sharded
caller builds its program (parallel/, the engine-selection mesh tier
in ops/pallas_gf.py, codes/engine.py's sharded program variants).

jax is imported lazily so the AST analysis tier keeps working in
jax-free environments.
"""

from __future__ import annotations


def shard_map_compat(fn, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map(fn, mesh, in_specs, out_specs)`` with the
    varying-manual-axes check off by default (the GF programs
    XOR-reduce across shards in ways the static analysis cannot see
    through; every sharded caller here pins byte-identity in tests
    instead)."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def batch_spec(axis: str, rank: int):
    """PartitionSpec sharding axis 0 of a rank-``rank`` array over mesh
    axis ``axis``, everything else replicated — the stripe-batch
    sharding every mesh-tier program uses."""
    from jax.sharding import PartitionSpec as P

    return P(axis, *([None] * (rank - 1)))
