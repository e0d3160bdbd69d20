"""The framework's one spelling of ``jax.shard_map``."""

import jax


def shard_map(f, mesh, in_specs, out_specs, check=False, axis_names=None):
    """shard_map with replication checking off by default (our collectives
    handle replication explicitly, as the reference's NCCL calls did).

    ``axis_names``: map over only these mesh axes; the rest stay under
    automatic GSPMD partitioning (used by the pipeline engine to permute
    over "stage" while data/model axes shard transparently)."""
    kwargs = {"check_vma": check}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
