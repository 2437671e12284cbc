"""Row-sharded embedding tables: shard-local gather + segment-sum combine.

A DLRM backend's embedding tables dominate its memory, not its FLOPs —
one chip's HBM caps the servable vocabulary long before compute matters.
This module lifts that ceiling the same way ``kv_shard.py`` lifts the KV
arena's: the *stacked* table matrix (all tables concatenated row-wise,
``[num_tables * rows_per_table, dim]``) is row-sharded over a 1-D
``"emb"`` mesh axis with ``NamedSharding``, and the ragged bag lookup
runs under ``shard_map``:

- every shard gathers the lookups whose **global row** falls in its local
  row range (unowned lookups read local row 0 and are masked to zero —
  the gather shape stays static);
- each shard segment-sums its owned vectors into the per-bag pooled
  matrix (``num_segments = max_batch_size × num_tables`` bags);
- a ``psum`` (default) or the Pallas remote-DMA ring from ``kv_shard``
  sums the per-shard partials, since one bag's lookups may span shards.

The combine order differs from the single-device oracle's, so exactness
needs sums the accumulation order can't perturb: ``quantize_table``
snaps values to 1/256 steps (integer multiples of 2^-8 sum exactly in
fp32 while |sum| < 2^15), which the DLRM backend applies to its table
init — making sharded-vs-oracle parity *bit-identical*, the property the
tier-1 suite asserts on 8 virtual CPU devices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def emb_mesh(n_shards: int):
    """A 1-D ``("emb",)`` mesh over the first ``n_shards`` devices."""
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_shards > len(devices):
        raise ValueError(
            f"emb_shards={n_shards} but runtime has {len(devices)} "
            f"device(s)")
    return Mesh(np.asarray(devices[:n_shards]), ("emb",))


def quantize_table(table):
    """Snap table values to 1/256 steps: integer multiples of 2^-8 add
    exactly in fp32 (until |sum| reaches 2^15), so the cross-shard psum's
    accumulation order cannot produce rounding drift vs the oracle."""
    import numpy as np

    return (np.round(np.asarray(table, np.float32) * 256.0) / 256.0).astype(
        np.float32)


def shard_table(table, mesh):
    """Place the stacked table on the mesh, rows sharded over ``emb``.
    The row count must divide evenly (pad the stacked matrix with zero
    rows first if it doesn't — zero rows are never indexed)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    if table.shape[0] % mesh.shape["emb"]:
        raise ValueError(
            f"stacked table rows ({table.shape[0]}) must divide evenly "
            f"over emb_shards ({mesh.shape['emb']})")
    return jax.device_put(table, NamedSharding(mesh, P("emb", None)))


def bag_sum_oracle(table, rows, seg_ids, num_segments: int):
    """Single-device reference: gather ``rows`` from the stacked table
    and segment-sum into ``num_segments`` bags.  Lookups whose
    ``seg_ids`` fall outside ``[0, num_segments)`` are padding and
    contribute nothing (masked explicitly — never trust scatter's
    out-of-bounds mode for correctness)."""
    valid = seg_ids < num_segments
    safe_rows = jnp.where(valid, rows, 0)
    vecs = table[safe_rows]
    vecs = jnp.where(valid[:, None], vecs, 0.0).astype(table.dtype)
    return jax.ops.segment_sum(
        vecs, jnp.where(valid, seg_ids, 0), num_segments=num_segments)


def sharded_bag_sum(mesh, table, rows, seg_ids, num_segments: int, *,
                    combine: str = "psum", interpret: bool = False):
    """The sharded bag lookup (see module docstring): same signature and
    result as :func:`bag_sum_oracle` plus the mesh.  ``table`` should
    already be placed by :func:`shard_table`; ``rows``/``seg_ids`` are
    replicated (they are a lookup-bucket long, tiny next to the table)."""
    from jax.sharding import PartitionSpec as P

    if combine not in ("ring", "psum"):
        raise ValueError(f"combine must be 'ring' or 'psum', "
                         f"got {combine!r}")
    n = mesh.shape["emb"]
    r_loc = table.shape[0] // n

    def body(tbl_sh, rows, seg_ids):
        idx = jax.lax.axis_index("emb")
        lo = idx * r_loc
        valid = seg_ids < num_segments
        owned = valid & (rows >= lo) & (rows < lo + r_loc)
        loc = jnp.where(owned, rows - lo, 0).astype(jnp.int32)
        vecs = tbl_sh[loc]
        vecs = jnp.where(owned[:, None], vecs, 0.0).astype(tbl_sh.dtype)
        pooled = jax.ops.segment_sum(
            vecs, jnp.where(valid, seg_ids, 0), num_segments=num_segments)
        if combine == "ring":
            from client_tpu.parallel.kv_shard import ring_all_reduce

            return ring_all_reduce(pooled, "emb", n, interpret=interpret)
        return jax.lax.psum(pooled, "emb")

    from jax import shard_map

    fn = shard_map(body, mesh=mesh, in_specs=(P("emb", None), P(), P()),
                   out_specs=P(), check_vma=False)
    return fn(table, rows, seg_ids)
