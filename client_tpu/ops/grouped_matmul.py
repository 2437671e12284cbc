"""Grouped matmul: rows sorted by expert, each group times its own matrix.

The expert layer of a sparse decoder (models/pangu_moe.py) routes every token
to a few of many experts and holds only some of them.  The (token, expert)
pairs whose expert lives here are laid out **sorted by expert** and the layer
is three matmuls over that layout: ``y[rows of e] = x[rows of e] @ w[e]``.
No pair is dropped (the layout has room for every pair a wave can route
here) and an expert no token chose costs nothing: it has no rows, so no grid
step names it and its matrix is never fetched.

**The layout** (``plan_groups``): expert e's rows start on a multiple of
``tile_m`` and its group is padded with zero rows to whole tiles, so a tile of
``tile_m`` rows belongs to one expert.  ``tile_expert[t]`` names it and
``n_tiles`` says how many tiles hold rows; both arrive by scalar prefetch.
A wave of n tokens choosing k experts each routes at most ``n * min(k, E)``
pairs to the E experts held here, so ``capacity_rows`` is a static bound and
shapes never depend on the routing.

**The kernel** is one Pallas grid ``(N // tile_n, tiles)`` with the row tiles
innermost: for a column block j the grid walks the tiles in expert order, the
BlockSpec index map picks ``w[tile_expert[t], :, j]`` out of the stacked
weights, and consecutive tiles of one expert repeat the block index, which
the pipeline takes as unchanged and does not fetch again.  Tiles past
``n_tiles`` repeat the last tile's indices (nothing is fetched) and skip the
product.  So each touched expert's matrix is read once a call, whatever its
row count, and the contraction is whole in one block: no accumulator is
carried.  Rows of the output past the last used tile are never written and
hold junk: the caller reads rows by ``dest`` and nothing else.

``interpret=True`` runs the same kernel on the CPU; ``reference_grouped_matmul``
is the XLA oracle (a dense product a group, masked to the group's rows).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def capacity_rows(n_pairs_max: int, n_experts: int, tile_m: int) -> int:
    """Rows of the sorted layout that hold any routing of at most
    ``n_pairs_max`` pairs over ``n_experts`` groups, each padded to whole
    tiles: a static bound, in whole tiles."""
    worst = n_pairs_max + n_experts * (tile_m - 1)
    return -(-worst // tile_m) * tile_m


def plan_groups(expert, n_experts: int, tile_m: int, rows: int):
    """Where each pair goes.  ``expert [P]`` int32: the pair's expert among
    the ``n_experts`` held here, or ``n_experts`` for a pair that is not this
    layer's to compute.  Returns a dict of

    - ``sizes [E]``: pairs of each expert (0: untouched, never read);
    - ``dest [P]``: the pair's row in the sorted layout, ``rows`` (one past
      the end) where it has none;
    - ``tile_expert [rows // tile_m]``, ``n_tiles [1]``: the kernel's plan;
    - ``padded [E]``: each group's rows, padding included."""
    p = expert.shape[0]
    sizes_all = jnp.zeros(n_experts + 1, jnp.int32).at[expert].add(1)
    sizes = sizes_all[:n_experts]
    padded = -(-sizes // tile_m) * tile_m
    ends = jnp.cumsum(padded)
    starts = jnp.concatenate([ends - padded, jnp.full(1, rows, jnp.int32)])
    first = jnp.cumsum(sizes_all) - sizes_all      # of each group, sorted
    order = jnp.argsort(expert, stable=True)
    by_expert = expert[order]
    rank = jnp.arange(p, dtype=jnp.int32) - first[by_expert]
    dest_sorted = jnp.where(by_expert < n_experts,
                            starts[by_expert] + rank, rows)
    dest = jnp.zeros(p, jnp.int32).at[order].set(dest_sorted)
    n_tiles = ends[-1] // tile_m
    tiles = jnp.minimum(jnp.arange(rows // tile_m, dtype=jnp.int32),
                        jnp.maximum(n_tiles - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends // tile_m, tiles, side="right"),
        n_experts - 1).astype(jnp.int32)
    return {"sizes": sizes, "dest": dest, "padded": padded,
            "tile_expert": tile_expert,
            "n_tiles": n_tiles.reshape(1).astype(jnp.int32)}


def pick_tile_n(k: int, n: int, itemsize: int, cap_bytes: int = 16 << 20):
    """Columns of a weight block: the largest multiple-of-128 divisor of
    ``n`` whose ``[k, tile_n]`` block stays under ``cap_bytes`` (double
    buffered it has to fit the core's 128 MiB beside the row tiles); ``n``
    itself where no such divisor exists (a narrow layer; a published width
    that is no multiple of 128, as an un-gated expert's 1856, is served from a
    leaf that lies the other way: ``grouped_matmul``'s ``transposed``)."""
    best = None
    for cand in range(128, n + 1, 128):
        if n % cand == 0 and k * cand * itemsize <= cap_bytes:
            best = cand
    return best if best is not None else n


def _gmm_kernel(te_ref, nt_ref, x_ref, w_ref, o_ref, *, transposed=False):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) < nt_ref[0])
    def _tile():
        if transposed:          # the weight block lies [N, K]
            prod = jax.lax.dot_general(
                x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            prod = jnp.dot(x_ref[...], w_ref[...],
                           preferred_element_type=jnp.float32)
        o_ref[...] = prod.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "interpret",
                                             "transposed"))
def grouped_matmul(x, w, tile_expert, n_tiles, *, tile_m: int,
                   tile_n: int | None = None, interpret: bool = False,
                   transposed: bool = False):
    """``x [M, K]`` in ``plan_groups``' layout (M a multiple of ``tile_m``),
    ``w [E, K, N]`` -> ``[M, N]`` float32 with rows of tile t holding ``x_t @
    w[tile_expert[t]]`` for ``t < n_tiles`` and junk behind.  ``transposed``:
    the weights lie ``[E, N, K]`` and a tile holds ``x_t @ w[e]^T`` (a leaf
    whose ``N`` is no multiple of 128 lanes lies with ``K`` minor: the other
    way the chip's layout pads every row of it, and the compiler re-lays the
    whole leaf around the call); a weight block is then all ``N`` rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    _, kw, n = w.swapaxes(1, 2).shape if transposed else w.shape
    if kw != k or m % tile_m or tile_expert.shape != (m // tile_m,):
        raise ValueError(f"x {x.shape}, w {w.shape}, tile_m {tile_m} and "
                         f"{tile_expert.shape[0]} tiles do not fit together")
    if transposed:
        tile_n = n
    elif tile_n is None:
        tile_n = pick_tile_n(k, n, w.dtype.itemsize)
    if n % tile_n:
        raise ValueError(f"tile_n ({tile_n}) must divide {n}")

    def used(t, nt):
        return jnp.minimum(t, jnp.maximum(nt[0] - 1, 0))

    if transposed:
        kernel = functools.partial(_gmm_kernel, transposed=True)
        w_spec = pl.BlockSpec((None, n, k),
                              lambda j, t, te, nt: (te[t], 0, 0))
    else:
        kernel = _gmm_kernel
        w_spec = pl.BlockSpec((None, k, tile_n),
                              lambda j, t, te, nt: (te[t], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // tile_n, m // tile_m),
        in_specs=[
            pl.BlockSpec((tile_m, k), lambda j, t, te, nt: (used(t, nt), 0)),
            w_spec,
        ],
        out_specs=pl.BlockSpec((tile_m, tile_n),
                               lambda j, t, te, nt: (used(t, nt), j)),
    )
    w_block = k * tile_n * w.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(110 << 20, 2 * w_block + (24 << 20))),
        interpret=interpret,
        name="grouped_matmul",
    )(tile_expert, n_tiles, x.astype(w.dtype), w)


def reference_grouped_matmul(x, w, padded):
    """XLA oracle on the same layout: group e is the ``padded[e]`` rows
    behind the groups before it, every group's product taken over all rows
    and kept where the rows are its own; rows behind the last group come out
    zero.  (``lax.ragged_dot`` would be shorter, but on a TPU it is a Pallas
    kernel itself, and no oracle of one.)"""
    ends = jnp.cumsum(padded)
    row = jnp.arange(x.shape[0])[:, None]
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for e in range(w.shape[0]):
        mine = (row >= ends[e] - padded[e]) & (row < ends[e])
        out = jnp.where(mine, jnp.matmul(x.astype(w.dtype), w[e],
                                         preferred_element_type=jnp.float32),
                        out)
    return out
