"""DLRM embedding-bag model family: ragged CSR lookups + MLPs.

The recommendation-serving workload class ("Dissecting Embedding Bag
Performance in DLRM Inference", PAPERS.md): per request, each of
``num_tables`` sparse features contributes a variable-length *bag* of
embedding-row ids; the model pools each bag (sum), crosses the pooled
vectors with a densified bottom-MLP feature via pairwise dot products,
and scores through a top MLP.  Cost scales with total lookups (nnz), not
batch rows — which is why this backend declares
``padding_axis="lookups"`` and is scheduled by the
:class:`~client_tpu.engine.ragged.RaggedScheduler`.

Wire format (KServe v2 tensors, both frontends):

- ``DENSE``   FP32 ``[dense_dim]`` — batched to ``[B, dense_dim]``;
- ``INDICES`` INT32 ragged ``[total_nnz]`` — all bags' row ids,
  concatenated row-major over ``[B, num_tables]`` bags;
- ``OFFSETS`` INT32 ragged ``[B * num_tables + 1]`` — CSR bag starts
  into ``INDICES`` (``OFFSETS[0] == 0``, last element ``== total_nnz``);
- ``OUTPUT0`` FP32 ``[B, 1]`` — the score.

Execution layout: ``pre_stage`` turns CSR into the static device shapes
(indices padded to the lookup bucket with sentinel segment ids, rows
padded to ``max_batch_size`` so lookups stay the only variable axis).
Tables live stacked (``[num_tables * table_rows, emb_dim]``) in one of
three modes:

- **device** (default): table is a jit param on one device;
- **sharded** (``emb_shards=N``): rows sharded over the ``"emb"`` mesh,
  lookups via :func:`~client_tpu.parallel.emb_shard.sharded_bag_sum`
  (bit-identical to the oracle — table values are 1/256-quantized);
- **host** (``host_tables=True``): table stays host-resident and
  ``pre_stage`` resolves lookups through the arena-budgeted
  :class:`~client_tpu.engine.rowcache.RowCache`; the device only pools
  pre-gathered vectors.
"""

from __future__ import annotations

import numpy as np

from client_tpu.engine.config import (
    DynamicBatchingConfig,
    ModelConfig,
    TensorConfig,
)
from client_tpu.engine.model import ModelBackend
from client_tpu.engine.types import EngineError
from client_tpu.models import register_model


def _init_mlp(rng, units: list[int]):
    """[(w, b)] per layer, modest scale; fp32."""
    out = []
    for d_in, d_out in zip(units, units[1:]):
        w = (rng.standard_normal((d_in, d_out)) / np.sqrt(d_in)).astype(
            np.float32)
        b = np.zeros((d_out,), np.float32)
        out.append((w, b))
    return out


class DlrmBackend(ModelBackend):
    """Sharded EmbeddingBag DLRM (see module docstring)."""

    indices_name = "INDICES"
    offsets_name = "OFFSETS"

    def __init__(self, name: str = "dlrm", num_tables: int = 4,
                 table_rows: int = 64, emb_dim: int = 8, dense_dim: int = 8,
                 max_batch_size: int = 8, max_lookups: int = 128,
                 lookup_buckets: list[int] | None = None,
                 emb_shards: int = 0, combine: str = "psum",
                 host_tables: bool = False, cache_budget_bytes: int = 0,
                 bottom_units: tuple = (16,), top_units: tuple = (16,),
                 seed: int = 0, max_queue_delay_us: int = 200):
        self.num_tables = int(num_tables)
        self.table_rows = int(table_rows)
        self.emb_dim = int(emb_dim)
        self.dense_dim = int(dense_dim)
        self.emb_shards = int(emb_shards)
        self.combine = combine
        self.host_tables = bool(host_tables)
        self.cache_budget_bytes = int(cache_budget_bytes)
        self.config = ModelConfig(
            name=name,
            platform="jax",
            max_batch_size=int(max_batch_size),
            padding_axis="lookups",
            max_lookups=int(max_lookups),
            batch_buckets=(sorted({int(b) for b in lookup_buckets})
                           if lookup_buckets else None),
            input=[
                TensorConfig("DENSE", "FP32", [self.dense_dim]),
                TensorConfig("INDICES", "INT32", [-1], ragged=True),
                TensorConfig("OFFSETS", "INT32", [-1], ragged=True),
            ],
            output=[TensorConfig("OUTPUT0", "FP32", [1])],
            dynamic_batching=DynamicBatchingConfig(
                max_queue_delay_microseconds=int(max_queue_delay_us)),
            instance_count=1,
        )
        rng = np.random.default_rng(seed)
        # 1/256-quantized values sum exactly in fp32 regardless of
        # accumulation order (emb_shard.quantize_table): sharded-vs-oracle
        # parity is bit-identical, and a reload reproduces the same table.
        from client_tpu.parallel.emb_shard import quantize_table

        stacked_rows = self.num_tables * self.table_rows
        if self.emb_shards > 1 and stacked_rows % self.emb_shards:
            # Pad with zero rows (never indexed) to an even row partition.
            stacked_rows += self.emb_shards - stacked_rows % self.emb_shards
        table = np.zeros((stacked_rows, self.emb_dim), np.float32)
        table[: self.num_tables * self.table_rows] = quantize_table(
            rng.standard_normal(
                (self.num_tables * self.table_rows, self.emb_dim)) * 0.5)
        self.table_host = table
        self._bottom = _init_mlp(
            rng, [self.dense_dim, *bottom_units, self.emb_dim])
        n_pairs = (self.num_tables + 1) * self.num_tables // 2
        self._top = _init_mlp(
            rng, [self.emb_dim + n_pairs, *top_units, 1])
        self.row_cache = None
        if self.host_tables:
            from client_tpu.engine.rowcache import RowCache

            self.row_cache = RowCache(self.table_host,
                                      self.cache_budget_bytes)
        self.mesh = None
        if self.emb_shards > 1 and not self.host_tables:
            from client_tpu.parallel.emb_shard import emb_mesh

            self.mesh = emb_mesh(self.emb_shards)

    # -- capacity planning ----------------------------------------------------

    def hbm_reservation_bytes(self) -> int:
        """Per-model memory the placement layer should charge: device-
        resident table bytes (the dominant cost), or the host-mode cache
        budget (staged vectors transit HBM per batch; the cache bound is
        the honest steady-state figure)."""
        if self.host_tables:
            return self.cache_budget_bytes
        return int(self.table_host.nbytes)

    # -- ragged validation (engine.validate_inputs hook) ----------------------

    def validate_ragged(self, inputs: dict, batch: int) -> None:
        cfg = self.config
        idx = inputs.get("INDICES")
        off = inputs.get("OFFSETS")
        if idx is None or off is None:
            return  # missing-input errors are raised by the generic loop
        idx = np.asarray(idx)
        off = np.asarray(off)
        want = batch * self.num_tables + 1
        if off.shape[0] != want:
            raise EngineError(
                f"OFFSETS length {off.shape[0]} != batch({batch}) * "
                f"num_tables({self.num_tables}) + 1 = {want}", 400)
        if off.shape[0] and off[0] != 0:
            raise EngineError("OFFSETS[0] must be 0", 400)
        if np.any(np.diff(off) < 0):
            raise EngineError("OFFSETS must be non-decreasing", 400)
        if off[-1] != idx.shape[0]:
            raise EngineError(
                f"OFFSETS[-1] ({int(off[-1])}) != len(INDICES) "
                f"({idx.shape[0]})", 400)
        if idx.shape[0] > cfg.max_lookups:
            # A single request past the largest lookup bucket cannot be
            # split (the feature interaction couples its bags): reject it
            # like an over-max_batch_size batch.
            raise EngineError(
                f"request carries {idx.shape[0]} lookups, exceeding "
                f"max_lookups {cfg.max_lookups} for '{cfg.name}'", 400)
        if idx.size and (idx.min() < 0 or idx.max() >= self.table_rows):
            raise EngineError(
                f"INDICES out of range [0, {self.table_rows})", 400)

    # -- staging (Model.execute_timed hook) -----------------------------------

    def pre_stage(self, inputs: dict, pad_to: int | None) -> dict:
        """CSR → static device layout.  All padding happens HERE (the
        generic row-pad in ``execute_timed`` is bypassed): lookups pad to
        the bucket with row 0 + sentinel segment id ``Bmax*T`` (masked in
        ``apply``), rows pad to ``max_batch_size`` so the executable sees
        exactly one shape per lookup bucket."""
        dense = np.asarray(inputs["DENSE"], np.float32)
        idx = np.asarray(inputs["INDICES"], np.int64)
        off = np.asarray(inputs["OFFSETS"], np.int64)
        b_max = self.config.max_batch_size
        t = self.num_tables
        nnz = int(idx.shape[0])
        lookups = int(pad_to) if pad_to else nnz
        # Per-lookup bag id (b*T + t, row-major) from the CSR offsets.
        seg = np.repeat(
            np.arange(off.shape[0] - 1, dtype=np.int32),
            np.diff(off).astype(np.int64))
        # Stacked-table global row: each bag's table is its bag id mod T.
        rows = (idx + (seg % t).astype(np.int64)
                * self.table_rows).astype(np.int32)
        if lookups > nnz:
            rows = np.concatenate(
                [rows, np.zeros(lookups - nnz, np.int32)])
            seg = np.concatenate(
                [seg, np.full(lookups - nnz, b_max * t, np.int32)])
        if dense.shape[0] < b_max:
            dense = np.pad(
                dense, [(0, b_max - dense.shape[0]), (0, 0)])
        if self.row_cache is not None:
            # Only the real lookups go through the cache — padding would
            # count row 0 as a hot row and inflate the hit rate. Padded
            # vector slots are zero (masked in apply regardless).
            vectors, _hits = self.row_cache.lookup_counted(rows[:nnz])
            if lookups > nnz:
                vectors = np.concatenate([vectors, np.zeros(
                    (lookups - nnz, self.emb_dim), vectors.dtype)])
            return {"DENSE": dense, "VECTORS": vectors, "SEG_IDS": seg}
        return {"DENSE": dense, "INDICES": rows, "SEG_IDS": seg}

    def synthetic_inputs(self, lookups: int) -> dict:
        """A zero CSR batch with exactly ``lookups`` nnz (one row, bags
        evenly split) — warmup / autotuner bucket compiles."""
        lookups = max(1, int(lookups))
        t = self.num_tables
        counts = np.full(t, lookups // t, np.int64)
        counts[: lookups % t] += 1
        off = np.concatenate(
            [np.zeros(1, np.int64), np.cumsum(counts)]).astype(np.int32)
        return {
            "DENSE": np.zeros((1, self.dense_dim), np.float32),
            "INDICES": np.zeros(lookups, np.int32),
            "OFFSETS": off,
        }

    # -- execution ------------------------------------------------------------

    def make_apply_params(self):
        import jax
        import jax.numpy as jnp

        from client_tpu.parallel.emb_shard import (
            bag_sum_oracle,
            shard_table,
            sharded_bag_sum,
        )

        b_max = self.config.max_batch_size
        t = self.num_tables
        d = self.emb_dim
        num_seg = b_max * t
        iu, ju = np.triu_indices(t + 1, k=1)
        host_mode = self.row_cache is not None
        mesh = self.mesh
        combine = self.combine
        # The Pallas ring combine needs interpret mode off-TPU (the psum
        # combine is a plain XLA collective and runs anywhere).
        from client_tpu.engine.backend_init import pallas_interpret

        interpret = pallas_interpret()

        params = {
            "bottom": [(jax.device_put(w), jax.device_put(b))
                       for w, b in self._bottom],
            "top": [(jax.device_put(w), jax.device_put(b))
                    for w, b in self._top],
        }
        if not host_mode:
            params["table"] = (shard_table(self.table_host, mesh)
                               if mesh is not None
                               else jax.device_put(self.table_host))
            from client_tpu.observability.memory import hbm_census

            hbm_census().tag(self.config.name, "embedding",
                             params["table"])

        def mlp(layers, x):
            for i, (w, b) in enumerate(layers):
                x = x @ w + b
                if i < len(layers) - 1:
                    x = jax.nn.relu(x)
            return x

        def apply(p, inputs):
            seg = inputs["SEG_IDS"]
            if host_mode:
                vecs = inputs["VECTORS"]
                valid = seg < num_seg
                vecs = jnp.where(valid[:, None], vecs, 0.0).astype(
                    vecs.dtype)
                pooled = jax.ops.segment_sum(
                    vecs, jnp.where(valid, seg, 0), num_segments=num_seg)
            elif mesh is not None:
                pooled = sharded_bag_sum(
                    mesh, p["table"], inputs["INDICES"], seg, num_seg,
                    combine=combine, interpret=interpret)
            else:
                pooled = bag_sum_oracle(
                    p["table"], inputs["INDICES"], seg, num_seg)
            pooled = pooled.reshape(b_max, t, d)
            bottom = mlp(p["bottom"], inputs["DENSE"])  # [Bmax, D]
            feats = jnp.concatenate([bottom[:, None, :], pooled], axis=1)
            z = jnp.einsum("bid,bjd->bij", feats, feats)
            inter = z[:, iu, ju]  # upper-triangular pairwise dots
            out = mlp(p["top"], jnp.concatenate([bottom, inter], axis=-1))
            return {"OUTPUT0": out}

        return apply, params


register_model("dlrm")(DlrmBackend)
# Host-table + hot-row-cache variant: the default registered config keeps
# a cache big enough for the hot set of a Zipf workload but far smaller
# than the table, so hit-rate metrics are non-trivial out of the box.
register_model("dlrm_cached", default=False)(
    lambda: DlrmBackend(name="dlrm_cached", host_tables=True,
                        cache_budget_bytes=4096))
