"""Multi-chip *inference*: zoo models sharded over a device mesh and served
through the ordinary engine path.

The reference has no counterpart (its servers are single-process black
boxes); this is the TPU-native promise of the project — the same
``TpuEngine``/scheduler/statistics stack, but the executable is partitioned
over a ``jax.sharding.Mesh``:

- parameters tensor-parallel on ``tp`` (megatron column/row splits for
  attention QKVO and the FFN pair),
- request batches data-parallel on ``dp`` (the scheduler's dynamic batches
  pad to buckets that are multiples of the dp degree),
- activations pinned at layer boundaries with sharding constraints so XLA
  places psum/all-gather collectives on ICI.

The engine needs no special casing: a backend that declares
``input_shardings`` gets its staged inputs ``device_put`` onto the mesh, and
GSPMD propagates everything else (see Model.execute_timed).
"""

from __future__ import annotations

import numpy as np

from client_tpu.engine.model import ModelBackend
from client_tpu.models.bert import BertBackend
from client_tpu.models.generate import TinyGptBackend


def dp_batch_buckets(dp: int, max_batch_size: int) -> tuple[int, list[int]]:
    """(rounded max batch, bucket series): every bucket a dp multiple so
    dynamic batches scatter evenly over the mesh, doubling up to the top."""
    top = ((max_batch_size + dp - 1) // dp) * dp
    buckets, b = [top], dp
    while b < top:
        buckets.append(b)
        b *= 2
    return top, sorted(set(buckets))


from client_tpu.parallel.mesh import drop_absent, make_constrain  # noqa: F401
# (make_constrain is re-exported: the sharded backends' public helper.)


def _served_lm_config(mesh, name, seq_len, vocab, max_batch_size):
    """(ModelConfig, input_shardings) for the token-in/logits-out served LM
    families (MoE, pipelined): INPUT_IDS INT32 [seq] -> LOGITS FP32
    [seq, vocab], dp-multiple batch buckets, batch rows sharded on dp."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from client_tpu.engine.config import (
        DynamicBatchingConfig,
        ModelConfig,
        TensorConfig,
    )

    top, buckets = dp_batch_buckets(int(mesh.shape["dp"]), max_batch_size)
    config = ModelConfig(
        name=name,
        platform="jax",
        max_batch_size=top,
        input=[TensorConfig("INPUT_IDS", "INT32", [seq_len])],
        output=[TensorConfig("LOGITS", "FP32", [seq_len, vocab])],
        dynamic_batching=DynamicBatchingConfig(
            preferred_batch_size=[max(1, top // 2), top],
            max_queue_delay_microseconds=500,
        ),
        instance_count=1,
    )
    config.batch_buckets = buckets
    shardings = {"INPUT_IDS": NamedSharding(mesh, P("dp", None))}
    return config, shardings


def place_with_specs(mesh, params, specs):
    """device_put a param tree with per-leaf PartitionSpecs, nulling
    mesh-absent axes the same way make_constrain does."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def place(x, s):
        s = P(*(drop_absent(mesh, a) for a in s))
        return jax.device_put(x, NamedSharding(mesh, s))

    return jax.tree.map(place, params, specs)


def bert_param_specs(P, n_layers: int):
    """PartitionSpec tree matching BertBackend._init_params.

    Embeddings and layer-norms replicate (small); attention and FFN weights
    split megatron-style over ``tp``: column-parallel into the head/hidden
    dimension, row-parallel back out, so each matmul pair needs exactly one
    psum on ICI.
    """
    def dense_col():  # [in, out] split on out
        return {"w": P(None, "tp"), "b": P("tp")}

    def dense_row():  # [in, out] split on in; output needs the psum
        return {"w": P("tp", None), "b": P()}

    def ln():
        return {"scale": P(), "bias": P()}

    layer = {
        # Fused QKV is column-parallel over its 3h output; bert.py's
        # head-major (b, s, heads, 3, hd) reshape means each tp shard holds
        # complete q/k/v triples for its heads, so the per-head activation
        # constraint matches the matmul's output sharding (no reshard).
        "wqkv": dense_col(),
        "wo": dense_row(),
        "ln1": ln(),
        "w1": dense_col(), "w2": dense_row(),
        "ln2": ln(),
    }
    return {
        "tok_embed": P(),
        "pos_embed": P(),
        "embed_ln": ln(),
        "layers": [dict(layer) for _ in range(n_layers)],
        "pooler": {"w": P(), "b": P()},
        "classifier": {"w": P(), "b": P()},
    }


class ShardedBertBackend(BertBackend):
    """BERT-base partitioned over a (dp, tp) mesh for serving.

    ``mesh`` defaults to all visible devices. Batch buckets are multiples of
    the dp degree so every dynamic batch shards evenly.
    """

    def __init__(self, mesh=None, name: str = "bert_base_mc",
                 max_batch_size: int = 16, **kw):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from client_tpu.parallel.mesh import make_mesh

        if mesh is None:
            mesh = make_mesh(axes=("dp", "tp"))
        self.mesh = mesh
        super().__init__(name=name, max_batch_size=max_batch_size, **kw)
        # Every bucket (including the top one) must be a dp multiple or the
        # batch device_put can't scatter evenly over the mesh.
        top, buckets = dp_batch_buckets(int(mesh.shape["dp"]),
                                        max_batch_size)
        self.config.max_batch_size = top
        self.config.batch_buckets = buckets
        # Computed once: Model.execute_timed reads this per batch on the
        # latency path.
        batch_spec = NamedSharding(mesh, P("dp", None))
        self.input_shardings = {"input_ids": batch_spec,
                                "attention_mask": batch_spec}

    def place_params(self, params):
        import numpy as np
        from jax.sharding import PartitionSpec as P

        specs = bert_param_specs(P, self.n_layers)

        # Canonical wqkv storage is qkv-major ([q | k | v] column blocks,
        # the fast single-device layout); the sharded apply reads the fused
        # output head-major so tp column splits land whole heads per shard.
        # Permuting the columns here keeps both modes the *same function* of
        # one canonical checkpoint — layout is purely a placement detail.
        h, hd = self.hidden, self.hidden // self.n_heads
        perm = np.empty(3 * h, dtype=np.int64)
        for i in range(3 * h):
            head, rem = divmod(i, 3 * hd)
            which, d = divmod(rem, hd)
            perm[i] = which * h + head * hd + d
        for lp in params["layers"]:
            lp["wqkv"]["w"] = np.asarray(lp["wqkv"]["w"])[:, perm]
            lp["wqkv"]["b"] = np.asarray(lp["wqkv"]["b"])[perm]

        return place_with_specs(self.mesh, params, specs)

    def make_apply_params(self):
        constrain = make_constrain(self.mesh)
        return (self._build_apply(constrain=constrain, head_major=True),
                self.place_params(self.load_or_init_params(self._init_params)))


# Zoo registration: opt-in (default=False) — a default load-all server
# should not pay a second full BERT-base load; reach it explicitly via
# build_repository(["bert_base_mc"]) or `--zoo bert_base_mc`.
from client_tpu.models import register_model  # noqa: E402

register_model("bert_base_mc", default=False)(ShardedBertBackend)


class LongContextBertBackend(BertBackend):
    """Long-context BERT served sequence-parallel over a ("dp", "sp") mesh.

    The sequence axis of every activation is sharded over "sp"; attention is
    exact ring attention (client_tpu.parallel.ring_attention): K/V shards
    rotate via ppermute on ICI while each device folds visiting blocks into
    a flash-style online softmax — no [S, S] score tensor, no single-device
    sequence residency. Parameters replicate (BERT-base fits one chip); for
    larger models compose with the tp splits above.
    """

    def __init__(self, mesh=None, name: str = "bert_long_mc",
                 seq_len: int = 2048, max_batch_size: int = 4, **kw):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from client_tpu.parallel.mesh import make_mesh

        if mesh is None:
            mesh = make_mesh(axes=("dp", "sp"))
        self.mesh = mesh
        sp = int(mesh.shape["sp"])
        if seq_len % sp:
            raise ValueError(
                f"seq_len {seq_len} must be a multiple of the sp mesh "
                f"axis ({sp})")
        super().__init__(name=name, seq_len=seq_len,
                         max_batch_size=max_batch_size, **kw)
        top, buckets = dp_batch_buckets(int(mesh.shape["dp"]),
                                        max_batch_size)
        self.config.max_batch_size = top
        self.config.batch_buckets = buckets
        seq_spec = NamedSharding(mesh, P("dp", "sp"))
        self.input_shardings = {"input_ids": seq_spec,
                                "attention_mask": seq_spec}

    def place_params(self, params):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        # Replicated across the mesh (sequence parallelism shards
        # activations, not weights).
        return jax.device_put(params, NamedSharding(self.mesh, P()))

    def make_attend(self, head_dim):
        from client_tpu.parallel.ring_attention import (
            sequence_parallel_attention,
        )

        mesh = self.mesh

        def attend(q, k, v, bias2d):
            return sequence_parallel_attention(mesh, q, k, v, bias2d,
                                               axis_name="sp")

        return attend

    def make_apply_params(self):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh

        def constrain(x, spec):
            # Pin the sequence axis (position 1) to "sp"; ignore tp hints
            # (this mesh doesn't carry tp — weights replicate).
            out = ["dp" if a == "dp" else None for a in spec]
            if len(out) >= 2:
                out[1] = "sp"
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*out)))

        return (self._build_apply(constrain=constrain),
                self.place_params(self.load_or_init_params(self._init_params)))


register_model("bert_long_mc", default=False)(LongContextBertBackend)


class ShardedTinyGptBackend(TinyGptBackend):
    """tiny_gpt tensor-parallel over a ``tp`` mesh axis for generative
    serving: attention/FFN weights column/row-split over tp, and the KV
    arena sharded on its heads axis — the GenerativeScheduler's
    prefill/decode programs are unchanged (GSPMD inserts the collectives).

    Requires ``n_heads`` divisible by the tp degree so column splits land
    whole heads per shard.
    """

    def __init__(self, mesh=None, name: str = "tiny_gpt_mc",
                 n_heads: int = 8, **kw):
        from client_tpu.parallel.mesh import make_mesh

        if mesh is None:
            mesh = make_mesh(axes=("tp",))
        self.mesh = mesh
        # GSPMD partitions the XLA decode step over the mesh; a Mosaic
        # custom call has no partitioning rule, so the tp families serve
        # the scatter/gather step, not the single-chip kernel.
        kw.setdefault("attn_impl", "reference")
        super().__init__(name=name, n_heads=n_heads, **kw)
        tp = int(mesh.shape["tp"])
        if self.n_heads % tp:
            raise ValueError(
                f"n_heads ({self.n_heads}) must divide by tp ({tp})")

    def _param_specs(self, P):
        layer = {
            "ln1g": P(), "ln1b": P(),
            "wq": P(None, "tp"), "wk": P(None, "tp"), "wv": P(None, "tp"),
            "wo": P("tp", None),
            "ln2g": P(), "ln2b": P(),
            "w1": P(None, "tp"), "w2": P("tp", None),
        }
        return {
            "embed": P(), "pos": P(),
            "layers": [dict(layer) for _ in range(self.n_layers)],
            "lnfg": P(), "lnfb": P(), "head": P(),
        }

    def place_params(self, params):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, self._param_specs(P))

    def init_arena(self, capacity: int):
        return _place_arena_heads_sharded(self.mesh,
                                          super().init_arena(capacity))


def _place_arena_heads_sharded(mesh, arena):
    """KV-arena placement shared by the sharded generative families:
    k/v [L, cap+1, S, H*D] shard their feature axis with the tp weight
    splits, whole heads per shard (dropped when the mesh has no tp); the
    per-row token slots and any other small plane replicate (tiny, read by
    every shard)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    kv = NamedSharding(mesh, P(None, None, None, drop_absent(mesh, "tp")))
    rep = NamedSharding(mesh, P())
    return {name: jax.device_put(a, kv if a.ndim == 4 else rep)
            for name, a in arena.items()}


register_model("tiny_gpt_mc", default=False)(ShardedTinyGptBackend)


class MoeGptBackend(TinyGptBackend):
    """Expert-parallel generative decode: a Switch-MoE decoder LM in the
    continuous-batching arena (GenerativeScheduler) over an ("ep","tp")
    mesh.

    Every decode wave routes its B tokens top-1 through an expert FFN stack
    sharded over ``ep`` (attention heads and expert hidden over ``tp``);
    the KV arena, prefill/decode programs, pipelined dispatch, and the
    decoupled token-stream protocol are inherited from TinyGptBackend
    unchanged — only the position-wise FFN hook differs.  The dispatch/
    combine one-hot einsums reshard token-major -> expert-major, which
    GSPMD lowers to all-to-all-style collectives on ICI (no explicit
    constraints needed: propagation from the [E,...] weight shardings pins
    the expert-major intermediates to ep).

    Routing is **dropless**: per-expert queue capacity equals the token
    count (worst case every token picks one expert), so no token ever
    overflows onto the residual path.  That keeps each token's output a
    pure function of its own features — decode stays batch-invariant and
    bit-identical to solo decode, the arena contract every served
    generative family must honor (unlike the capacity-dropping `moe_lm_mc`
    forward family, which documents its variance).  The cost is the dense
    [T, E, T] dispatch tensor — the exact one-hot Switch formulation,
    fine at decode-wave sizes (T <= max_streams); a ragged/sorted Pallas
    dispatch is the scale-up path, not a semantic change.

    Reference anchor: the decoupled streaming contract this family serves
    through (/root/reference/src/python/examples/
    simple_grpc_custom_repeat.py); the reference has no parallelism or
    generative scheduler (SURVEY.md §2.9).
    """

    def __init__(self, mesh=None, name: str = "moe_gpt_mc",
                 n_layers: int = 2, d_model: int = 128, n_heads: int = 4,
                 d_ff: int = 256, vocab: int = 256, max_seq_len: int = 64,
                 max_streams: int = 32, n_experts: int | None = None,
                 weights_path: str | None = None, **kw):
        from client_tpu.parallel.mesh import make_mesh
        from client_tpu.parallel.moe import default_n_experts

        if mesh is None:
            mesh = make_mesh(axes=("ep", "tp"))
        self.mesh = mesh
        self.n_experts = n_experts or default_n_experts(mesh)
        ep = int(mesh.shape.get("ep", 1))
        tp = int(mesh.shape.get("tp", 1))
        if self.n_experts % ep:
            raise ValueError(
                f"n_experts ({self.n_experts}) must divide by ep ({ep})")
        if n_heads % tp:
            raise ValueError(
                f"n_heads ({n_heads}) must divide by tp ({tp})")
        if d_ff % tp:
            raise ValueError(f"d_ff ({d_ff}) must divide by tp ({tp})")
        # As ShardedTinyGptBackend: GSPMD partitions the XLA decode step.
        kw.setdefault("attn_impl", "reference")
        super().__init__(name=name, n_layers=n_layers, d_model=d_model,
                         n_heads=n_heads, d_ff=d_ff, vocab=vocab,
                         max_seq_len=max_seq_len, max_streams=max_streams,
                         **kw)
        self.weights_path = weights_path

    def _init_params(self):
        """Base init with each layer's dense FFN pair swapped for the
        routed expert stacks (same scale conventions: 1/sqrt(fan_in))."""
        import math as _math

        params = super()._init_params()
        rng = np.random.default_rng(self._seed + 1)
        d, f, E = self.d_model, self.d_ff, self.n_experts

        def w(*shape, scale):
            return (rng.standard_normal(shape) * scale).astype(np.float32)

        for lp in params["layers"]:
            del lp["w1"], lp["w2"]
            lp["router"] = w(d, E, scale=0.02)
            lp["w1e"] = w(E, d, f, scale=1.0 / _math.sqrt(d))
            lp["w2e"] = w(E, f, d, scale=1.0 / _math.sqrt(f))
        return params

    def _ffn(self, lp, h):
        """Dropless top-1 Switch FFN on [T, d] rows (both prefill's
        per-row stack under vmap and the decode wave's [B, d] call):
        ``moe_ffn`` with capacity == T — every token's queue position is
        < T, so ``keep == onehot`` and nothing ever drops; one shared
        routing implementation for training, forward serving, and decode."""
        from client_tpu.parallel.moe import moe_ffn

        y, _aux = moe_ffn(h[None], lp["router"], lp["w1e"], lp["w2e"],
                          capacity=h.shape[0])
        return y[0]

    def _param_specs(self, P):
        layer = {
            "ln1g": P(), "ln1b": P(),
            "wq": P(None, "tp"), "wk": P(None, "tp"), "wv": P(None, "tp"),
            "wo": P("tp", None),
            "ln2g": P(), "ln2b": P(),
            "router": P(),
            "w1e": P("ep", None, "tp"),
            "w2e": P("ep", "tp", None),
        }
        return {
            "embed": P(), "pos": P(),
            "layers": [dict(layer) for _ in range(self.n_layers)],
            "lnfg": P(), "lnfb": P(), "head": P(),
        }

    def place_params(self, params):
        from jax.sharding import PartitionSpec as P

        return place_with_specs(self.mesh, params, self._param_specs(P))

    def init_arena(self, capacity: int):
        return _place_arena_heads_sharded(self.mesh,
                                          super().init_arena(capacity))


register_model("moe_gpt_mc", default=False)(MoeGptBackend)


class MoeLmBackend(ModelBackend):
    """Switch-MoE language model served over a ("dp","ep","tp") mesh.

    Per-token next-token logits from the MoE transformer forward
    (client_tpu.parallel.moe): expert FFN stacks sharded over ``ep``
    (hidden over ``tp``), batch over ``dp``; the one-hot dispatch/combine
    einsums reshard token-major -> expert-major, which XLA lowers to
    all-to-all-style collectives on ICI. Expert capacity is derived from
    the compiled bucket's token count (ceil(tokens / E * capacity_factor)),
    so overflow drops are per-batch — standard Switch semantics: a token
    past its expert's queue rides the residual path.

    NOT batch-invariant, unlike every other served family: which tokens
    overflow depends on the co-batched tokens ahead of them in the
    dispatch queue and on the bucket the dynamic batcher picks, so a
    request's logits can differ between solo and co-batched service.
    This is inherent to capacity-based MoE routing (the reference point
    is Switch/GShard, not this framework); serve with
    ``dynamic_batching=None`` if per-request determinism matters more
    than throughput.
    """

    def __init__(self, mesh=None, name: str = "moe_lm_mc", seq_len: int = 32,
                 d_model: int = 64, d_ff: int = 128, n_layers: int = 2,
                 n_heads: int = 4, n_experts: int | None = None,
                 capacity_factor: float = 1.25, vocab: int = 256,
                 max_batch_size: int = 8,
                 weights_path: str | None = None):
        from client_tpu.parallel.mesh import make_mesh

        if mesh is None:
            mesh = make_mesh(axes=("dp", "ep", "tp"))
        self.mesh = mesh
        self.weights_path = weights_path
        self.seq_len = seq_len
        self.d_model = d_model
        self.d_ff = d_ff
        self.n_layers = n_layers
        self.n_heads = n_heads
        from client_tpu.parallel.moe import default_n_experts

        self.n_experts = n_experts or default_n_experts(mesh)
        ep = int(mesh.shape.get("ep", 1))
        if self.n_experts % ep:
            raise ValueError(
                f"n_experts ({self.n_experts}) must divide by ep ({ep})")
        tp = int(mesh.shape.get("tp", 1))
        if d_ff % tp:
            raise ValueError(f"d_ff ({d_ff}) must divide by tp ({tp})")
        if d_model % n_heads:
            raise ValueError(
                f"d_model ({d_model}) must divide by n_heads ({n_heads})")
        self.capacity_factor = capacity_factor
        self.vocab = vocab
        self.config, self.input_shardings = _served_lm_config(
            mesh, name, seq_len, vocab, max_batch_size)

    def _init_params(self):
        import jax

        from client_tpu.parallel.moe import _init_moe_params

        return _init_moe_params(jax.random.PRNGKey(0), self.vocab,
                                self.d_model, self.d_ff, self.n_layers,
                                self.n_experts)

    def place_params(self, params):
        from jax.sharding import PartitionSpec as P

        from client_tpu.parallel.moe import _moe_specs

        return place_with_specs(self.mesh, params,
                                _moe_specs(P, self.n_layers))

    def make_apply_params(self):
        import numpy as np

        from client_tpu.parallel.moe import _moe_forward

        n_heads, n_experts = self.n_heads, self.n_experts
        cf = self.capacity_factor
        constrain = make_constrain(self.mesh)

        def apply(params, inputs):
            tokens = inputs["INPUT_IDS"]
            B, S = tokens.shape  # static per compiled bucket
            capacity = int(np.ceil(B * S / n_experts * cf))
            logits, _aux = _moe_forward(params, tokens, n_heads, capacity,
                                        constrain)
            return {"LOGITS": logits.astype("float32")}

        return apply, self.place_params(
            self.load_or_init_params(self._init_params))


register_model("moe_lm_mc", default=False)(MoeLmBackend)


class PipelinedLmBackend(ModelBackend):
    """Transformer LM served with its blocks pipeline-parallel over ``pp``.

    Each device row holds a contiguous slice of layers (a pipeline stage);
    a served batch flows through the stages as one microbatch via the same
    shard_map + ppermute schedule the training step uses
    (client_tpu.parallel.pipeline.pipeline_apply with M=1 — handoffs ride
    ICI). Embed/unembed replicate. The per-request latency is the sum of
    stage times (a pipeline helps model *capacity*, not solo latency);
    dynamic batching rides inside the single microbatch.
    """

    def __init__(self, mesh=None, name: str = "pipelined_lm_mc",
                 seq_len: int = 32, d_model: int = 64, d_ff: int = 128,
                 n_layers: int | None = None, n_heads: int = 4,
                 vocab: int = 256, max_batch_size: int = 8,
                 weights_path: str | None = None):
        from client_tpu.parallel.mesh import make_mesh

        if mesh is None:
            mesh = make_mesh(axes=("dp", "pp"))
        if "pp" not in mesh.shape:
            raise ValueError(
                "PipelinedLmBackend requires a mesh with a 'pp' axis; got "
                f"axes {tuple(mesh.shape)}")
        self.mesh = mesh
        self.weights_path = weights_path
        pp = int(mesh.shape["pp"])
        if n_layers is None:
            n_layers = pp * max(1, 4 // pp)
        if n_layers % pp:
            raise ValueError(
                f"n_layers ({n_layers}) must divide by pp ({pp})")
        if d_model % n_heads:
            raise ValueError(
                f"d_model ({d_model}) must divide by n_heads ({n_heads})")
        self.seq_len = seq_len
        self.d_model = d_model
        self.d_ff = d_ff
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.vocab = vocab
        self.config, self.input_shardings = _served_lm_config(
            mesh, name, seq_len, vocab, max_batch_size)

    def _init_params(self):
        import jax

        from client_tpu.parallel.pipeline import _init_stacked_params

        return _init_stacked_params(jax.random.PRNGKey(0), self.vocab,
                                    self.d_model, self.d_ff, self.n_layers)

    def place_params(self, params):
        from jax.sharding import PartitionSpec as P

        from client_tpu.parallel.pipeline import _stacked_specs

        return place_with_specs(self.mesh, params, _stacked_specs(P))

    def make_apply_params(self):
        import jax.numpy as jnp

        from client_tpu.parallel.pipeline import pipeline_apply
        from client_tpu.parallel.training import _rms_norm

        mesh = self.mesh
        n_heads = self.n_heads
        block_keys = ("wq", "wk", "wv", "wo", "w1", "w2")

        def apply(params, inputs):
            tokens = inputs["INPUT_IDS"]
            seq = tokens.shape[-1]
            mask = jnp.tril(jnp.ones((seq, seq), dtype=bool))
            x = params["embed"][tokens][None]        # [M=1, B, S, D]
            x = pipeline_apply(mesh, {k: params[k] for k in block_keys},
                               x, n_heads, mask)[0]
            logits = _rms_norm(x) @ params["unembed"]
            return {"LOGITS": logits.astype("float32")}

        return apply, self.place_params(
            self.load_or_init_params(self._init_params))


register_model("pipelined_lm_mc", default=False)(PipelinedLmBackend)
