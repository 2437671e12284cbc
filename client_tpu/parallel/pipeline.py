"""Pipeline parallelism: GPipe-style microbatched stages over the ``pp`` axis.

TPU-first design, not a port (the reference — a Triton client fork — has no
parallelism at all, SURVEY.md §2.9): transformer blocks are stacked along a
leading layer axis that is sharded over ``pp`` with ``shard_map``, so every
device holds `n_layers / pp` consecutive blocks (one pipeline stage). A
microbatch loop runs as a single ``lax.scan`` of M + S - 1 ticks; each tick
every stage applies its blocks to its in-flight microbatch and hands the
activation to the next stage with ``lax.ppermute`` — the collective rides
ICI on real hardware. Shapes are static, control flow is compiler-visible,
and the whole schedule differentiates (ppermute/scan transpose), so the same
function serves the forward pass and the pipeline-parallel training step.

The batch dimension is additionally sharded over ``dp`` (a 2D ("dp","pp")
mesh): microbatches are time-multiplexed through the stages while each
microbatch's rows stay data-parallel.
"""

from __future__ import annotations

import functools

import numpy as np

from client_tpu.parallel.training import _attention, _rms_norm


def _init_stacked_params(rng, vocab, d_model, d_ff, n_layers):
    import jax

    keys = jax.random.split(rng, 8)
    scale = 0.02

    def norm(key, shape):
        return jax.random.normal(key, shape) * scale

    return {
        "embed": norm(keys[0], (vocab, d_model)),
        "unembed": norm(keys[1], (d_model, vocab)),
        # blocks stacked on a leading layer axis — sharded over pp
        "wq": norm(keys[2], (n_layers, d_model, d_model)),
        "wk": norm(keys[3], (n_layers, d_model, d_model)),
        "wv": norm(keys[4], (n_layers, d_model, d_model)),
        "wo": norm(keys[5], (n_layers, d_model, d_model)),
        "w1": norm(keys[6], (n_layers, d_model, d_ff)),
        "w2": norm(keys[7], (n_layers, d_ff, d_model)),
    }


def _stacked_specs(P):
    stage = P("pp", None, None)
    return {
        "embed": P(None, None),
        "unembed": P(None, None),
        "wq": stage, "wk": stage, "wv": stage, "wo": stage,
        "w1": stage, "w2": stage,
    }


def _block(lp, x, n_heads, mask):
    """One pre-norm transformer block. lp holds unstacked [D,D]/[D,F] mats."""
    import jax

    x = x + _attention(lp, x, n_heads, mask)
    h = _rms_norm(x)
    return x + jax.nn.gelu(h @ lp["w1"]) @ lp["w2"]


def _stage_fn(stacked, x, n_heads, mask):
    """Apply this stage's local slice of blocks (scan over the layer axis)."""
    from jax import lax

    def body(carry, lp):
        return _block(lp, carry, n_heads, mask), None

    out, _ = lax.scan(body, x, stacked)
    return out


def pipeline_apply(mesh, stacked, x_mb, n_heads, mask):
    """Run [M, mb, S, D] microbatches through pp-sharded stages.

    GPipe schedule as one scan of M + S - 1 ticks: at tick t, stage s holds
    microbatch t - s (when 0 <= t - s < M). Stage 0 reads x_mb[t]; every
    other stage reads what its predecessor ppermuted to it last tick; the
    last stage collects its outputs. The collected buffer is broadcast from
    the last stage with all_gather so the shard_map output is well-defined
    (replicated) on every pp member.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape["pp"]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def run(stacked_local, x_local):
        # stacked_local leaves: [n_layers/pp, ...]; x_local: [M, mb/dp, S, D]
        s = lax.axis_index("pp")
        M = x_local.shape[0]
        ticks = M + n_stages - 1

        def tick(carry, t):
            state, outputs = carry
            x_in = jnp.where(s == 0, x_local[jnp.clip(t, 0, M - 1)], state)
            y = _stage_fn(stacked_local, x_in, n_heads, mask)
            state_next = lax.ppermute(y, "pp", perm)
            idx = t - (n_stages - 1)
            valid = jnp.logical_and(
                s == n_stages - 1,
                jnp.logical_and(idx >= 0, idx < M))
            written = outputs.at[jnp.clip(idx, 0, M - 1)].set(y)
            outputs = jnp.where(valid, written, outputs)
            return (state_next, outputs), None

        init = (jnp.zeros_like(x_local[0]), jnp.zeros_like(x_local))
        (_, outputs), _ = lax.scan(tick, init, jnp.arange(ticks))
        # broadcast the last stage's collected outputs to every pp member
        return lax.all_gather(outputs, "pp")[n_stages - 1]

    from jax import shard_map

    block_spec = jax.tree.map(lambda _: P("pp"), stacked)
    mapped = shard_map(run, mesh=mesh,
                       in_specs=(block_spec, P(None, "dp", None, None)),
                       out_specs=P(None, "dp", None, None),
                       check_vma=False)
    return mapped(stacked, x_mb)


def make_pipeline_train_step(mesh, vocab=256, d_model=64, d_ff=128,
                             n_layers=4, n_heads=4, lr=1e-3):
    """Returns (params, opt_state, train_step, shard_fn) for LM training
    with pp-sharded blocks; embed/unembed replicated outside the pipeline.

    train_step(params, opt, tokens) expects tokens [M, mb, S+1] already
    placed by shard_fn — the microbatch count M and size mb come from the
    tokens shape (mb must divide by the dp axis)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape["pp"]
    if n_layers % n_stages:
        raise ValueError(f"n_layers={n_layers} not divisible by pp={n_stages}")

    params = _init_stacked_params(
        jax.random.PRNGKey(0), vocab, d_model, d_ff, n_layers)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, _stacked_specs(P))
    tx = optax.adamw(lr)
    opt_state = tx.init(params)

    def loss_fn(p, tokens):
        # tokens [M, mb, S+1]
        inp, tgt = tokens[..., :-1], tokens[..., 1:]
        seq = inp.shape[-1]
        mask = jnp.tril(jnp.ones((seq, seq), dtype=bool))
        x = p["embed"][inp]                          # [M, mb, S, D]
        x = pipeline_apply(mesh, {k: p[k] for k in
                                  ("wq", "wk", "wv", "wo", "w1", "w2")},
                           x, n_heads, mask)
        logits = _rms_norm(x) @ p["unembed"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)
        return jnp.mean(nll)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(p, opt, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens)
        updates, opt = tx.update(grads, opt, p)
        p = optax.apply_updates(p, updates)
        return p, opt, loss

    data_sharding = NamedSharding(mesh, P(None, "dp", None))

    def shard_fn(tokens):
        dp = mesh.shape["dp"]
        if tokens.shape[1] % dp:
            raise ValueError(
                f"microbatch size {tokens.shape[1]} must divide by dp ({dp})")
        return jax.device_put(jnp.asarray(tokens, jnp.int32), data_sharding)

    return params, opt_state, train_step, shard_fn


def reference_forward(params, x_mb, n_heads, mask):
    """Sequential (unpipelined) oracle: apply every block in order."""
    n_layers = params["wq"].shape[0]
    x = x_mb
    for i in range(n_layers):
        lp = {k: params[k][i] for k in ("wq", "wk", "wv", "wo", "w1", "w2")}
        x = _block(lp, x, n_heads, mask)
    return x


def dryrun_pipeline_step(n_devices: int, microbatches=4, seq=16) -> None:
    """Build a ("dp","pp") mesh, jit the pipelined train step, run ONE step."""
    import jax
    import numpy as np

    from client_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_devices, axes=("dp", "pp"))
    n_stages = mesh.shape["pp"]
    mb = 2 * mesh.shape["dp"]  # microbatch rows must divide by dp
    params, opt, step, shard_fn = make_pipeline_train_step(
        mesh, n_layers=n_stages * max(1, 4 // n_stages))
    tokens = shard_fn(np.random.default_rng(0).integers(
        0, 256, size=(microbatches, mb, seq + 1)))
    params, opt, loss = step(params, opt, tokens)
    jax.block_until_ready(loss)
    assert np.isfinite(float(loss)), "pipeline step produced non-finite loss"
