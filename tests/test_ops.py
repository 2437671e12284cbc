"""Pallas kernels and parallel attention ops.

The flash kernel runs in interpreter mode on CPU (same kernel code the TPU
compiles); ring attention runs on the 8-virtual-device mesh. Oracles are
the XLA-scheduled dense attention.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from client_tpu.ops.flash_attention import (
    flash_attention,
    reference_attention,
)
from client_tpu.parallel.mesh import make_mesh
from client_tpu.parallel.ring_attention import sequence_parallel_attention


def _qkv(b, s, h, d, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    return [jax.random.normal(k, (b, s, h, d), dtype) for k in keys]


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    b, s, h, d = 2, 256, 4, 64
    q, k, v = _qkv(b, s, h, d)
    bias = np.zeros((b, s), np.float32)
    bias[:, -37:] = -1e9  # padding mask tail
    bias = jnp.asarray(bias)
    out = flash_attention(q, k, v, bias, causal=causal, interpret=True)
    ref = reference_attention(q, k, v, bias, causal=causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_flash_no_bias_and_blocks():
    b, s, h, d = 1, 512, 2, 32
    q, k, v = _qkv(b, s, h, d)
    out = flash_attention(q, k, v, None, block_q=128, block_k=256,
                          interpret=True)
    ref = reference_attention(q, k, v, None)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_flash_fully_masked_rows_finite():
    """All keys masked → zero output, not NaN (online-softmax guard)."""
    b, s, h, d = 1, 128, 2, 32
    q, k, v = _qkv(b, s, h, d)
    bias = jnp.full((b, s), -1e9, jnp.float32)
    out = flash_attention(q, k, v, bias, interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_flash_rejects_indivisible_seq():
    q, k, v = _qkv(1, 96, 2, 32)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, v, None, block_q=128, block_k=64,
                        interpret=True)


def test_ring_attention_matches_dense():
    mesh = make_mesh(8, axes=("dp", "sp"))
    b, s, h, d = 4, 256, 4, 32
    q, k, v = _qkv(b, s, h, d)
    bias = np.zeros((b, s), np.float32)
    bias[:, -29:] = -1e9
    bias = jnp.asarray(bias)
    out = sequence_parallel_attention(mesh, q, k, v, bias)
    ref = reference_attention(q, k, v, bias)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_ring_attention_sp_only_mesh():
    mesh = make_mesh(8, axes=("sp",))
    b, s, h, d = 2, 128, 2, 16
    q, k, v = _qkv(b, s, h, d)
    bias = jnp.zeros((b, s), jnp.float32)
    out = sequence_parallel_attention(mesh, q, k, v, bias)
    ref = reference_attention(q, k, v, bias)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_bert_flash_impl_matches_einsum():
    """BertBackend(attention_impl='flash') — the bert_long path — matches
    the einsum implementation (interpret mode runs the same kernel)."""
    from client_tpu.models.bert import BertBackend

    kw = dict(seq_len=64, hidden=64, n_layers=2, n_heads=4, ffn=128,
              vocab=512, max_batch_size=2)
    outs = {}
    for impl in ("einsum", "flash"):
        backend = BertBackend(name=f"b_{impl}", attention_impl=impl, **kw)
        fn, params = backend.make_apply_params()
        rng = np.random.default_rng(5)
        inputs = {
            "input_ids": rng.integers(0, 512, (2, 64)).astype(np.int32),
            "attention_mask": np.ones((2, 64), np.int32),
        }
        inputs["attention_mask"][:, -11:] = 0
        outs[impl] = np.asarray(fn(params, inputs)["logits"])
    assert np.allclose(outs["einsum"], outs["flash"], atol=2e-2)  # bf16


def test_long_context_bert_through_engine():
    """Sequence-parallel BERT infers through the full engine path and
    matches the single-device model (same canonical weights)."""
    from client_tpu.engine import InferRequest, TpuEngine
    from client_tpu.engine.repository import ModelRepository
    from client_tpu.models.bert import BertBackend
    from client_tpu.parallel.serving import LongContextBertBackend

    mesh = make_mesh(8, axes=("dp", "sp"))
    kw = dict(seq_len=64, hidden=64, n_layers=2, n_heads=4, ffn=128,
              vocab=512)
    repo = ModelRepository()
    repo.register_backend(
        LongContextBertBackend(mesh, name="bert_sp", max_batch_size=4, **kw))
    repo.register_backend(BertBackend(name="bert_ref", max_batch_size=4,
                                      **kw))
    engine = TpuEngine(repo)
    try:
        rng = np.random.default_rng(3)
        ids = rng.integers(0, 512, (2, 64)).astype(np.int32)
        mask = np.ones((2, 64), np.int32)
        mask[:, -9:] = 0

        def req(m):
            return InferRequest(
                model_name=m,
                inputs={"input_ids": ids, "attention_mask": mask})

        out_sp = engine.infer(req("bert_sp"), timeout_s=300).outputs["logits"]
        out_ref = engine.infer(req("bert_ref"),
                               timeout_s=300).outputs["logits"]
        assert float(np.max(np.abs(out_sp - out_ref))) < 2e-2  # bf16
    finally:
        engine.shutdown()


# -- fused decode-wave kernel (ops/decode_kernel.py) ---------------------------


from client_tpu.ops.decode_kernel import (  # noqa: E402
    copied_rows,
    decode_wave_attention,
    pick_block_s,
    reference_decode_attention,
    row_group,
    tail_quantum,
    wave_block_rows,
    window_wave_attention,
)
from client_tpu.parallel.kv_shard import (  # noqa: E402
    arena_row_layout,
    kv_mesh,
    ring_all_reduce,
    sharded_decode_attention,
)


def _decode_case(layers=2, rows=5, s=32, h=2, d=16, bsz=4, seed=0):
    """A populated arena + one wave of lane inputs. Lane 3 is a padded
    lane parked on the dummy row (len 0) like the scheduler pads waves."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    k_arena = jax.random.normal(ks[0], (layers, rows, s, h * d))
    v_arena = jax.random.normal(ks[1], (layers, rows, s, h * d))
    q = jax.random.normal(ks[2], (bsz, h, d))
    kn = jax.random.normal(ks[3], (bsz, h, d))
    vn = jax.random.normal(ks[4], (bsz, h, d))
    rows_ix = jnp.asarray([0, 2, 1, rows - 1], jnp.int32)[:bsz]
    lens = jnp.asarray([7, 0, s - 1, 0], jnp.int32)[:bsz]
    return k_arena, v_arena, q, kn, vn, rows_ix, lens


class TestFusedDecodeKernel:
    @pytest.mark.parametrize("block_s", [8, 16, 32])
    def test_matches_reference_across_blocks(self, block_s):
        k_a, v_a, q, kn, vn, rows, lens = _decode_case()
        for layer in (0, 1):
            fk, fv, fo = decode_wave_attention(
                k_a, v_a, q, kn, vn, rows, lens, layer=layer,
                block_s=block_s, interpret=True)
            rk, rv, ro = reference_decode_attention(
                k_a, v_a, q, kn, vn, rows, lens, layer=layer)
            # Real lanes' outputs agree; padded lanes (dummy row, len 0)
            # are junk in both impls and are discarded by the scheduler.
            live = np.asarray(lens) > 0
            live[0] = True  # len 7 lane
            assert float(jnp.max(jnp.abs(fo[live] - ro[live]))) < 2e-5
            # The scatter itself is exact on every real row the wave
            # touched (the arena IS the model state; bitwise matters).
            for b in (0, 2):
                r, ln = int(rows[b]), int(lens[b])
                np.testing.assert_array_equal(
                    np.asarray(fk[layer, r, ln]), np.asarray(rk[layer, r, ln]))
                np.testing.assert_array_equal(
                    np.asarray(fv[layer, r, ln]), np.asarray(rv[layer, r, ln]))

    # Slots of 32 rows in blocks of 8 (one quantum a block), and of 64 rows
    # in blocks of 32 whose last is copied in quanta of 8: lengths 0, 1,
    # around a quantum (7, 8, 9), around a block (31, 32, 33) and ``S - 1``.
    @pytest.mark.parametrize("s,block_s,length", [
        (32, 8, n) for n in (0, 1, 7, 8, 15, 31)] + [
        (64, 32, n) for n in (0, 1, 7, 8, 9, 31, 32, 33, 63)])
    def test_every_prefix_length(self, s, block_s, length):
        """Scatter offset and strict mask at quantum and block boundaries
        and the edges (empty prefix, full arena row)."""
        k_a, v_a, q, kn, vn, _, _ = _decode_case(bsz=1, s=s)
        rows = jnp.asarray([1], jnp.int32)
        lens = jnp.asarray([length], jnp.int32)
        fk, fv, fo = decode_wave_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=0, block_s=block_s,
            interpret=True)
        rk, rv, ro = reference_decode_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=0)
        assert float(jnp.max(jnp.abs(fo - ro))) < 2e-5
        np.testing.assert_array_equal(np.asarray(fk[0, 1]),
                                      np.asarray(rk[0, 1]))
        np.testing.assert_array_equal(np.asarray(fv[0, 1]),
                                      np.asarray(rv[0, 1]))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("length", [1, 8, 9, 24, 33, 47, 63])
    def test_copies_cover_copied_rows_and_no_row_more(self, length, dtype):
        """``copied_rows`` is what the kernel's copies cover: values behind
        it may be NaN and the output does not see them (they were never in
        VMEM); a NaN in the last row it counts, where that row is dead,
        reaches the output through ``p = 0`` times it.  The interpreter
        hands the kernel scratch buffers full of NaN, as a chip hands it
        anything: rows of a place no copy filled are part of every case."""
        k_a, v_a, q, kn, vn, _, _ = _decode_case(bsz=2, s=64, d=64)
        k_a, v_a = k_a.astype(dtype), v_a.astype(dtype)
        rows = jnp.asarray([1, 3], jnp.int32)
        lens = jnp.asarray([length, 5], jnp.int32)
        group = row_group(dtype)
        assert tail_quantum(32, group) == group
        copied = copied_rows(length, 64, 128, dtype, 32)
        assert copied == -(-length // group) * group
        _, _, want = reference_decode_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=0)
        # Not the written row's group: that one is copied apart, and back.
        behind = max(copied, (length // group + 1) * group)
        poisoned = v_a.at[0, 1, behind:].set(jnp.nan)
        _, fv, fo = decode_wave_attention(
            k_a, poisoned, q, kn, vn, rows, lens, layer=0, block_s=32,
            interpret=True)
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        assert float(jnp.max(jnp.abs(fo - want))) < tol
        assert bool(jnp.all(jnp.isnan(fv[0, 1, behind:])))
        if copied > length + 1:
            poisoned = v_a.at[0, 1, copied - 1].set(jnp.nan)
            _, _, fo = decode_wave_attention(
                k_a, poisoned, q, kn, vn, rows, lens, layer=0, block_s=32,
                interpret=True)
            assert bool(jnp.any(jnp.isnan(fo[0])))
            assert bool(jnp.all(jnp.isfinite(fo[1])))

    def test_copied_rows_at_the_served_shapes(self):
        """A block of at most 1 MiB a leaf, its last in sixteenths or row
        groups: the three served leaves."""
        served = {  # slot rows, row width, dtype: block, quantum
            (4096, 4096, jnp.bfloat16): (128, 16),      # evabyte_6b5
            (1024, 768, jnp.float32): (256, 16),        # gpt2_small
            (16384, 512, jnp.bfloat16): (1024, 64),     # smallthinker, global
            (4096, 512, jnp.bfloat16): (1024, 64),      # its rings
        }
        for (s, w, dtype), (block, quantum) in served.items():
            assert wave_block_rows(s, w, dtype) == block
            assert tail_quantum(block, row_group(dtype)) == quantum
            assert copied_rows(0, s, w, dtype) == 0
            assert copied_rows(1, s, w, dtype) == quantum
            assert copied_rows(block, s, w, dtype) == block
            assert copied_rows(block + 1, s, w, dtype) == block + quantum
            assert copied_rows(s - 1, s, w, dtype) == s
            # A ring's live rows are the slot's at most.
            assert copied_rows(3 * s + 5, s, w, dtype) == s
        assert copied_rows(830, 1024, 768, jnp.float32) == 832
        assert copied_rows(2061, 4096, 4096, jnp.bfloat16) == 2064
        # A block that is no whole row groups is copied whole.
        assert tail_quantum(8, 16) == 8
        assert copied_rows(3, 32, 32, jnp.bfloat16, 8) == 8

    def test_untouched_rows_survive_aliasing(self):
        """input_output_aliases updates in place: rows no lane points at
        must come through bit-identical."""
        k_a, v_a, q, kn, vn, rows, lens = _decode_case(rows=6)
        before = np.asarray(k_a[0, 3]).copy()
        fk, _, _ = decode_wave_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=0, block_s=8,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(fk[0, 3]), before)

    def test_outputs_finite_for_padded_lanes(self):
        """len==0 lanes (dummy row) must produce finite output (the new
        token is always a valid attention target), never NaN."""
        k_a, v_a, q, kn, vn, _, _ = _decode_case(bsz=2)
        rows = jnp.asarray([4, 4], jnp.int32)
        lens = jnp.asarray([0, 0], jnp.int32)
        _, _, o = decode_wave_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=0, interpret=True)
        assert bool(jnp.all(jnp.isfinite(o)))

    def test_pick_block_s(self):
        assert pick_block_s(32) == 32
        assert pick_block_s(256) == 256
        assert pick_block_s(1024) == 512
        assert pick_block_s(256, cap=64) == 64
        assert pick_block_s(24) == 24
        assert pick_block_s(7) == 7  # no aligned divisor: whole row

    def test_block_s_must_divide(self):
        k_a, v_a, q, kn, vn, rows, lens = _decode_case()
        with pytest.raises(ValueError, match="divide"):
            decode_wave_attention(k_a, v_a, q, kn, vn, rows, lens,
                                  layer=0, block_s=24, interpret=True)


class TestDecodeKernelByDtype:
    """One kernel, two dtypes (ISSUE 28): float32 arenas (GPT-2) and
    bfloat16 arenas (a 16-row HBM tile, blocks to the MXU in one pass),
    with the layer static or taken by scalar prefetch (a decoder that scans
    over its layers)."""

    @staticmethod
    def _case(dtype, s=64, h=4, d=32):
        k_a, v_a, q, kn, vn, rows, _ = _decode_case(s=s, h=h, d=d)
        lens = jnp.asarray([7, 16, s - 1, 0], jnp.int32)
        return k_a.astype(dtype), v_a.astype(dtype), q, kn, vn, rows, lens

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                          (jnp.bfloat16, 3e-2)])
    @pytest.mark.parametrize("dynamic", [False, True])
    def test_parity_with_the_xla_oracle(self, dtype, tol, dynamic):
        k_a, v_a, q, kn, vn, rows, lens = self._case(dtype)
        for layer in (0, 1):
            kw = (dict(layer=None, layer_index=jnp.int32(layer)) if dynamic
                  else dict(layer=layer))
            fk, fv, fo = decode_wave_attention(
                k_a, v_a, q, kn, vn, rows, lens, block_s=16,
                interpret=True, **kw)
            rk, rv, ro = reference_decode_attention(
                k_a, v_a, q, kn, vn, rows, lens, layer=layer)
            assert fk.dtype == dtype and fo.dtype == jnp.float32
            assert float(jnp.max(jnp.abs(fo[:3] - ro[:3]))) < tol
            # The write is exact in the arena's dtype, on every row a real
            # lane touched, and nothing else of the slot moved.
            for b in (0, 1, 2):
                r = int(rows[b])
                np.testing.assert_array_equal(
                    np.asarray(fk[layer, r].astype(jnp.float32)),
                    np.asarray(rk[layer, r].astype(jnp.float32)))
                np.testing.assert_array_equal(
                    np.asarray(fv[layer, r].astype(jnp.float32)),
                    np.asarray(rv[layer, r].astype(jnp.float32)))

    def test_row_group_follows_the_dtype(self):
        from client_tpu.ops.decode_kernel import row_group

        assert row_group(jnp.float32) == 8
        assert row_group(jnp.bfloat16) == 16

    def test_float32_program_is_the_one_it_was(self):
        """For a float32 arena the dtype-generic kernel traces to GPT-2's
        program: full-precision products and nothing in bfloat16; the layer
        is its eighth operand whether it is a Python int or traced (PR 44:
        one body a wave program, not one a layer), so two layers are one
        traced function."""
        k_a, v_a, q, kn, vn, rows, lens = self._case(jnp.float32)

        def call(*layers):
            jaxpr = jax.make_jaxpr(lambda *a: [decode_wave_attention(
                *a, block_s=16, interpret=False, **kw) for kw in layers])(
                    k_a, v_a, q, kn, vn, rows, lens)
            outer = [e for e in jaxpr.jaxpr.eqns
                     if e.primitive.name in ("pjit", "jit")]
            (eqn,) = [e for e in outer[0].params["jaxpr"].jaxpr.eqns
                      if e.primitive.name == "pallas_call"]
            return outer, eqn, str(eqn.params["jaxpr"])

        outer, eqn, body = call(dict(layer=0), dict(layer=1))
        assert len(eqn.invars) == 8
        assert "bf16" not in body and "HIGHEST" in body
        assert len(outer) == 2
        assert outer[0].params["jaxpr"] is outer[1].params["jaxpr"]
        _, eqn, _ = call(dict(layer=None, layer_index=jnp.int32(0)))
        assert len(eqn.invars) == 8


class TestFlashPrefix:
    """``flash_attention(prefix=P)``: the first P keys stand before every
    query (masked by the bias where unused), the rest are causal."""

    @pytest.mark.parametrize("n_seen", [0, 5, 16])
    def test_matches_a_dense_mask(self, n_seen):
        b, s, h, d, pre = 2, 32, 2, 16, 16
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (b, s, h, d))
        k = jax.random.normal(ks[1], (b, pre + s, h, d))
        v = jax.random.normal(ks[2], (b, pre + s, h, d))
        idx = jnp.arange(pre + s)
        seen = (idx < n_seen) | (idx >= pre)
        bias = jnp.broadcast_to(jnp.where(seen, 0.0, -1e30), (b, pre + s))
        got = flash_attention(q, k, v, bias, causal=True, prefix=pre,
                              block_q=8, block_k=16, interpret=True)
        ok = seen[None, :] & ((idx[None, :] - pre) <= jnp.arange(s)[:, None])
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        sc = jnp.where(ok[None, None], sc, -1e30)
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5

    def test_rejects_keys_that_are_not_prefix_plus_queries(self):
        q = jnp.zeros((1, 16, 1, 8))
        k = jnp.zeros((1, 24, 1, 8))
        with pytest.raises(ValueError, match="prefix"):
            flash_attention(q, k, k, causal=True, prefix=16, interpret=True)


def _dense_mask_attention(q, k, v, bias, *, causal, prefix):
    """``reference_attention`` with the prefix rule: float32 scores over a
    dense mask, q/k/v ``[B, S, H, D]``."""
    s, s_k, d = q.shape[1], k.shape[1], q.shape[3]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) / np.sqrt(d)
    if bias is not None:
        sc = sc + bias[:, None, None, :]
    if causal:
        ok = (jnp.arange(s_k)[None, :] - prefix) <= jnp.arange(s)[:, None]
        sc = jnp.where(ok[None, None], sc, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1),
                      v.astype(jnp.float32))


class TestFlashLaneDenseLayout:
    """The kernel on ``[B, S, H*D]`` operands, as the projections write
    them: one head a lane block at D = 128, two heads sharing a 128-lane
    tile at D = 64, the whole row where heads neither fill nor divide a
    tile; causal blocks skipped and unmasked by their place in the grid."""

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 2e-2)])
    @pytest.mark.parametrize("h,d", [(4, 64), (2, 128), (3, 64), (2, 48)])
    @pytest.mark.parametrize("mode", ["causal", "bias", "causal+bias",
                                      "prefix"])
    def test_against_the_dense_oracle(self, mode, h, d, dtype, tol):
        b, s = 2, 64
        pre = 32 if mode == "prefix" else 0
        ks = jax.random.split(jax.random.PRNGKey(h * d), 3)
        q = jax.random.normal(ks[0], (b, s, h * d), dtype)
        k = jax.random.normal(ks[1], (b, pre + s, h * d), dtype)
        v = jax.random.normal(ks[2], (b, pre + s, h * d), dtype)
        bias = None
        if mode != "causal":
            idx = jnp.arange(pre + s)
            # A masked tail (padding), or a prefix seen only in part.
            drop = (idx >= 11) & (idx < pre) if pre else idx >= s - 13
            bias = jnp.broadcast_to(jnp.where(drop, -1e30, 0.0),
                                    (b, pre + s)).astype(jnp.float32)
        causal = mode != "bias"
        got = flash_attention(q, k, v, bias, causal=causal, prefix=pre,
                              n_heads=h, block_q=16, block_k=32,
                              interpret=True)
        assert got.shape == q.shape and got.dtype == dtype

        def heads(t):
            return t.reshape(b, t.shape[1], h, d)

        want = _dense_mask_attention(heads(q), heads(k), heads(v), bias,
                                     causal=causal, prefix=pre)
        err = jnp.abs(got.astype(jnp.float32) - want.reshape(b, s, h * d))
        assert float(jnp.max(err)) < tol

    @pytest.mark.parametrize("block_q,block_k,sub_q", [
        (8, 8, None), (16, 64, None), (64, 16, None), (32, 32, 16),
        (64, 64, None), (64, 64, 16), (64, 64, 64), (512, 512, None),
        (512, 512, 128), (256, 512, 64)])
    def test_causal_blocks_in_every_shape(self, block_q, block_k, sub_q):
        """Blocks that the causal rule masks whole are skipped, blocks it
        does not touch go unmasked, and where one block holds the whole
        problem each piece of queries takes the keys up to its last one:
        the result is the dense one whatever the grid."""
        b, s, h, d = 1, max(64, block_q), 2, 64
        q, k, v = _qkv(b, s, h, d)
        got = flash_attention(q, k, v, causal=True, block_q=block_q,
                              block_k=block_k, sub_q=sub_q, interpret=True)
        ref = reference_attention(q, k, v, causal=True)
        assert float(jnp.max(jnp.abs(got - ref))) < 2e-5
        with pytest.raises(ValueError, match="pieces"):
            flash_attention(q, k, v, causal=True, block_q=block_q,
                            block_k=block_k, sub_q=block_q + 8,
                            interpret=True)

    def test_both_ranks_are_one_kernel(self):
        b, s, h, d = 2, 32, 4, 64
        q, k, v = _qkv(b, s, h, d)
        four = flash_attention(q, k, v, causal=True, interpret=True)
        three = flash_attention(*(t.reshape(b, s, h * d) for t in (q, k, v)),
                                causal=True, n_heads=h, interpret=True)
        np.testing.assert_array_equal(np.asarray(four).reshape(b, s, h * d),
                                      np.asarray(three))
        with pytest.raises(ValueError, match="n_heads"):
            flash_attention(*(t.reshape(b, s, h * d) for t in (q, k, v)),
                            interpret=True)

    def test_no_operand_is_transposed(self):
        """q, k, v reach the kernel as they were given and the output
        leaves as it is: no transpose in the traced program."""
        from client_tpu.ops.flash_attention import head_tile

        q = jnp.zeros((2, 32, 12 * 64))
        jaxpr = jax.make_jaxpr(lambda *a: flash_attention(
            *a, causal=True, n_heads=12))(q, q, q)
        (outer,) = jaxpr.jaxpr.eqns
        names = [e.primitive.name for e in outer.params["jaxpr"].jaxpr.eqns]
        assert "pallas_call" in names and "transpose" not in names
        assert (head_tile(12, 64), head_tile(32, 128), head_tile(4, 16),
                head_tile(3, 64), head_tile(8, 32)) == (128, 128, 64, 192, 128)


class TestPromptRowsWrite:
    """Prefill's write into the arena (ops/arena_write.py): lane b's
    ``[n, H*D]`` slab lands at ``[layer, rows[b], :n]``; every other row,
    position and layer is bitwise what it was."""

    def _case(self, n=16, dtype=jnp.float32):
        ks = jax.random.split(jax.random.PRNGKey(11), 4)
        shape = (2, 6, 32, 128)
        k_a = jax.random.normal(ks[0], shape).astype(dtype)
        v_a = jax.random.normal(ks[1], shape).astype(dtype)
        kn = jax.random.normal(ks[2], (4, n, 128))
        vn = jax.random.normal(ks[3], (4, n, 128))
        # Lanes 1 and 3 are padded: both point at the dummy row (the last).
        rows = jnp.asarray([2, 5, 0, 5], jnp.int32)
        return k_a, v_a, kn, vn, rows

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("layer", [0, 1])
    def test_kernel_writes_the_rows_and_nothing_else(self, layer, dtype):
        from client_tpu.ops.arena_write import (kernel_writes,
                                                write_prompt_rows)

        k_a, v_a, kn, vn, rows = self._case(dtype=dtype)
        assert kernel_writes(16, dtype) and not kernel_writes(4, dtype)
        assert kernel_writes(8, jnp.float32)
        assert not kernel_writes(8, jnp.bfloat16)
        fk, fv = write_prompt_rows(k_a, v_a, kn, vn, rows, layer=layer,
                                   interpret=True)
        for got, before, new in ((fk, k_a, kn), (fv, v_a, vn)):
            got, before = np.asarray(got), np.asarray(before)
            for b in (0, 2):                       # live lanes
                np.testing.assert_array_equal(
                    got[layer, int(rows[b]), :16],
                    np.asarray(new[b].astype(dtype)))
            touched = np.zeros(before.shape[:3], bool)
            touched[layer, np.asarray(rows), :16] = True
            np.testing.assert_array_equal(got[~touched], before[~touched])
            # The dummy row took a padded lane's slab (either's).
            junk = got[layer, 5, :16]
            assert any(np.array_equal(junk, np.asarray(new[b].astype(dtype)))
                       for b in (1, 3))

    def test_a_lane_can_be_left_out(self):
        from client_tpu.ops.arena_write import write_prompt_rows

        k_a, v_a, kn, vn, rows = self._case()
        fk, _ = write_prompt_rows(k_a, v_a, kn, vn, rows,
                                  jnp.asarray([1, 0, 0, 0]), layer=1,
                                  interpret=True)
        np.testing.assert_array_equal(np.asarray(fk[1, 2, :16]),
                                      np.asarray(kn[0]))
        for row in (0, 5):
            np.testing.assert_array_equal(np.asarray(fk[1, row]),
                                          np.asarray(k_a[1, row]))

    @pytest.mark.parametrize("n", [4, 16, 32])
    def test_reference_is_the_same_write(self, n):
        from client_tpu.ops.arena_write import (reference_write_prompt_rows,
                                                write_prompt_rows)

        k_a, v_a, kn, vn, _ = self._case(n=n)
        rows = jnp.asarray([2, 4, 0, 5], jnp.int32)        # distinct rows
        rk, rv = reference_write_prompt_rows(k_a, v_a, kn, vn, rows, layer=1)
        np.testing.assert_array_equal(np.asarray(rk[1, 4, :n]),
                                      np.asarray(kn[1]))
        np.testing.assert_array_equal(np.asarray(rk[0]), np.asarray(k_a[0]))
        if n >= 8:
            fk, fv = write_prompt_rows(k_a, v_a, kn, vn, rows, layer=1,
                                       interpret=True)
            np.testing.assert_array_equal(np.asarray(fk), np.asarray(rk))
            np.testing.assert_array_equal(np.asarray(fv), np.asarray(rv))

    def test_slabs_must_fit_the_rows(self):
        from client_tpu.ops.arena_write import write_prompt_rows

        k_a, v_a, kn, vn, rows = self._case()
        with pytest.raises(ValueError, match="fit"):
            write_prompt_rows(k_a, v_a, kn[..., :64], vn[..., :64], rows,
                              layer=0, interpret=True)

    @pytest.mark.parametrize("shards", [2, 4])
    def test_row_sharded_arena_takes_each_lane_on_its_shard(self, shards):
        from client_tpu.ops.arena_write import reference_write_prompt_rows
        from client_tpu.parallel.kv_shard import (
            arena_row_layout,
            kv_mesh,
            shard_arena,
            sharded_write_prompt_rows,
        )

        mesh = kv_mesh(shards)
        total, free, dummy = arena_row_layout(8, shards)
        ks = jax.random.split(jax.random.PRNGKey(2), 4)
        shape = (2, total, 16, 128)
        k_a, v_a = (jax.random.normal(k, shape) for k in ks[:2])
        kn, vn = (jax.random.normal(k, (4, 8, 128)) for k in ks[2:])
        rows = jnp.asarray([free[0], free[-1], dummy, free[3]], jnp.int32)
        arena = shard_arena({"k": k_a, "v": v_a,
                             "tok": jnp.zeros(total, jnp.int32)}, mesh)
        fk, fv = jax.jit(lambda k, v: sharded_write_prompt_rows(
            mesh, k, v, kn, vn, rows, layer=1, interpret=True))(
                arena["k"], arena["v"])
        rk, rv = reference_write_prompt_rows(k_a, v_a, kn, vn, rows, layer=1)
        np.testing.assert_array_equal(np.asarray(fk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(rv))


class TestDecodeKernelGpt2Geometry:
    """The arena access at GPT-2's head geometry (12 heads x 64 on a
    768-lane row): the kernel against the XLA oracle where the block does
    not divide every length, at the row's edges, with padded lanes parked
    on the dummy row, and with every row and position a wave does not
    touch bitwise unchanged."""

    H, D, S, ROWS = 12, 64, 48, 7

    def _case(self, lens, seed=5):
        bsz = len(lens)
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        shape = (2, self.ROWS, self.S, self.H * self.D)
        k_a = jax.random.normal(ks[0], shape)
        v_a = jax.random.normal(ks[1], shape)
        q, kn, vn = (jax.random.normal(k, (bsz, self.H, self.D))
                     for k in ks[2:])
        # Real lanes take rows 0..; a length of None is a padded lane:
        # the dummy row (the last), length 0.
        rows = [self.ROWS - 1 if n is None else i
                for i, n in enumerate(lens)]
        return (k_a, v_a, q, kn, vn, jnp.asarray(rows, jnp.int32),
                jnp.asarray([n or 0 for n in lens], jnp.int32))

    @pytest.mark.parametrize("block_s", [8, 16, 24, 48])
    def test_matches_oracle_where_block_splits_lengths(self, block_s):
        lens = [0, 1, 7, 8, 23, 47]          # 47 = S - 1, the row's end
        k_a, v_a, q, kn, vn, rows, lens_a = self._case(lens)
        fk, fv, fo = decode_wave_attention(
            k_a, v_a, q, kn, vn, rows, lens_a, layer=1, block_s=block_s,
            interpret=True)
        rk, rv, ro = reference_decode_attention(
            k_a, v_a, q, kn, vn, rows, lens_a, layer=1)
        assert float(jnp.max(jnp.abs(fo - ro))) < 2e-5
        np.testing.assert_array_equal(np.asarray(fk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(rv))

    @pytest.mark.parametrize("lens", [
        [5, None, 30, None, None],      # in the middle and at the end
        [None, 5, 30],                  # the first lane
        [None, None, 47, 9, None],      # both ends
        [12],                           # a wave of one lane
        [None],                         # and of one padded lane
    ], ids=str)
    def test_padded_lanes_on_the_dummy_row(self, lens):
        k_a, v_a, q, kn, vn, rows, lens_a = self._case(lens)
        fk, fv, fo = decode_wave_attention(
            k_a, v_a, q, kn, vn, rows, lens_a, layer=0, block_s=16,
            interpret=True)
        _, _, ro = reference_decode_attention(
            k_a, v_a, q, kn, vn, rows, lens_a, layer=0)
        live = np.asarray([n is not None for n in lens])
        if live.any():
            assert float(jnp.max(jnp.abs(fo[live] - ro[live]))) < 2e-5
        # A padded lane attends to its own token alone: exactly v_new.
        np.testing.assert_allclose(np.asarray(fo[~live]),
                                   np.asarray(vn[~live]), rtol=1e-6)
        # The dummy row took the junk; nothing beyond its position 0 did.
        dummy = self.ROWS - 1
        np.testing.assert_array_equal(np.asarray(fk[0, dummy, 1:]),
                                      np.asarray(k_a[0, dummy, 1:]))
        # Every live lane's slot but for its one new row is as it was.
        for b in np.nonzero(live)[0]:
            n = lens[b]
            np.testing.assert_array_equal(
                np.delete(np.asarray(fv[0, b]), n, 0),
                np.delete(np.asarray(v_a[0, b]), n, 0))

    def test_a_wave_writes_one_position_per_lane_and_nothing_else(self):
        lens = [3, 47, None, 16]
        k_a, v_a, q, kn, vn, rows, lens_a = self._case(lens)
        layer = 1
        fk, fv, _ = decode_wave_attention(
            k_a, v_a, q, kn, vn, rows, lens_a, layer=layer, block_s=24,
            interpret=True)
        touched = np.zeros(k_a.shape[:3], bool)
        touched[layer, np.asarray(rows), np.asarray(lens_a)] = True
        for got, before, new in ((fk, k_a, kn), (fv, v_a, vn)):
            got, before = np.asarray(got), np.asarray(before)
            np.testing.assert_array_equal(got[~touched], before[~touched])
            for b in (0, 1, 3):
                np.testing.assert_array_equal(
                    got[layer, int(rows[b]), int(lens_a[b])],
                    np.asarray(new[b]).reshape(-1))

    def test_feature_width_must_match_the_arena(self):
        k_a, v_a, q, kn, vn, rows, lens_a = self._case([1, 2])
        with pytest.raises(ValueError, match="features"):
            decode_wave_attention(k_a, v_a, q[:, :6], kn[:, :6], vn[:, :6],
                                  rows, lens_a, layer=0, interpret=True)


class TestShardedKvArena:
    def test_arena_row_layout(self):
        assert arena_row_layout(4, 1) == (5, [0, 1, 2, 3], 4)
        total, free, dummy = arena_row_layout(4, 2)
        assert (total, dummy) == (6, 2)
        assert free == [0, 1, 3, 4]  # rows 2 and 5 are the junk rows
        with pytest.raises(ValueError, match="divisible"):
            arena_row_layout(5, 2)

    def test_ring_all_reduce_sums(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = kv_mesh(4)
        x = jnp.arange(4 * 8, dtype=jnp.float32).reshape(4, 8)

        # The TPU interpreter's vector-clock race detector checks the
        # ring's synchronisation as well as its sum: without the per-hop
        # handshake it reports the neighbor overwriting a slot that is
        # still being sent from.
        from jax._src.pallas.mosaic.interpret import (
            interpret_pallas_call as tpu_interpret,
        )
        from jax.experimental.pallas import tpu as pltpu

        def body(x_sh):
            return ring_all_reduce(
                x_sh[0], "kv", 4,
                interpret=pltpu.InterpretParams(detect_races=True))[None]

        fn = shard_map(body, mesh=mesh, in_specs=(P("kv"),),
                       out_specs=P("kv"), check_vma=False)
        out = np.asarray(fn(x))
        want = np.tile(np.asarray(x).sum(0), (4, 1))
        np.testing.assert_allclose(out, want, rtol=1e-6)
        assert not tpu_interpret.races.races_found

    @pytest.mark.parametrize("n,combine", [(2, "ring"), (2, "psum"),
                                           (4, "ring"), (4, "psum")])
    def test_sharded_matches_single_chip(self, n, combine):
        """2 and 4 mesh shards over the row-sharded arena == the
        single-chip kernel on the free rows, and == the XLA reference."""
        cap = 4
        total, free, _dummy = arena_row_layout(cap, n)
        layers, s, h, d, bsz = 2, 16, 2, 8, 3
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        k_a = jax.random.normal(ks[0], (layers, total, s, h * d))
        v_a = jax.random.normal(ks[1], (layers, total, s, h * d))
        q = jax.random.normal(ks[2], (bsz, h, d))
        kn = jax.random.normal(ks[3], (bsz, h, d))
        vn = jax.random.normal(ks[4], (bsz, h, d))
        # Lanes on more than one shard: the first, third and last free
        # rows (2 shards: rows 0 | 3, 4; 4 shards: rows 0 | 4 | 6).
        rows = jnp.asarray([free[0], free[2], free[3]], jnp.int32)
        lens = jnp.asarray([5, 0, s - 1], jnp.int32)

        mesh = kv_mesh(n)
        sk, sv, so = sharded_decode_attention(
            mesh, k_a, v_a, q, kn, vn, rows, lens, layer=1,
            interpret=True, combine=combine)
        fk, fv, fo = decode_wave_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=1, interpret=True)
        rk, rv, ro = reference_decode_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=1)
        assert float(jnp.max(jnp.abs(so - fo))) < 2e-5
        assert float(jnp.max(jnp.abs(so - ro))) < 2e-5
        # Free-row arena content identical across all three paths (junk
        # rows absorb unowned scatters and are never read).
        np.testing.assert_allclose(np.asarray(sk[:, free]),
                                   np.asarray(fk[:, free]), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(sv[:, free]),
                                   np.asarray(rv[:, free]), rtol=1e-6)

    def test_kv_mesh_rejects_oversubscription(self):
        with pytest.raises(ValueError, match="device"):
            kv_mesh(1024)


# -- grouped-query rows, a ring, a band (PR 43) ---------------------------------

def _grouped_case(s=32, h=6, hkv=2, d=16, bsz=4, dtype=jnp.float32, seed=3):
    """An arena of grouped-query rows (``hkv`` key heads a row, ``h`` query
    heads) and one wave; lane 3 is a padded lane on the dummy row."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    k_arena = jax.random.normal(ks[0], (2, 5, s, hkv * d)).astype(dtype)
    v_arena = jax.random.normal(ks[1], (2, 5, s, hkv * d)).astype(dtype)
    q = jax.random.normal(ks[2], (bsz, h, d))
    kn = jax.random.normal(ks[3], (bsz, hkv, d))
    vn = jax.random.normal(ks[4], (bsz, hkv, d))
    rows = jnp.asarray([0, 2, 1, 4], jnp.int32)[:bsz]
    return k_arena, v_arena, q, kn, vn, rows


def _ring_by_hand(k_arena, v_arena, q, kn, vn, row, n, layer, window):
    """One lane by positions, nothing shared with the oracle's masks: the
    ring's rows put back in order of position, the last ``window - 1`` of
    them and the new token under one softmax."""
    s = k_arena.shape[2]
    d = q.shape[-1]
    group = q.shape[0] * d // k_arena.shape[3]
    first = max(0, n - s)
    keys = [np.asarray(k_arena[layer, row, p % s], np.float32)
            for p in range(first, n)] + [np.asarray(kn, np.float32).ravel()]
    vals = [np.asarray(v_arena[layer, row, p % s], np.float32)
            for p in range(first, n)] + [np.asarray(vn, np.float32).ravel()]
    keys = np.stack(keys)[-window:].reshape(-1, kn.shape[0], d)
    vals = np.stack(vals)[-window:].reshape(-1, kn.shape[0], d)
    out = []
    for i in range(q.shape[0]):
        sc = keys[:, i // group] @ np.asarray(q[i]) / np.sqrt(d)
        p = np.exp(sc - sc.max())
        out.append((p / p.sum()) @ vals[:, i // group])
    return np.stack(out)


class TestGroupedQueryRowsAndRing:
    """``decode_wave_attention`` with fewer key heads a row than q has heads,
    and ``window_wave_attention`` (the same kernel over a ring), interpreted,
    against ``reference_decode_attention`` and against a lane worked out by
    positions."""

    # Three query heads a key head of 16 at the kernel's own scale, and
    # ``granite4_h_micro``'s heads: four a key head of 64 (two key heads a
    # 128-lane tile), the scores under a multiplier of the model's (1/64).
    @pytest.mark.parametrize("heads,sm_scale", [((6, 2, 16), None),
                                                ((32, 8, 64), 0.015625)],
                             ids=["6_over_2_of_16", "32_over_8_of_64"])
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 2e-2)])
    @pytest.mark.parametrize("block_s,lens", [
        (8, [7, 31, 16, 0]), (32, [7, 31, 16, 0]),
        (16, [15, 16, 17, 0]), (32, [0, 8, 9, 1])])
    def test_grouped_query_rows_match_the_oracle(self, block_s, lens, dtype,
                                                 tol, heads, sm_scale):
        h, hkv, d = heads
        k_a, v_a, q, kn, vn, rows = _grouped_case(h=h, hkv=hkv, d=d,
                                                  dtype=dtype)
        lens = jnp.asarray(lens, jnp.int32)
        fk, fv, fo = decode_wave_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=1, block_s=block_s,
            interpret=True, sm_scale=sm_scale)
        rk, rv, ro = reference_decode_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=1, sm_scale=sm_scale)
        assert float(jnp.max(jnp.abs(fo[:3] - ro[:3]))) < tol
        np.testing.assert_array_equal(np.asarray(fk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(rv))

    def test_a_scale_of_the_models_is_the_query_scaled_by_hand(self):
        """1/64 is ``1 / sqrt(64)`` of a query that is an eighth of itself:
        exact in float32, so the oracle's two forms agree to the bit."""
        k_a, v_a, q, kn, vn, rows = _grouped_case(h=32, hkv=8, d=64)
        lens = jnp.asarray([7, 31, 16, 0], jnp.int32)
        _, _, scaled = reference_decode_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=1, sm_scale=0.015625)
        _, _, by_hand = reference_decode_attention(
            k_a, v_a, q / 8, kn, vn, rows, lens, layer=1)
        assert float(jnp.max(jnp.abs(scaled - by_hand))) < 1e-6

    # Lengths under, at and over the ring (32 rows): the first overwrite is
    # at 32, the ring has wrapped twice at 77.
    @pytest.mark.parametrize("length", [0, 5, 31, 32, 33, 40, 63, 64, 77])
    @pytest.mark.parametrize("window", [None, 31, 27])
    @pytest.mark.parametrize("block_s", [8, 32])
    def test_ring_at_every_length(self, length, window, block_s):
        k_a, v_a, q, kn, vn, _ = _grouped_case(bsz=1)
        rows = jnp.asarray([2], jnp.int32)
        lens = jnp.asarray([length], jnp.int32)
        fk, fv, fo = window_wave_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=0, block_s=block_s,
            interpret=True, window=window)
        rk, rv, ro = reference_decode_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=0, ring=True,
            window=window)
        assert float(jnp.max(jnp.abs(fo - ro))) < 2e-5
        np.testing.assert_array_equal(np.asarray(fk), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(fv), np.asarray(rv))
        # The written row is ``length mod 32`` and no other.
        changed = np.nonzero((np.asarray(fk[0, 2]) != np.asarray(
            k_a[0, 2])).any(-1))[0]
        assert changed.tolist() == [length % 32]
        want = _ring_by_hand(k_a, v_a, q[0], kn[0], vn[0], 2, length, 0,
                             32 if window is None else window)
        assert float(np.abs(np.asarray(fo[0]) - want).max()) < 2e-5

    # Rings empty, part full, full and wrapped in one wave; a wave that
    # starts and one that ends with lanes without a row.
    @pytest.mark.parametrize("lens", [[5, 32, 70, 0], [0, 0, 40, 31],
                                      [33, 64, 0, 0], [0, 9, 0, 100]],
                             ids=str)
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                           (jnp.bfloat16, 2e-2)])
    def test_a_wave_of_lanes_over_the_ring(self, lens, dtype, tol):
        k_a, v_a, q, kn, vn, rows = _grouped_case(dtype=dtype)
        lens = jnp.asarray(lens, jnp.int32)
        _, _, fo = window_wave_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=1, block_s=16,
            interpret=True)
        _, _, ro = reference_decode_attention(
            k_a, v_a, q, kn, vn, rows, lens, layer=1, ring=True)
        assert float(jnp.max(jnp.abs(fo - ro))) < tol
        assert bool(jnp.all(jnp.isfinite(fo)))

    def test_rows_that_hold_no_whole_group_are_refused(self):
        k_a, v_a, q, kn, vn, rows = _grouped_case(h=5)
        with pytest.raises(ValueError, match="features"):
            decode_wave_attention(k_a, v_a, q, kn, vn, rows,
                                  jnp.zeros(4, jnp.int32), layer=0,
                                  interpret=True)
        k_a, v_a, q, kn, vn, rows = _grouped_case()
        with pytest.raises(ValueError, match="window"):
            window_wave_attention(k_a, v_a, q, kn, vn, rows,
                                  jnp.zeros(4, jnp.int32), layer=0,
                                  interpret=True, window=33)


def _band_attention(q, k, v, prefix, window):
    """``reference_attention`` under a band mask, key heads repeated: q ``[B,
    S, H, D]``, k and v ``[B, prefix + S, Hkv, D]``."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    ago = (prefix + jnp.arange(q.shape[1])[:, None]) - jnp.arange(
        k.shape[1])[None, :]
    seen = ago >= 0
    if window is not None:
        seen = seen & (ago < window)
    bias = jnp.where(seen, 0.0, -1e30)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(sc + bias[None, None], -1), v)


class TestFlashBandAndGroupedQueryHeads:
    """``flash_attention(window=W)`` and ``n_kv_heads``, interpreted, against
    a dense band mask; block sizes that leave whole key blocks before the
    band (neither fetched nor computed) and behind the causal edge."""

    # (queries, prefix, window, heads, key heads, head size, block_q,
    # block_k, sub_q)
    @pytest.mark.parametrize("case", [
        (64, 0, 24, 4, 4, 16, 16, 8, None),     # blocks before the band
        (64, 0, 24, 4, 2, 128, 16, 8, None),    # and grouped-query heads
        (64, 0, 8, 2, 2, 16, 8, 8, None),       # a band of one block
        (32, 64, 40, 4, 2, 128, 32, 32, None),  # a prefix cut by the band
        (32, 64, 64, 2, 1, 128, 32, 32, None),  # the piece's full ring
        (16, 48, 16, 2, 2, 128, 16, 16, None),  # the prefix masked whole
        (32, 0, 8, 2, 1, 128, 32, 32, 8),       # one block, cut in pieces
        (64, 0, None, 4, 2, 128, 16, 16, None),  # grouped, no band
        (64, 0, 1000, 4, 1, 128, 16, 32, None),  # a band wider than all
    ])
    def test_matches_a_dense_band_mask(self, case):
        s, prefix, window, h, hkv, d, bq, bk, sub = case
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (1, s, h, d))
        k = jax.random.normal(ks[1], (1, prefix + s, hkv, d))
        v = jax.random.normal(ks[2], (1, prefix + s, hkv, d))
        got = flash_attention(
            q.reshape(1, s, h * d), k.reshape(1, -1, hkv * d),
            v.reshape(1, -1, hkv * d), causal=True, prefix=prefix,
            window=window, n_heads=h, n_kv_heads=hkv, block_q=bq,
            block_k=bk, sub_q=sub, interpret=True).reshape(q.shape)
        want = _band_attention(q, k, v, prefix, window)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5

    def test_the_band_skips_the_blocks_it_masks_whole(self):
        """Junk (NaN) in a key block wholly before the band never reaches
        the output: the block is not computed."""
        s, d, window = 64, 16, 16
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q, k, v = (jax.random.normal(key, (1, s, 2, d)) for key in ks)
        # Queries 48.. see keys 33..: key block 0 (0..15) is before every
        # band of the last query block, and behind no earlier block's edge.
        got = flash_attention(q, k, v, causal=True, window=window,
                              block_q=16, block_k=16, interpret=True)
        junk_v = v.at[:, :16].set(jnp.nan)
        again = flash_attention(q, k, junk_v, causal=True, window=window,
                                block_q=16, block_k=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[:, 32:]),
                                      np.asarray(again[:, 32:]))

    def test_what_cannot_be_served_is_refused(self):
        q = jnp.zeros((1, 16, 4 * 16))
        kv = jnp.zeros((1, 16, 2 * 16))
        with pytest.raises(ValueError, match="grouped-query"):
            flash_attention(q, kv, kv, causal=True, n_heads=4, n_kv_heads=2,
                            interpret=True)
        with pytest.raises(ValueError, match="band"):
            flash_attention(q, q, q, n_heads=4, window=8, interpret=True)


# -- the state-space update and the un-gated grouped product (PR 45) -------------

from client_tpu.ops import ssd  # noqa: E402
from client_tpu.ops.grouped_matmul import (  # noqa: E402
    capacity_rows,
    grouped_matmul,
    pick_tile_n,
    plan_groups,
    reference_grouped_matmul,
)


def _ssd_operands(n, heads=4, p=8, groups=2, state=16, strong=False, seed=0):
    rng = np.random.default_rng(seed)
    dt = rng.uniform(0.001, 8.0 if strong else 0.1, (n, heads))
    return (jnp.asarray(rng.normal(size=(n, heads, p)), jnp.float32),
            jnp.asarray(dt, jnp.float32),
            -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32),
            jnp.asarray(rng.normal(size=(n, groups, state)), jnp.float32),
            jnp.asarray(rng.normal(size=(n, groups, state)), jnp.float32),
            jnp.asarray(rng.normal(size=(heads, state, p)), jnp.float32))


class TestSsd:
    # (positions, chunk, groups of B and C, limits as a share of the largest
    # value): two groups as ``nemotron3_nano_30b``'s eight, one for all the
    # heads and the published chunk of 256 as ``granite4_h_micro``'s.  The
    # cases of 32 positions keep their absolute limits; 512 strong steps
    # leave states of 30 and more, and float32 carries seven digits of them,
    # so that case alone is held to the limits times the largest value.
    @pytest.mark.parametrize("n,chunk,groups,relative", [
        (32, 1, 2, False), (32, 4, 2, False), (32, 16, 2, False),
        (32, 32, 2, False), (32, 4, 1, False), (32, 32, 1, False),
        (512, 256, 1, True)])
    @pytest.mark.parametrize("strong", [False, True])
    @pytest.mark.parametrize("pack", [1, 2])
    def test_the_chunked_form_is_the_recurrence_from_a_state(
            self, n, chunk, groups, relative, strong, pack):
        """From a non-zero start state, at any chunk, packed as the arena
        packs it or a state a head; under a decay of ``exp(-128)`` a step
        nothing overflows (no ``1 / exp(G)`` is formed)."""
        x, dt, a, b, c, s0 = _ssd_operands(n, groups=groups, strong=strong)
        want_y, want_s = ssd.ssd_recurrence(x, dt, a, b, c, s0)
        got_y, got_s = ssd.ssd_chunk_scan(x, dt, a, b, c,
                                          ssd.pack_state(s0, pack),
                                          chunk=chunk)
        assert np.isfinite(np.asarray(got_y)).all()
        size_y = max(1.0, float(jnp.abs(want_y).max())) if relative else 1.0
        size_s = max(1.0, float(jnp.abs(want_s).max())) if relative else 1.0
        assert np.abs(np.asarray(got_y - want_y)).max() < 2e-4 * size_y
        assert np.abs(np.asarray(ssd.unpack_state(got_s, pack) - want_s)
                      ).max() < 2e-5 * size_s

    def test_the_recurrence_is_the_published_line(self):
        """``S = exp(dt A) S + (dt x) B^T``, ``y = S C``, head h on group ``h
        // (H / G)``, written out with numpy for one position."""
        x, dt, a, b, c, s0 = (np.asarray(t, np.float64)
                              for t in _ssd_operands(1, seed=3))
        y, s = ssd.ssd_recurrence(*(jnp.asarray(t, jnp.float32) for t in
                                    (x, dt, a, b, c, s0)))
        for h in range(4):
            g = h // 2
            want = (np.exp(dt[0, h] * a[h]) * s0[h].T
                    + np.outer(dt[0, h] * x[0, h], b[0, g]))       # [P, N]
            assert np.abs(np.asarray(s)[h].T - want).max() < 1e-5
            assert np.abs(np.asarray(y)[0, h] - want @ c[0, g]).max() < 1e-4

    def test_a_padded_position_moves_nothing(self):
        x, dt, a, b, c, s0 = _ssd_operands(16, seed=1)
        dt = dt.at[11:].set(0.0)
        _, s_all = ssd.ssd_chunk_scan(x, dt, a, b, c, s0, chunk=8)
        _, s_cut = ssd.ssd_recurrence(x[:11], dt[:11], a, b[:11], c[:11], s0)
        assert np.abs(np.asarray(s_all - s_cut)).max() < 2e-5

    def test_packing_is_its_own_inverse_and_puts_a_groups_heads_side_by_side(
            self):
        s = jnp.arange(2 * 4 * 3 * 5, dtype=jnp.float32).reshape(2, 4, 3, 5)
        packed = ssd.pack_state(s, 2)
        assert packed.shape == (2, 2, 3, 10)
        assert np.array_equal(np.asarray(packed[1, 0, :, 5:]),
                              np.asarray(s[1, 1]))
        assert np.array_equal(np.asarray(ssd.unpack_state(packed, 2)),
                              np.asarray(s))

    @pytest.mark.parametrize("layer", [1, "traced"])
    @pytest.mark.parametrize("heads,groups,pack,block", [
        (4, 2, 2, 32), (8, 2, 2, 2), (8, 8, 1, 4), (64, 8, 2, 32),
        (4, 1, 2, 32), (64, 1, 2, 32)])
    def test_wave_kernel_parity_in_place(self, heads, groups, pack, block,
                                         layer, monkeypatch):
        """The kernel (interpreted) against its oracle: the lanes' slots
        advanced, every other slot and layer bit for bit as it was, whatever
        the block of packed heads; two padded lanes on the junk slot."""
        monkeypatch.setattr(ssd, "HEAD_BLOCK", block)
        p, state, slots = 8, 16, 6
        x, dt, a, b, c, _ = _ssd_operands(5, heads, p, groups, state, seed=2)
        rng = np.random.default_rng(4)
        arena = ssd.pack_state(jnp.asarray(
            rng.normal(size=(3, slots, heads, state, p)), jnp.float32), pack)
        rows = jnp.asarray([4, 0, 2, 5, 5], jnp.int32)
        want_s, want_y = ssd.reference_ssd_update(arena, x, dt, a, b, c, rows,
                                                  layer=1)
        at = 1 if layer == 1 else jnp.asarray(1, jnp.int32)
        got_s, got_y = jax.jit(
            lambda s, ly: ssd.ssd_wave_update(s, x, dt, a, b, c, rows,
                                              layer=ly, interpret=True),
            static_argnums=(1,) if layer == 1 else ())(arena, at)
        live = np.asarray([4, 0, 2])
        assert np.abs(np.asarray(got_y - want_y))[:3].max() < 1e-5
        assert np.abs(np.asarray(got_s - want_s))[1, live].max() < 1e-6
        untouched = np.asarray(got_s).copy()
        untouched[1, [4, 0, 2, 5]] = np.asarray(arena)[1, [4, 0, 2, 5]]
        assert np.array_equal(untouched, np.asarray(arena))

    def test_wave_kernel_refuses_heads_that_do_not_lie_in_the_leaf(self):
        x, dt, a, b, c, _ = _ssd_operands(2, heads=4, groups=2)
        # Four packed heads of 16 lanes for four heads of 8: neither packed
        # nor plain.
        arena = jnp.zeros((1, 3, 4, 16, 16), jnp.float32)
        with pytest.raises(ValueError, match="do not lie"):
            ssd.ssd_wave_update(arena, x, dt, a, b, c,
                                jnp.asarray([0, 1], jnp.int32), layer=0,
                                interpret=True)


@pytest.mark.parametrize("width", [24, 1856 // 8, 200])
@pytest.mark.parametrize("skew", [False, True])
def test_transposed_grouped_matmul_at_a_width_off_the_lanes(width, skew):
    """The un-gated expert's up product: weights ``[E, f, d]`` with ``f`` no
    multiple of 128, multiplied transposed, one block an expert: the kernel
    (interpreted) against the oracle on the same layout, and against the
    plain form on the swapped weights."""
    rng = np.random.default_rng(6)
    n_experts, d, tile_m, pairs = 5, 64, 8, 37
    expert = (np.minimum(rng.geometric(0.6, pairs) - 1, n_experts)
              if skew else rng.integers(0, n_experts + 1, pairs))
    rows = capacity_rows(pairs, n_experts, tile_m)
    plan = plan_groups(jnp.asarray(expert, jnp.int32), n_experts, tile_m,
                       rows)
    xs = jnp.asarray(rng.normal(size=(rows, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n_experts, width, d)), jnp.float32)
    got = grouped_matmul(xs, w, plan["tile_expert"], plan["n_tiles"],
                         tile_m=tile_m, interpret=True, transposed=True)
    want = reference_grouped_matmul(xs, w.swapaxes(1, 2), plan["padded"])
    used = int(plan["n_tiles"][0]) * tile_m
    assert got.shape == (rows, width)
    assert np.abs(np.asarray(got - want))[:used].max() < 1e-4
    plain = grouped_matmul(xs, w.swapaxes(1, 2), plan["tile_expert"],
                           plan["n_tiles"], tile_m=tile_m, interpret=True)
    assert np.abs(np.asarray(got - plain))[:used].max() < 1e-4
    with pytest.raises(ValueError, match="do not fit"):
        grouped_matmul(xs, w[:, :, :-1], plan["tile_expert"], plan["n_tiles"],
                       tile_m=tile_m, interpret=True, transposed=True)


def test_pick_tile_n_falls_back_to_a_width_that_has_no_lane_divisor():
    assert pick_tile_n(2688, 1856, 2) == 1856       # 14.5 x 128: one block
    assert pick_tile_n(1856, 2688, 2) == 2688       # 21 x 128, 9.98 MB
    assert pick_tile_n(2560, 1536, 2) == 1536
