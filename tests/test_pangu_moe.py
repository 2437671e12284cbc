"""The sparse-expert decoder with a latent cache (models/pangu_moe.py) at a
tiny preset on the CPU, seeded weights, Pallas interpreted: the served path
(prefill by pieces, absorbed decode waves through the latent cache) against
the plain reference's full forward pass on logits, the share of an
expert-parallel group against the uncut layer, dropless routing, the two new
kernels against their oracles, the wave's routing counts through the
scheduler, and the benchmark family's readers and arithmetic."""

import functools
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import family  # noqa: E402

from client_tpu.engine import TpuEngine  # noqa: E402
from client_tpu.engine.repository import ModelRepository  # noqa: E402
from client_tpu.engine.types import InferRequest  # noqa: E402
from client_tpu.models import experts  # noqa: E402
from client_tpu.models.seeded import SeededWeight  # noqa: E402
from client_tpu.models.pangu_moe import PanguMoeBackend  # noqa: E402
from client_tpu.observability import spans  # noqa: E402
from client_tpu.ops.decode_kernel import (  # noqa: E402
    latent_row_width,
    latent_wave_attention,
    reference_latent_attention,
)
from client_tpu.ops.grouped_matmul import (  # noqa: E402
    capacity_rows,
    grouped_matmul,
    plan_groups,
    reference_grouped_matmul,
)

fam = family.load("pangu_moe")
SEQ, PIECE, N = 64, 16, 44
# bfloat16 matmuls and a bfloat16 cache against the float32 reference with
# the routing followed, at the tiny preset: logits of magnitude 3 agree to
# about 0.04.
TOL_BF16 = 0.12
TOL_F32 = 2e-4


def backend(**kw):
    kw = {"seed": 5, "max_seq_len": SEQ, "piece": PIECE, **kw}
    return PanguMoeBackend(**kw)


def f32_params(be):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32),
                                  be._init_params())


def ids_of(n=N, seed=0, vocab=96):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def reference(be, ids, follow=None):
    with jax.default_matmul_precision("highest"):
        logits, chosen, gaps = fam.backend_forward(
            f32_params(be), be, ids, len(ids), follow=follow)
    return np.asarray(logits), chosen, gaps


def serve(be, ids, n_prompt):
    """Prefill ``ids[:n_prompt]`` by pieces, then decode the rest teacher-
    forced through the cache (lane 0 of a wave of two, the other padded).
    -> logits ``[len(ids), vocab]``, choices ``[expert layers, len(ids),
    top_k]``."""
    params = be.place_params(be._init_params())
    arena = be.init_arena(3)
    piece = jax.jit(be.piece_hidden_fn())
    hidden = jax.jit(be._decode_hidden_fn())
    rows = np.asarray([1], np.int32)
    logits, routes = [], []
    for st in range(0, n_prompt, be.piece):
        n = min(be.piece, n_prompt - st)
        buf = np.zeros((1, be.piece), np.int32)
        buf[0, :n] = ids[st:st + n]
        arena, x, route = piece(params, arena, rows, buf,
                                np.asarray([n], np.int32),
                                np.asarray([st], np.int32))
        logits.append(np.asarray(be._logits(params, x[:n])))
        routes.append(np.asarray(route)[:, :n])
    for t in range(n_prompt, len(ids)):
        arena = {**arena, "tok": arena["tok"].at[1].set(int(ids[t]))}
        arena, x = hidden(params, arena, np.asarray([1, 3], np.int32),
                          np.asarray([t, 0], np.int32))
        logits.append(np.asarray(be._logits(params, x))[:1])
        routes.append(np.stack([np.asarray(r)[:1] for r in x["route"]]))
    return np.concatenate(logits), np.concatenate(routes, axis=1)


# -- the served path against the plain reference, on logits -------------------

@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_float32_pieces_then_waves_match_the_reference_exactly_routed(
        attn_impl):
    """float32 weights, cache and matmuls: program and reference choose the
    same experts, and the logits of every position (two and a half pieces of
    prefill, absorbed decode behind them) are the full forward pass's."""
    be = backend(dtype="float32", attn_impl=attn_impl)
    ids = ids_of()
    want, chosen, _ = reference(be, ids)
    got, routes = serve(be, ids, 37)
    assert np.array_equal(np.sort(routes, -1), np.sort(chosen, -1))
    assert np.abs(got - want).max() < TOL_F32


def test_bfloat16_pieces_then_waves_match_the_reference_that_follows():
    """The served precision: the reference follows the program's own routing
    (top-k is discontinuous: a near-tie may fall either way in bfloat16) and
    the logits agree to bfloat16's rounding; left free, the reference picks
    the same experts but at near-ties."""
    be = backend()
    ids = ids_of()
    got, routes = serve(be, ids, 37)
    want, _, _ = reference(be, ids, follow=routes)
    assert np.abs(got - want).max() < TOL_BF16
    _, chosen, gaps = reference(be, ids)
    differ = (np.sort(routes, -1) != np.sort(chosen, -1)).any(-1)
    assert differ.mean() < 0.1


def test_absorbed_decode_is_unabsorbed_prefill_on_the_same_rows():
    """Position t's logits by a decode wave (q times W_kb, rows read as they
    lie, W_vb behind) and by a prefill piece that computes k_nope and v."""
    be = backend(dtype="float32")
    ids = ids_of(40, seed=2)
    by_prefill, _ = serve(be, ids, 40)
    by_decode, _ = serve(be, ids, 20)
    assert np.abs(by_prefill[20:] - by_decode[20:]).max() < TOL_F32


def test_a_wrong_cache_row_fails():
    be = backend(dtype="float32")
    ids = ids_of()
    want, _, _ = reference(be, ids)
    shifted = ids.copy()
    shifted[3] = (shifted[3] + 1) % 96
    got, _ = serve(be, shifted, 37)
    assert np.abs(got[10:] - want[10:]).max() > 100 * TOL_F32


def test_piece_attention_by_the_flash_kernel():
    """Per head q and k of nope + rope, v of another width: whole 128-lane
    tiles of each (the published 192 -> 256 and 128, narrowed in count, not
    in width)."""
    kw = dict(n_heads=2, nope_dim=192, rope_dim=64, v_dim=128, kv_rank=128,
              dtype="float32", max_seq_len=32, piece=16)
    ids = ids_of(30, seed=4)
    want, _ = serve(backend(attention_impl="einsum", **kw), ids, 30)
    got, _ = serve(backend(attention_impl="flash", **kw), ids, 30)
    assert np.abs(got - want).max() < 1e-3


def test_weights_are_bfloat16_values_in_either_form():
    w = SeededWeight((1, 2), (300, 70), 0.5, offset=1.0)
    a, b = np.asarray(w), np.asarray(w, np.float32)
    assert a.dtype.name == "bfloat16" and b.dtype == np.float32
    assert np.array_equal(a.astype(np.float32), b)
    assert abs(float(b.std()) - 0.5) < 0.02 and abs(float(b.mean()) - 1) < .02
    # An expert's weights are its own, whichever share holds it.
    whole = np.asarray(SeededWeight((1, 3), (4, 50, 6), 1.0, first=0))
    part = np.asarray(SeededWeight((1, 3), (2, 50, 6), 1.0, first=2))
    assert np.array_equal(whole[2:].astype(np.float32),
                          part.astype(np.float32))


# -- the share of an expert-parallel group --------------------------------------

def expert_layers_of(be):
    return [lp for lp in f32_params(be)["layers"] if "router" in lp]


def routed_part(be, lp, h):
    """What this share's held experts add, by the program."""
    lp = jax.tree_util.tree_map(jnp.asarray, lp)
    y, counts, _ = be._experts(lp, jnp.asarray(h), jnp.ones(len(h), bool),
                               experts.TILE_M_WAVE)
    return np.asarray(y), np.asarray(counts)


def _kimi_backend(**kw):
    from client_tpu.models.kimi_linear import KimiLinearBackend

    return KimiLinearBackend(**{"seed": 5, "max_seq_len": SEQ,
                                "piece": PIECE, **kw})


# (family, its backend, experts a share holds, the gate's scale): sixteen
# shares of one expert, and eight shares of two whose gate has a selection
# bias (models/kimi_linear.py; eight of 32 at the published widths).
SHARES = {"pangu_moe": (backend, 1, 2.5),
          "kimi_linear": (_kimi_backend, 2, 2.446)}


@pytest.mark.parametrize("name", sorted(SHARES))
def test_the_shares_add_up_to_the_uncut_layer(name):
    """Every share computes the terms of its own experts; all the routed
    parts and the shared expert counted once are the uncut reference's
    layer.  (The weights are the model's, whichever share holds them.)"""
    build, held, scale = SHARES[name]
    ref = family.load(name)
    kw = dict(dtype="float32", n_experts=16, experts_held=held, top_k=4)
    shares = [build(first_expert=s, **kw) for s in range(0, 16, held)]
    layers = [expert_layers_of(be)[0] for be in shares]
    h = np.random.default_rng(1).standard_normal((24, 64)).astype(np.float32)
    uncut = dict(layers[0])
    uncut["egu"] = np.concatenate([lp["egu"] for lp in layers])
    uncut["ed"] = np.concatenate([lp["ed"] for lp in layers])
    with jax.default_matmul_precision("highest"):
        want, chosen, _ = ref.expert_layer(
            {k: v if k in ("egu", "ed") else jnp.asarray(v)
             for k, v in uncut.items()}, jnp.asarray(h), top_k=4,
            scale=scale, first=0)
        shared = np.asarray(ref.swiglu(jnp.asarray(h), uncut["sgu"],
                                       uncut["sd"]))
    parts = [routed_part(be, lp, h) for be, lp in zip(shares, layers)]
    total = shared + sum(y for y, _ in parts)
    assert np.abs(total - np.asarray(want)).max() < 1e-4
    # Every pair is computed by exactly one share.
    assert sum(int(c[0]) for _, c in parts) == chosen.size


def skewed(be, lp, column, value):
    """A router whose ``column`` scores ``value`` for every token."""
    router = np.array(lp["router"])
    router[:, column] = 0.0
    lp = dict(lp, router=router)
    h = np.random.default_rng(3).standard_normal((40, 64)).astype(np.float32)
    h[:, 0] = 1.0
    router[0, column] = value          # h[:, 0] = 1: the logit is `value`
    return lp, h


@pytest.mark.parametrize("attn_impl", ["reference", "fused"])
def test_dropless_when_every_token_goes_to_one_held_expert(attn_impl):
    be = backend(dtype="float32", attn_impl=attn_impl)
    lp, h = skewed(be, expert_layers_of(be)[0], column=2, value=50.0)
    y, counts = routed_part(be, lp, h)
    with jax.default_matmul_precision("highest"):
        want, chosen, _ = fam.expert_layer(
            {k: v if k in ("egu", "ed") else jnp.asarray(v)
             for k, v in lp.items()}, jnp.asarray(h), top_k=be.top_k,
            scale=be.routed_scale, first=0)
        shared = np.asarray(fam.swiglu(jnp.asarray(h), lp["sgu"], lp["sd"]))
    assert (chosen == 2).sum() == len(h)            # every token chose it
    assert int(counts[1]) == len(h)                 # and none was dropped
    assert np.abs(y + shared - np.asarray(want)).max() < 1e-4


def test_an_expert_no_token_chose_is_not_read():
    """Its group has no rows, no tile names it, and its matrices may hold
    anything."""
    be = backend(dtype="float32", attn_impl="fused")
    lp, h = skewed(be, expert_layers_of(be)[0], column=1, value=-50.0)
    clean, counts = routed_part(be, lp, h)
    poisoned = dict(lp, egu=np.array(lp["egu"]), ed=np.array(lp["ed"]))
    poisoned["egu"][1] = np.nan
    poisoned["ed"][1] = np.nan
    y, counts_p = routed_part(be, poisoned, h)
    assert np.isfinite(y).all() and np.array_equal(y, clean)
    assert np.array_equal(counts, counts_p) and int(counts[2]) <= 3
    top_i = np.asarray(be.route(jax.tree_util.tree_map(jnp.asarray, lp),
                                jnp.asarray(h))[0])
    plan = plan_groups(jnp.where(top_i < 4, top_i, 4).reshape(-1).astype(
        jnp.int32), 4, 16, capacity_rows(40 * 4, 4, 16))
    assert int(plan["sizes"][1]) == 0
    used = np.asarray(plan["tile_expert"])
    assert 1 not in used        # beyond n_tiles the last used tile repeats


def test_padded_lanes_route_nowhere():
    be = backend(dtype="float32")
    lp = jax.tree_util.tree_map(jnp.asarray, expert_layers_of(be)[0])
    h = jnp.asarray(np.random.default_rng(7).standard_normal((8, 64)),
                    jnp.float32)
    live = jnp.asarray([True] * 3 + [False] * 5)
    _, counts, top_i = be._experts(lp, h, live, 16)
    held = np.asarray(top_i)[:3] < be.experts_held
    assert int(counts[0]) == int(held.sum())


# -- the kernels against their oracles -------------------------------------------

# heads, kv rank, rope lanes, lengths: the base case (lanes with an empty
# prefix, a full slot, a length on a block's edge); a head count that is no
# multiple of 8; values narrower than the row by more than one tile (64 of
# 384 lanes); live rows that end one short of and one past a block's edge;
# a full lane tile of heads on a one-tile row.
LATENT_CASES = {
    "base": (4, 32, 8, [0, 17, 63, 16, 0]),
    "six_heads": (6, 32, 8, [0, 17, 63, 16, 0]),
    "narrow_values": (4, 64, 200, [5, 17, 63, 16, 0]),
    "around_a_block_edge": (4, 32, 8, [15, 17, 31, 33, 47]),
    "128_heads": (128, 32, 8, [0, 17, 63, 16, 1]),
    # The walk over a lane's live blocks (PR 40): the wave's live blocks are
    # one stream through a ring of three buffers, fetched two ahead across
    # the lanes' ends.  No lane with a block; one lane alone; empty lanes
    # between long ones (the copies started across lanes skip them and are
    # not lost); the last lane the longest; every block of every slot live;
    # lengths on a block's edge (``around_a_block_edge`` has its two sides);
    # a slot (a fifth entry: its rows) of eight blocks for three buffers.
    "no_live_lane": (4, 32, 8, [0, 0, 0, 0, 0]),
    "one_lane": (4, 32, 8, [41]),
    "empty_lanes_between": (4, 32, 8, [61, 0, 0, 55, 3]),
    "last_lane_longest": (4, 32, 8, [3, 17, 0, 20, 63]),
    "full_slots": (4, 32, 8, [63, 63, 63, 63, 63]),
    "on_a_block_edge": (4, 32, 8, [16, 32, 48, 0, 33]),
    "eight_blocks": (4, 32, 8, [127, 70, 0, 9, 100], 128),
}


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("scanned", [False, True])
@pytest.mark.parametrize("case", sorted(LATENT_CASES))
def test_latent_kernel_parity(dtype, atol, scanned, case):
    """The query ``[B, W, H]`` in the cache's dtype and the result ``[B, V,
    H]``, against the oracle: the row written in place and exact, every
    other row bitwise kept."""
    rng = np.random.default_rng(0)
    h, rank, rp, lens, *slot_rows = LATENT_CASES[case]
    layers, slots, s = 2, 7, *(slot_rows or [64])
    lanes = len(lens)
    w = latent_row_width(rank, rp)
    c = jnp.asarray(rng.standard_normal((layers, slots, s, w)), dtype)
    q = jnp.asarray(0.25 * rng.standard_normal((lanes, w, h)), dtype)
    new = jnp.asarray(rng.standard_normal((lanes, w)), jnp.float32)
    rows = jnp.asarray([3, 0, 5, 1, 6][:lanes], jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    kw = dict(layer=None, layer_index=jnp.int32(1)) if scanned \
        else dict(layer=1)
    got_c, got = latent_wave_attention(
        c, q, new, rows, lens, value_dim=rank, block_s=16, interpret=True,
        **kw)
    want_c, want = reference_latent_attention(
        c, q, new, rows, lens, layer=1, value_dim=rank)
    assert got.shape == (lanes, rank, h) and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < atol
    assert bool((got_c == want_c).all())
    touched = np.zeros(c.shape[:3], bool)
    touched[1, np.asarray(rows), np.asarray(lens)] = True
    assert np.array_equal(np.asarray(got_c)[~touched],
                          np.asarray(c)[~touched])
    assert np.array_equal(
        np.asarray(got_c)[1, np.asarray(rows), np.asarray(lens)],
        np.asarray(new.astype(dtype)))


@pytest.mark.parametrize("slot_rows", [4096, 8192])
def test_latent_kernel_grid_is_the_lanes(slot_rows):
    """No grid axis over a slot's blocks, so no step without a block
    (PR 40): the grid is the wave's lanes whatever the slot holds, the cache
    goes in and out whole and in HBM (aliased), and its blocks of 512 rows,
    the size the chip chose, go through a ring of three VMEM buffers."""
    lanes, w = 8, 640
    c = jax.ShapeDtypeStruct((2, 9, slot_rows, w), jnp.bfloat16)
    q = jax.ShapeDtypeStruct((lanes, w, 128), jnp.bfloat16)
    new = jax.ShapeDtypeStruct((lanes, w), jnp.float32)
    i32 = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    jaxpr = jax.make_jaxpr(functools.partial(
        latent_wave_attention, layer=1, value_dim=512))(c, q, new, i32, i32)
    (call,) = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (lanes,)
    assert call.params["input_output_aliases"] == ((2, 0),)
    refs = [str(v.aval) for v in call.params["jaxpr"].invars]
    arena = f"Ref<any>{{bfloat16[2,9,{slot_rows},{w}]}}"
    assert refs.count(arena) == 2
    assert [r for r in refs if f"512,{w}]" in r] == [
        f"Ref<vmem>{{bfloat16[3,512,{w}]}}"]


def test_the_wave_query_is_what_the_kernel_used_to_round_to():
    """``_qkv`` scales in float32 and rounds to the cache's dtype: bit for
    bit the ``(q * sm_scale).astype(bfloat16)`` the kernel made of a float32
    ``[q_nope W_kb^T | q_rope | 0]`` until PR 33, in the kernel's layout."""
    be = backend()
    lp = jax.tree_util.tree_map(jnp.asarray, be._init_params()["layers"][1])
    x = {"h": jnp.asarray(np.random.default_rng(3).standard_normal((6, 64)),
                          jnp.float32)}
    pos = jnp.asarray([0, 1, 7, 30, 31, 63], jnp.int32)
    q, row = be._qkv(lp, x, pos)
    q_nope, q_rope, _, _ = be._queries_and_rows(lp, x["h"], pos)
    q_lat = be._heads_mm("bhn,hnr->bhr", q_nope, lp["wkb"])
    was = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((6, be.n_heads, be.row_width - be.kv_rank
                                   - be.rope_dim), jnp.float32)], axis=-1)
    was = (was * be.sm_scale).astype(jnp.bfloat16)           # [B, H, W]
    assert q.dtype == jnp.bfloat16 and q.shape == (6, be.row_width, 4)
    assert np.array_equal(np.asarray(q, np.float32),
                          np.asarray(was, np.float32).transpose(0, 2, 1))
    assert row.shape == (6, be.row_width) and row.dtype == jnp.float32


@pytest.mark.parametrize("tile_m,skew", [(8, False), (16, True)])
def test_grouped_matmul_parity(tile_m, skew):
    rng = np.random.default_rng(1)
    experts, k, n, pairs = 4, 32, 256, 40
    expert = rng.integers(0, experts + 2, pairs).clip(0, experts)
    if skew:
        expert[:] = np.where(expert < experts, 3, experts)
    rows = capacity_rows(pairs, experts, tile_m)
    plan = plan_groups(jnp.asarray(expert, jnp.int32), experts, tile_m, rows)
    x = jnp.asarray(rng.standard_normal((pairs, k)), jnp.float32)
    xs = jnp.zeros((rows + 1, k)).at[plan["dest"]].set(x)[:rows]
    w = jnp.asarray(rng.standard_normal((experts, k, n)), jnp.float32)
    got = grouped_matmul(xs, w, plan["tile_expert"], plan["n_tiles"],
                         tile_m=tile_m, tile_n=128, interpret=True)
    want = reference_grouped_matmul(xs, w, plan["padded"])
    used = int(plan["n_tiles"][0]) * tile_m
    assert float(jnp.abs(got[:used] - want[:used]).max()) < 1e-4
    sizes = np.asarray(plan["sizes"])
    assert sizes.tolist() == [int((expert == e).sum())
                              for e in range(experts)]
    for p in range(pairs):          # every pair's row is its own product
        if expert[p] < experts:
            assert np.allclose(got[int(plan["dest"][p])],
                               x[p] @ w[int(expert[p])], atol=1e-4)
        else:
            assert int(plan["dest"][p]) == rows


# -- the scheduler, end to end ----------------------------------------------------

MODEL = "pangu_t"
PLAN = [(3, 10), (20, 14), (40, 8), (17, 12)]


def stream(engine, prompt, max_tokens, model):
    tokens, err, done = [], [], threading.Event()

    def cb(resp):
        if resp.error is not None:
            err.append(resp.error)
            done.set()
        elif resp.final:
            done.set()
        else:
            tokens.append(int(resp.outputs["TOKEN"][0]))

    engine.async_infer(InferRequest(
        model_name=model, inputs={"INPUT_IDS": np.asarray(prompt, np.int32)},
        parameters={"max_tokens": max_tokens, "seed": 0}), cb)

    def join():
        assert done.wait(300), "stream did not finish"
        assert not err, err
        return tokens

    return join


def gen_profile(engine, model):
    sched = engine._schedulers[model]
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if not sched._streams and not sched._inflight \
                and sched._rec.open is sched._rec.span[spans.S_IDLE]:
            break
        time.sleep(0.005)
    snap = engine.profile_snapshot(model=model)
    return snap["models"][f"{model}:1"]["generative"]


@pytest.fixture(scope="module", params=["reference", "fused"])
def served(request):
    name = f"{MODEL}_{request.param}"
    be = backend(name=name, attn_impl=request.param, max_streams=4)
    repo = ModelRepository()
    repo.register_backend(be)
    engine = TpuEngine(repo)
    engine._schedulers[name].warmup()
    before = gen_profile(engine, name)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 96, n).tolist() for n, _ in PLAN]
    joins = [stream(engine, p, m, name) for p, (_, m) in zip(prompts, PLAN)]
    together = [j() for j in joins]
    alone = [stream(engine, p, m, name)() for p, (_, m) in zip(prompts, PLAN)]
    after = gen_profile(engine, name)
    yield be, prompts, together, alone, before, after
    engine.shutdown()


class TestScheduler:
    def test_together_equals_alone_token_for_token(self, served):
        _, _, together, alone, *_ = served
        assert together == alone
        assert [len(t) for t in together] == [m for _, m in PLAN]

    def test_the_reference_accepts_every_token(self, served):
        be, prompts, together, alone, *_ = served
        probe = {"prompts": prompts, "max_tokens": None,
                 "concurrent": together, "solo": alone}
        params = f32_params(be)

        def rows_fn(prompt, emitted):
            seq = np.asarray(prompt + emitted, np.int32)
            with jax.default_matmul_precision("highest"):
                logits, _, gaps = fam.backend_forward(params, be, seq[:-1],
                                                      len(emitted))
            return logits, gaps[len(seq) - 1 - len(emitted):]

        for p, (_, m) in zip(prompts, PLAN):     # one budget a stream
            probe["max_tokens"] = m
            one = {**probe, "prompts": [p],
                   "concurrent": [together[prompts.index(p)]],
                   "solo": [alone[prompts.index(p)]]}
            verdict = fam.judge(one, rows_fn, margin=TOL_BF16,
                                margin_tie=1.0, tie=0.05)
            assert verdict["ok"], verdict

    def test_what_a_wave_routed_reaches_the_counters(self, served):
        """The device's counts ride behind a wave's tokens: pairs held here,
        the busiest expert's and the experts touched move with the waves,
        within what the shapes allow."""
        be, _, _, _, before, after = served
        c = {k: after["counters"][k] - before["counters"][k]
             for k in after["counters"]}
        layers = be.n_layers - be.n_dense
        assert c["fetched_waves"] > 0 and c["expert_pairs_local"] > 0
        assert c["expert_pairs_local"] <= c["fetched_lanes_live"] * layers \
            * min(be.top_k, be.experts_held)
        assert c["experts_touched"] <= c["fetched_waves"] * layers \
            * be.experts_held
        assert c["expert_pairs_busiest"] <= c["expert_pairs_local"] \
            <= c["expert_pairs_busiest"] * be.experts_held
        assert c["experts_touched"] >= c["fetched_waves"]  # top 4 of 16, 4 held
        assert c["prefill_pieces"] >= sum(
            -(-n // PIECE) for n, _ in PLAN) * 2

    def test_pieces_are_counted_and_tokens_leave(self, served):
        _, _, together, _, before, after = served
        assert after["counters"]["first_tokens"] \
            - before["counters"]["first_tokens"] == 2 * len(PLAN)


def test_a_backend_that_declares_no_wave_stats_returns_tokens_alone():
    from client_tpu.models.generate import TinyGptBackend

    be = TinyGptBackend(name="plain")
    assert be.wave_stats == () and be.cache_leaves == ("k", "v")
    assert be.latent_attention is None


def test_an_unknown_wave_stat_is_refused():
    class Odd(PanguMoeBackend):
        wave_stats = ("no_such_counter",)

    repo = ModelRepository()
    repo.register_backend(Odd(name="odd"))
    with pytest.raises(Exception):
        TpuEngine(repo)._schedulers["odd"]


# -- the benchmark family: the comparison, the arithmetic, the readers ------------

CFG = {
    "family": "pangu_moe", "hidden_size": 7680, "num_attention_heads": 128,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "n_shared_experts": 1,
    "n_routed_experts": 16, "num_experts_per_tok": 8,
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "vocab_size": 19200,
    "serve": {"kwargs": {"n_experts": 256, "max_streams": 128},
              "expert_tile_rows": 16},
}


def test_the_configuration_file_is_the_catalog_row_but_for_its_cuts():
    import json

    with open(os.path.join(BENCH, "configs", "pangu_ultra_moe.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7680,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 25600000,
        "routed_scaling_factor": 2.5, "sandwich_norm": True,
        "tie_word_embeddings": False, "v_head_dim": 128,
        "vocab_size": 153600}
    differs = sorted(k for k, v in published.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in cfg["reduced"])
    for k in cfg["reduced"]:
        assert cfg["published"][k] == published[k]
    for key in CFG:
        if key not in ("family", "serve"):
            assert cfg[key] == CFG[key], key
    assert cfg["serve"]["expert_tile_rows"] == experts.TILE_M_WAVE
    assert fam.wave_rows(cfg) == capacity_rows(128 * 8, 16, 16) == 1264


def test_step_arithmetic_by_hand():
    m = fam._dims(CFG)
    assert m["attn"] == 196_575_232 and m["expert"] == 47_185_920
    assert m["dense"] == 424_673_280 and m["router"] == 1_966_080
    # One layer of the latent kernel: 128 lanes x 1450 rows of 576 values.
    flops, nbytes = fam.latent_attention(CFG, 128, 1450)
    assert flops == 2 * 128 * 1450 * 128 * (576 + 512)
    assert nbytes == 128 * 1451 * 576 * 2 + 128 * 128 * (576 + 512) * 4
    up = fam.expert_ffn(CFG, 64, 16, "up")
    down = fam.expert_ffn(CFG, 64, 16, "down")
    both = fam.expert_ffn(CFG, 64, 16)
    assert both == (up[0] + down[0], up[1] + down[1])
    assert both[0] == 2 * 64 * m["expert"]
    assert abs(both[1] - 16 * m["expert"] * 2) < 0.01 * both[1]
    # A wave's bytes are the weights but the untouched experts', and the
    # cache: 4.92e9 parameters x 2 B = 9.85 GB and 128 x 1450 x 1152 x 5.
    flops, nbytes = fam.decode_step(CFG, 128, 1450, 64, 16)
    weights = 2 * (5 * m["attn"] + m["dense"] + 4 * (m["shared"] + 16
                   * m["expert"]) + 7680 * 19200) + 4 * 4 * m["router"]
    assert abs(nbytes - weights - 5 * 128 * 1451 * 1152) < 0.01 * nbytes
    assert 9.5e9 < weights < 9.8e9        # the embedding is gathered


def snaps(counters_after, waves=None):
    zero = {k: 0 for k in counters_after}

    def snap(c, n):
        model = {"generative": {"spans": {}, "counters": c},
                 "decode_waves": [{"bucket": b, "waves": w * n,
                                   "device_s": 0.0}
                                  for b, w in (waves or {}).items()]}
        return {"profile": {"models": {"pangu_ultra_moe:1": model}}}

    return snap(zero, 0), snap(counters_after, 1)


RECORDED = {"fetched_waves": 1000, "fetched_lanes_live": 126_000,
            "fetched_positions_valid": 126_000 * 1400,
            "expert_pairs_local": 252_000, "expert_pairs_busiest": 32_000,
            "experts_touched": 62_720}


def read(name, ctx):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "m_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


@pytest.mark.parametrize("name,want", [
    ("expert_rows_per_expert", 252_000 / 1000 / 4 / 16),       # 3.9375
    ("expert_imbalance", 32_000 * 16 / 252_000),               # 2.03
    ("experts_touched_share", 100 * 62_720 / 1000 / 4 / 16),   # 98.0
])
def test_counter_readers_on_recorded_counters(name, want):
    before, after = snaps(RECORDED)
    ctx = {"cfg": CFG, "snap_before": before, "snap_after": after}
    assert read(name, ctx) == pytest.approx(want)
    # The parent has no such counters: nothing is read, nothing raised.
    bare_b, bare_a = snaps({"fetched_waves": 1000,
                            "fetched_lanes_live": 126_000})
    assert read(name, {"cfg": CFG, "snap_before": bare_b,
                       "snap_after": bare_a}) is None
    assert read(name, {"cfg": CFG}) is None


def trace_of(ops, steps=200):
    return {"modules": {"jit_decode": {"count": steps, "mean_ms": 16.0}},
            "device_ops": ops}


def test_roofline_readers_multiply_by_the_calls_they_found():
    import roofline

    before, after = snaps(RECORDED, waves={128: 900, 64: 100})
    peaks = roofline.peaks_for("TPU v5 lite")
    pairs, touched = 252_000 / 1000 / 4, 62_720 / 1000 / 4
    up = roofline.min_seconds(*fam.expert_ffn(CFG, pairs, touched, "up"),
                              peaks)[0]
    down = roofline.min_seconds(*fam.expert_ffn(CFG, pairs, touched, "down"),
                                peaks)[0]
    att = roofline.min_seconds(*fam.latent_attention(CFG, 126, 1400),
                               peaks)[0]
    ops = [["fusion.7_bf16_128_18432_", 0.9],
           ["grouped_matmul.8_f32_1264_4096_", 0.30],
           ["grouped_matmul.10_f32_1264_4096_", 0.31],
           ["grouped_matmul.9_f32_1264_7680_", 0.16],
           ["grouped_matmul.3_f32_5120_4096_", 0.2],      # a prefill piece's
           ["grouped_matmul.4_f32_752_4096_", 0.1],       # a 64-lane wave's
           ["latent_wave_attention.5_bf16_5_129_4096_640_", 0.08],
           ["latent_wave_attention.6_bf16_5_129_4096_640_", 0.09]]
    ctx = {"cfg": CFG, "snap_before": before, "snap_after": after,
           "trace": trace_of(ops), "device": {"kind": "TPU v5 lite"}}
    got = read("expert_ffn_roofline", ctx)
    assert got == pytest.approx(
        100 * 200 * 0.9 * (2 * up + down) / (0.30 + 0.31 + 0.16))
    assert 0 < got < 100
    got = read("latent_attn_roofline", ctx)
    assert got == pytest.approx(100 * 200 * 2 * att / 0.17)
    # Nothing of the kernels among the ten longest, or no trace: nothing.
    for trace in (trace_of(ops[:1]), None):
        for name in ("expert_ffn_roofline", "latent_attn_roofline"):
            assert read(name, {**ctx, "trace": trace}) is None


def test_step_mix_reads_the_touched_experts_not_all_sixteen():
    before, after = snaps(RECORDED)
    ctx = {"cfg": CFG, "snap_before": before, "snap_after": after}
    (count, (flops, nbytes)), = fam.step_mix(ctx)
    assert count == 1000
    all_sixteen = fam.decode_step(CFG, 126, 1400, 63, 16)[1]
    assert nbytes < all_sixteen
    assert nbytes == fam.decode_step(CFG, 126, 1400, 63, 15.68)[1]
    assert fam.step_mix({"cfg": CFG}) is None


def test_check_rejects_a_corrupted_stream():
    """The judge on a tiny model's own streams: a stream's tokens pass, the
    same stream with a token replaced does not."""
    be = backend(dtype="float32")
    params = f32_params(be)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, 96, 12).tolist()
    seq = list(prompt)
    for _ in range(6):
        with jax.default_matmul_precision("highest"):
            logits, _, _ = fam.backend_forward(
                params, be, np.asarray(seq, np.int32), 1)
        seq.append(int(np.argmax(np.asarray(logits)[0])))
    good = seq[len(prompt):]
    probe = {"prompts": [prompt], "max_tokens": 6, "concurrent": [good],
             "solo": [good]}
    with jax.default_matmul_precision("highest"):
        assert fam.check(params, probe, be)["ok"]
        bad = list(good)
        bad[2] = (bad[2] + 1) % 96
        verdict = fam.check(params, {**probe, "concurrent": [bad]}, be)
    assert not verdict["ok"] and verdict["rows_over_their_margin"] >= 1


def test_a_tie_row_is_judged_by_the_tie_margin():
    rows = np.zeros((2, 8), np.float32)
    rows[:, 0] = 1.0
    rows[:, 1] = 0.8                     # the emitted token: 0.2 below

    def rows_fn(prompt, emitted, gaps=(np.inf, np.inf)):
        return rows, np.asarray(gaps)

    probe = {"prompts": [[1, 2]], "max_tokens": 2, "concurrent": [[1, 1]],
             "solo": [[1, 1]]}
    free = fam.judge(probe, rows_fn, margin=0.1, margin_tie=0.5, tie=0.02)
    assert not free["ok"] and free["routing_tie_rows"] == 0
    tied = fam.judge(probe, lambda p, e: rows_fn(p, e, (0.001, 0.001)),
                     margin=0.1, margin_tie=0.5, tie=0.02)
    assert tied["ok"] and tied["routing_tie_rows"] == 4
    assert tied["worst_margin_below_max_at_ties"] == pytest.approx(0.2)


def test_a_launch_of_another_model_imports_none_of_it():
    """The zoo registers a builder that imports the decoder when it is
    built: importing every zoo module brings in neither the model nor its
    grouped matmul."""
    import subprocess

    code = ("import sys, client_tpu.models as zoo; zoo._import_all(); "
            "assert 'pangu_moe' in zoo.model_names(); "
            "hit = [m for m in sys.modules if 'pangu_moe' in m "
            "or 'grouped_matmul' in m]; assert not hit, hit")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
