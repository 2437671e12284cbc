"""What PR 59 added to the yardstick for ``granite4_h_micro.helpdesk``, on the
CPU: the configuration file against the catalog's row and against the arena
the backend builds from it, the cell's entries in ``BENCHMARK.json`` and its
traffic file against the issue's parameters, the family's arithmetic and
comparison, the controls the comparison must refuse, the new reader
(``benchmark/testdata/check_granite_hybrid.py``) and, marked ``slow`` (a
server, a reference and a load generator for most of a minute, beside tier-1's
timing-sensitive tests: ``python -m pytest
tests/test_granite_hybrid_rehearsal.py`` runs it), the cell end to end at the
configuration's ``rehearse_cpu`` sizes.  A rehearsal proves nothing about the
chip."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "testdata"))

import family  # noqa: E402

from client_tpu.models.granite_hybrid import GraniteHybridBackend  # noqa: E402

fam = family.load("granite_hybrid")
kimi = family.load("kimi_linear")
CELL, CONFIG = "granite4_h_micro.helpdesk", "granite4_h_micro"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


def _config():
    from traffic import load_json

    return load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


def test_the_new_reader_and_the_familys_arithmetic_check_out():
    import check_granite_hybrid

    assert check_granite_hybrid.main() == 0


@pytest.mark.slow
def test_the_new_cell_rehearses_on_the_cpu():
    import check_granite_hybrid

    assert check_granite_hybrid.rehearse() == 0


# -- the configuration -------------------------------------------------------------

def test_the_configuration_file_is_the_catalog_row_key_by_key():
    cfg = _config()
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "granite-4.0-h-micro")
    assert cfg["source"] == row["source_url"]
    assert {k: cfg.get(k) for k in row["config"]} == row["config"]
    assert cfg["reduced"] == []
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 40
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    for key in ("assumed", "departures", "deployment", "memory",
                "harness_names"):
        assert cfg[key], key
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert (entry["reduced"], entry["source"], entry["file"]) == (
        [], cfg["source"], f"benchmark/configs/{CONFIG}.json")


def test_the_backend_built_from_the_file_is_the_issues_arena():
    from serve import backend_kwargs

    cfg = _config()
    be = GraniteHybridBackend(name="g", **backend_kwargs(cfg, 7, None))
    assert be.layer_kinds.count("state") == 36
    assert be.layer_kinds.count("rows") == 4
    assert (be.d_inner, be.conv_dim, be.pack, be.chunk, be.head_dim) == (
        4096, 4352, 2, 256, 64)
    assert (be.embedding_multiplier, be.residual_multiplier, be.attn_scale,
            be.logits_scaling) == (12.0, 0.22, 1 / 64, 8.0)
    assert (be.max_streams, be.max_seq_len, be.prefill_piece) == (
        80, 2048, (512, 2))
    arena = jax.eval_shape(lambda: be.init_arena(be.max_streams))
    assert arena["s"].shape == (36, 81, 32, 128, 128)
    assert arena["s"].dtype == jnp.float32
    assert arena["conv"].shape == (36, 81, 3 * 4352)
    assert arena["k"].shape == arena["v"].shape == (4, 81, 2048, 512)
    # A slot: 36 states of 2 097 152 B, 36 tails of 26 kB, 16.8 MB of rows.
    slot = {k: int(np.prod(a.shape[2:])) * a.shape[0] * a.dtype.itemsize
            for k, a in arena.items() if k != "tok"}
    assert slot["s"] == 36 * 2_097_152
    assert slot["conv"] == 36 * 26_112
    assert slot["k"] + slot["v"] == 4 * 2048 * 2048 == 16_777_216
    cache = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in arena.values())
    assert 7.54e9 < cache < 7.56e9                   # the issue's 7.55 GB
    n_params = sum(int(np.prod(w.shape)) for w in jax.tree_util.tree_leaves(
        be._init_params(), is_leaf=lambda w: hasattr(w, "shape")))
    assert 3.19e9 < n_params < 3.195e9               # the issue's 3.19B
    assert f"{n_params / 1e6:.1f}M" in cfg["memory"]["weights_bytes"]
    assert 13.9e9 < cache + 2 * n_params < 13.97e9
    assert be.stream_record == 1 + 8


# -- the cell ------------------------------------------------------------------------

def test_the_cell_is_the_issues():
    from traffic import load_json

    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert {k: cell[k] for k in ("name", "config", "traffic", "chips")} == {
        "name": CELL, "config": CONFIG, "traffic": "helpdesk", "chips": 1}
    assert len(cell["why"]) <= 200
    t = load_json(os.path.join(BENCH, "traffic", "helpdesk.json"))
    assert (t["loop"], t["clients"], t["cycle_requests"], t["preroll_s"],
            t["stagger_s"], t["trace_seconds"], t["max_model_len"]) == (
        "closed", 80, 160, 30, 20, 4, 2048)
    assert t["prompt_len"] == {"dist": "uniform", "min": 64, "max": 1024}
    assert t["output_len"] == {"dist": "loguniform", "min": 128, "max": 1024}
    assistant = load_json(os.path.join(BENCH, "traffic", "assistant.json"))
    for key in ("warmup", "warmup_rounds", "server_args", "step_module",
                "trace_end_margin_s", "workers", "rows_per_request"):
        assert t[key] == assistant[key], key
    lens = t["probe_prompt_lens"]
    assert len(lens) == 4 and min(lens) < 256 and max(lens) > 1024
    assert max(lens) <= 1100
    cfg = _config()
    assert cfg["wire"]["endpoint"] == "generate_stream"
    assert cfg["serve"]["kwargs"]["max_streams"] == t["clients"]


def test_the_metrics_that_list_the_cell():
    by = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert by["hybrid_dense_roofline.itl"] == {"name": "hybrid_dense_roofline.itl", "unit": "%",
                   "better": "higher", "source": "device_trace",
                   "layer": "kernels", "moves": "itl_mean_ms",
                   "workloads": [CELL]}
    for name in ("step_mfu_roofline.itl", "step_device_ms.itl",
                 "ssm_state_roofline.itl", "decode_attn_roofline.itl",
                 "state_bytes_share.obs", "prefill_padded_position_share.itl",
                 "kv_live_share.itl", "arena_live_share.itl",
                 "hbm_peak_bytes.itl", "device_idle_share.itl",
                 "gaps_behind_prefill_share.obs", "prefill_gap_cost_ms.itl",
                 "piece_roofline.itl",
                 "startup_compile_s.setup", "startup_trace_s.setup",
                 "startup_lower_s.setup", "startup_model_load_s.setup",
                 "startup_first_run_s.setup", "setup_unspanned_s.setup"):
        assert CELL in by[name]["workloads"], name
    # No piece carries a wave, there is no expert and no ring: their readers
    # do not list the cell.
    for name in ("wave_carried_share.itl", "decode_attn_all_roofline.itl",
                 "piece_wave_roofline.itl", "expert_mlp_roofline.itl",
                 "window_attn_roofline.itl", "loop_dense_roofline.itl"):
        assert CELL not in by[name]["workloads"], name
    itl = next(m for m in MANIFEST["end_to_end"] if m["name"] == "itl_mean_ms")
    assert CELL in itl["workloads"] and itl["bound"] == 0.09
    for metric in MANIFEST["per_layer"]:
        if CELL in metric.get("workloads", ()):
            assert os.path.exists(os.path.join(
                BENCH, "metrics", metric["name"].rsplit(".", 1)[0] + ".py")
            ) or os.path.exists(os.path.join(
                BENCH, "metrics", metric["name"] + ".py")), metric["name"]


def test_step_arithmetic_by_hand():
    """The issue's reckoning of a wave of 80 lanes: 36 states a lane read
    and written, 12 GB, beside 6.4 GB of weights and under 0.6 GB of rows."""
    cfg = _config()
    m = fam._dims(cfg)
    assert m["mamba_proj"] + m["mamba_small"] + 2048 == 25_849_280
    assert m["attn"] + 2048 == 10_487_808 and m["ffn"] + 2048 == 50_333_696
    states, rows = fam.cache_bytes(cfg, 80, 80 * 900)
    assert states == 80 * 36 * 2 * 2_097_152
    assert rows == 80 * 900 * 4 * 2 * 1024 < 0.6e9
    flops, weights = fam.wave_dense(cfg, 80)
    assert weights == 2 * (36 * m["mamba_proj"] + 4 * m["attn"]
                           + 40 * m["ffn"] + 2048 * 100352)
    assert flops == 80 * weights
    # A piece of 512 positions: 3.1 TFLOP of products, and 36 chunked scans
    # at the published chunk (257 / 2 pairs a position within a chunk, the
    # carried state read and entered) that add 59 GFLOP, 1.9%; 36 states of
    # 2 MB read and written beside the layers' weights.
    products = 2 * 512 * (36 * m["mamba_proj"] + 4 * m["attn"]
                          + 40 * m["ffn"])
    scan = fam.chunk_scan(cfg, 512)
    assert scan == 512 * (257 * (128 + 4096) + 4 * 4096 * 128)
    piece, moved = fam.piece_step(cfg, 512, 0, 0, 1)
    assert piece == products + 36 * scan and 3.0e12 < piece < 3.3e12
    assert 0.018 < 36 * scan / products < 0.02
    assert moved == products / 512 + 36 * 2 * 2_097_152
    # The triangle of a whole prompt of 512 in the four attention layers.
    pairs = 4 * 512 * 513 // 2
    with_pairs, _ = fam.piece_step(cfg, 512, 0, pairs, 1)
    assert with_pairs - piece == 4 * pairs * 32 * 64


# -- the comparison ------------------------------------------------------------------

def _judged(**fault):
    """``judge`` on one hand-made stream whose served logits are the
    reference's, moved by a fault."""
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(4, 20)) * 0.5
    toks = rows.argmax(-1).tolist()
    served = np.concatenate([rows[np.arange(4), toks][:, None],
                             rows[:, :8]], axis=1).astype(np.float32)
    served = served + np.float32(fault.get("offset", 0.0))
    if "one" in fault:
        served[2, 3] += fault["one"]
    if "margin" in fault:
        rows[1, (toks[1] + 1) % 20] = rows[1, toks[1]] + fault["margin"]
    rec = np.zeros((3 + 4 - 1, 9), np.int32)
    rec[2:] = served.view(np.int32)
    probe = {"prompts": [[1, 2, 3]], "max_tokens": 4,
             "concurrent": [toks], "solo": [toks],
             "concurrent_record": [rec.tolist()],
             "solo_record": [rec.tolist()]}
    return kimi.judge(probe, lambda p, e, w: (rows, np.zeros(6)), 0,
                      margin=fam.MARGIN, logit_rms_alone=fam.LOGIT_RMS_ALONE,
                      logit_rms_together=fam.LOGIT_RMS_TOGETHER,
                      logit_max=fam.LOGIT_MAX, tie=0.0)


@pytest.mark.parametrize("fault,ok", [
    ({}, True),
    ({"offset": 2 * fam.LOGIT_RMS_TOGETHER}, False),
    ({"offset": fam.LOGIT_RMS_ALONE / 2}, True),
    ({"one": 2 * fam.LOGIT_MAX}, False),
    ({"margin": 2 * fam.MARGIN}, False),
])
def test_the_comparison_fails_by_each_of_its_limits(fault, ok):
    assert _judged(**fault)["ok"] is ok


# -- the controls ----------------------------------------------------------------------

KW = dict(seed=5, max_seq_len=64, piece=16, chunk=8)
IDS = np.random.default_rng(9).integers(0, 96, 30).astype(np.int32)


def _walk(be, n_prompt=21):
    """The served path's logits of every position: pieces, then waves."""
    params, arena = be.place_params(be._init_params()), be.init_arena(1)
    piece, hidden = (jax.jit(be.piece_hidden_fn()),
                     jax.jit(be._decode_hidden_fn()))
    out = []
    for st in range(0, n_prompt, be.piece):
        n = min(be.piece, n_prompt - st)
        buf = np.zeros((1, be.piece), np.int32)
        buf[0, :n] = IDS[st:st + n]
        arena, x, _ = piece(params, arena, np.zeros(1, np.int32), buf,
                            np.asarray([n], np.int32),
                            np.asarray([st], np.int32))
        out.append(np.asarray(be._logits(params, x[:n])))
    for t in range(n_prompt, len(IDS)):
        arena = {**arena, "tok": arena["tok"].at[0].set(int(IDS[t]))}
        arena, x = hidden(params, arena, np.zeros(1, np.int32),
                          np.asarray([t], np.int32))
        out.append(np.asarray(be._logits(params, x)))
    return np.concatenate(out)


@pytest.fixture(scope="module")
def served_errors():
    """max |served - reference| at the tiny preset, by dtype."""
    out = {}
    for dtype in ("float32", "bfloat16"):
        be = GraniteHybridBackend(dtype=dtype, **KW)
        params = jax.tree_util.tree_map(
            lambda a: np.asarray(a, np.float32), be._init_params())
        want = np.asarray(fam.backend_forward(params, be, IDS, len(IDS)))
        out[dtype] = (params, want, np.abs(_walk(be) - want).max())
    return out


def test_the_eleven_controls_are_the_issues_and_one_precision_more():
    import granite_hybrid_controls as controls

    assert sorted(controls.CONTROLS) == sorted([
        "embedding_1", "residual_1", "attention_1", "logits_1", "sqrt_scale",
        "rotated", "untied_head", "norm_groups_8", "gate_after_norm",
        "bf16_state", "e4m3"])


@pytest.mark.parametrize("which", [
    "embedding_1", "residual_1", "attention_1", "logits_1", "sqrt_scale",
    "rotated", "untied_head", "norm_groups_8", "gate_after_norm",
    "bf16_state", "e4m3"])
def test_a_control_is_refused_at_the_tiny_preset(which, served_errors):
    """Each control is the served backend with one thing wrong, on the same
    weights; the reference it is judged by stays the published model
    (``published``), and its logits lie further from the reference's than
    the served program's by a wide factor: ten times in float32 for a wrong
    model and for a bfloat16 state (what it adds drowns in bfloat16 products
    at this size: 36 layers of 64 x 64 x 128 tell it on the chip), half
    again in bfloat16 for float8 operands."""
    import granite_hybrid_controls as controls

    precision = which == "e4m3"
    dtype = "bfloat16" if precision else "float32"
    wrong = controls.CONTROLS[which](dtype=dtype, **KW)
    assert isinstance(wrong, GraniteHybridBackend)
    params, want, served = served_errors[dtype]
    if which == "untied_head":
        params = {**params, "head": np.asarray(
            wrong._init_params()["head"], np.float32)}
    again = np.asarray(fam.backend_forward(params, wrong, IDS, len(IDS)))
    assert np.array_equal(again, want)       # the reference does not move
    off = np.abs(_walk(wrong) - want).max()
    assert off > (1.5 * served if precision else max(10 * served, 2e-5)), (
        which, off, served)
    if which == "bf16_state":
        assert jax.eval_shape(lambda: wrong.init_arena(2))["s"].dtype \
            == jnp.bfloat16
