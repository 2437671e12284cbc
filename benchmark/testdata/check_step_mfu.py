#!/usr/bin/env python3
"""Checks ``step_mfu_roofline.itl``: the whole step's share of the roofline,
read from the window's counters and the harness's clock.

(a) Each family's cost of a prefill program against arithmetic written out
    here at the cell's published widths: one program of 512 valid positions
    from a prompt's start that also ends it (its head runs).
(b) The reader on a hand-made context (counters, ``t0``, ``t1``) equals the
    sum worked out here: waves x a wave's least seconds + the programs'
    least seconds, over the window.
(c) The same context with its trace's modules renamed (``jit_decode`` ->
    ``jit_piece_wave``), or with no trace at all, reads the same number; a
    snapshot's own clock, where it has one, is the denominator.
(d) ``gpt2_small``: a wave of bucket 48 with 33 live lanes costs 33 lanes'
    rows, not 48; its prefills the prompts' own positions.
(e) The guard of ``cohere_moe._traced_waves`` fires at 25 lanes a wave of 24
    slots and not at 24.
(f) The manifest: every cell reports ``step_mfu_roofline.itl``, none reports
    ``step_roofline.itl``, and every family a configuration names has a
    ``prefill_work`` and a ``step_mix``.

Run by ``check_readers.main()`` (and so by the tier-1 test that runs it).
"""

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import family  # noqa: E402
import reduce  # noqa: E402
import roofline  # noqa: E402
from run import load_reader as reader  # noqa: E402  (by manifest name)
from traffic import load_json  # noqa: E402

METRIC, OLD = "step_mfu_roofline.itl", "step_roofline.itl"
KIND = "TPU v5 lite"
FLOPS, BYTES = 197e12, 819e9


def check(ok, what):
    print(("ok   " if ok else "FAIL ") + what)
    return 0 if ok else 1


def near(a, b, tol=1e-9):
    return a is not None and abs(a - b) <= tol * max(1.0, abs(b))


def config(name):
    return load_json(os.path.join(BENCH, "configs", name + ".json"))


# -- (a) a prefill program by hand --------------------------------------------

TRI = 512 * 513 // 2        # the pairs one layer scores over 512 positions


def by_hand():
    """{configuration: (arguments of ``piece_step``, flops, bytes)}: 512
    valid positions from a prompt's start, one program, its head run."""
    out = {}
    # smallthinker_21b: 8 layers (2 global, 6 window); attention 2560 x 128 x
    # (2 x 28 + 2 x 4); a float32 router over 64; 64 ReGLU experts of 768, all
    # held, 6 a position; an untied head over 151936.
    attn, router, expert = 2560 * 128 * 64, 2560 * 64, 3 * 2560 * 768
    out["smallthinker_21b"] = (
        (512, 6 * TRI, 2 * TRI, 1, 1),
        2 * 512 * 8 * (attn + router + 6 * expert)
        + 4 * 8 * TRI * 28 * 128 + 2 * 2560 * 151936,
        8 * ((attn + 64 * expert) * 2 + router * 4) + 2560 * 151936 * 2)
    # pangu_ultra_moe: 5 layers (1 dense of 18432, 4 expert layers); latent
    # attention (q 7680 -> 1536 -> 128 x 192; kv 7680 -> 576; 512 -> 128 x
    # 256; o 128 x 128 -> 7680); a float32 router over 256; 16 held experts
    # of 2048 (8 / 256 x 16 = 0.5 a position) and one shared; head 19200.
    attn = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256
            + 128 * 128 * 7680)
    dense, expert, router = 3 * 7680 * 18432, 3 * 7680 * 2048, 7680 * 256
    out["pangu_ultra_moe"] = (
        (512, 0, 5 * TRI, 1, 1),
        2 * 512 * (5 * attn + dense + 4 * (expert + router + 0.5 * expert))
        + 2 * 5 * TRI * 128 * (128 + 64 + 128) + 2 * 7680 * 19200,
        (5 * attn + dense) * 2 + 4 * ((expert + 16 * expert) * 2 + router * 4)
        + 7680 * 19200 * 2)
    # kimi_linear: 8 layers (6 KDA, 2 latent; 1 dense of 9216, 7 expert
    # layers); latent attention without a query rank (q 2304 -> 32 x 192; kv
    # 2304 -> 576; 512 -> 32 x 256; o 32 x 128 -> 2304); a KDA layer's
    # projections 4 x 2304 x 4096 + 2 x (2304 x 128 + 128 x 4096) + 2304 x 32
    # + 3 x 4096 x 4 taps; router over 256; 32 held experts of 1024 (1 a
    # position) and one shared; head 20480.
    latent = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304
    kda = (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
           + 3 * 4096 * 4)
    dense, expert, router = 3 * 2304 * 9216, 3 * 2304 * 1024, 2304 * 256
    out["kimi_linear"] = (
        (512, 0, 2 * TRI, 1, 1),
        2 * 512 * (2 * latent + 6 * kda + dense
                   + 7 * (expert + router + 1 * expert))
        + 2 * 2 * TRI * 32 * (128 + 64 + 128) + 2 * 2304 * 20480,
        (2 * latent + 6 * kda + dense) * 2
        + 7 * ((expert + 32 * expert) * 2 + router * 4) + 2304 * 20480 * 2)
    # nemotron3_nano_30b: MEMEM*EMEMEM* = 6 M, 5 E, 2 *; an M layer 2688 x
    # (4096 + 6144 + 64) in, 4096 x 2688 out, a convolution of 4 taps and a
    # bias over 6144, 4096 of norm; attention 2688 x 128 x (2 x 32 + 2 x 2);
    # router over 128; 64 held un-gated experts of 1856 (6 / 128 x 64 = 3 a
    # position) and a shared one of 3712; head 65536.
    mamba = 2688 * (4096 + 6144 + 64) + 4096 * 2688 + 6144 * 5 + 4096
    attn, router = 2688 * 128 * 68, 2688 * 128
    shared, expert = 2 * 2688 * 3712, 2 * 2688 * 1856
    out["nemotron3_nano_30b"] = (
        (512, 0, 2 * TRI, 1, 1),
        2 * 512 * (6 * mamba + 2 * attn + 5 * (shared + router + 3 * expert))
        + 4 * 2 * TRI * 32 * 128 + 2 * 2688 * 65536,
        (6 * mamba + 2 * attn) * 2
        + 5 * ((shared + 64 * expert) * 2 + router * 4) + 2688 * 65536 * 2)
    # ouro_2b6: 12 layers x 4 passes over one set of weights; a layer 2048 x
    # 128 x (2 x 16 + 2 x 16) + 3 x 2048 x 5632; head 49152.
    layer = 2048 * 128 * 64 + 3 * 2048 * 5632
    out["ouro_2b6"] = (
        (512, 0, 48 * TRI, 1, 1),
        2 * 512 * 48 * layer + 4 * 48 * TRI * 16 * 128 + 2 * 2048 * 49152,
        48 * layer * 2 + 2048 * 49152 * 2)
    # command_a_plus (the accepted count, here for the record): 4 layers (3
    # window, 1 full); attention 4096 x 128 x (2 x 128 + 2 x 8); router over
    # 128; 4 shared and 16 held experts of 4096 (8 / 128 x 16 = 1 a
    # position); a tied head over 32768.
    attn, router, expert = 4096 * 128 * 272, 4096 * 128, 3 * 4096 * 4096
    out["command_a_plus"] = (
        (512, 3 * TRI, 1 * TRI, 1, 1),
        2 * 512 * 4 * (attn + router + 4 * expert + 1 * expert)
        + 4 * 4 * TRI * 128 * 128 + 2 * 4096 * 32768,
        4 * ((attn + 4 * expert + 16 * expert) * 2 + router * 4)
        + 4096 * 32768 * 2)
    return out


def pieces() -> int:
    status = 0
    for name, (args, flops, nbytes) in by_hand().items():
        cfg = config(name)
        got = family.load(cfg["family"]).piece_step(cfg, *args)
        status |= check(
            near(got[0], flops, 1e-12) and near(got[1], nbytes, 1e-12),
            f"{name}: a program of 512 valid positions that ends its prompt: "
            f"{got[0] / 1e12:.4f} TFLOP, {got[1] / 1e9:.4f} GB "
            f"(by hand {flops / 1e12:.4f}, {nbytes / 1e9:.4f})")
    # evabyte_6b5: 8 layers of 4 x 4096^2 + 3 x 4096 x 11008, a head of 320
    # x 8; 2048 valid bytes of one piece after one earlier window (128
    # summaries), one program.
    cfg = config("evabyte_6b5")
    w = 8 * (4 * 4096 * 4096 + 3 * 4096 * 11008) + 4096 * 2560
    pairs = 2048 * 2049 // 2 + 2048 * 128
    got = family.load("evabyte").pieces_useful(cfg, 2048, pairs, 1, 1)
    status |= check(
        near(got[0], 8 * (2 * 2048 * (w - 4096 * 2560) / 8 + 4 * pairs * 4096)
             + 2 * 4096 * 2560, 1e-12)
        and near(got[1], w * 2 + 2048 * (4096 * 2 + 4), 1e-12),
        f"evabyte_6b5: a piece of 2048 valid bytes behind 128 summaries: "
        f"{got[0] / 1e12:.4f} TFLOP, {got[1] / 1e9:.4f} GB")
    # gpt2_small: 12 layers of 4 x 768^2 + 2 x 768 x 3072 and a head of
    # 50257, float32; two prompts of 700 and 900 tokens in one program.
    cfg = config("gpt2_small")
    w = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 50257
    tri = 700 * 701 // 2 + 900 * 901 // 2
    got = family.load("gpt").prefill_step(cfg, 1600, tri, 2, 1)
    status |= check(
        near(got[0], 12 * (2 * 1600 * 7077888 + 4 * tri * 768)
             + 2 * 2 * 768 * 50257, 1e-12)
        and near(got[1], w * 4 + 1600 * 768 * 4 + 12 * 2 * 1600 * 768 * 4
                 + 1600 * 4, 1e-12),
        f"gpt2_small: prompts of 700 and 900 tokens in one program, at "
        f"their own positions: {got[0] / 1e12:.4f} TFLOP, "
        f"{got[1] / 1e9:.4f} GB")
    return status


# -- (b), (c) the reader on a hand-made context -------------------------------

def snap(counters, programs, t=None):
    s = {"profile": {"models": {"m:1": {"generative": {
        "spans": {"gen.prefill_dispatch": {
            "count": programs, "total_ns": 0, "max_ns": 0}},
        "counters": counters}}}}}
    if t is not None:
        s["t"] = t
    return s


# 1000 waves of 17 live lanes at 800 positions, four passes each; 100 piece
# programs of 40 000 valid positions in all, 60 of them ending a prompt; a
# window of 50 s.
OURO = dict(fetched_waves=1000, fetched_lanes_live=17_000,
            fetched_lanes_padded=1_000,
            fetched_positions_valid=13_600_000,
            fetched_rows_global=48 * 13_600_000, fetched_passes=4_000,
            prefill_positions_valid=40_000, prefill_heads=60,
            prefill_pieces=100)


def ouro_ctx(trace, clock=False):
    cfg = config("ouro_2b6")
    return {"cfg": cfg, "traffic": {"step_module": "jit_decode"},
            "device": {"kind": KIND}, "t0": 100.0, "t1": 150.0,
            "seconds": 50.0, "trace": trace,
            "snap_before": snap(dict.fromkeys(OURO, 0), 0,
                                99.9 if clock else None),
            "snap_after": snap(OURO, 100, 150.7 if clock else None)}


def the_reader() -> int:
    read = reader(METRIC)
    # On paper.  A wave: the 48 layer bodies' weights and the head read once,
    # 17 lanes x 800 rows of 8 KB x 48 calls (and a row written a call), the
    # lanes' hidden rows; memory-bound.
    layer = 2048 * 128 * 64 + 3 * 2048 * 5632
    wave_bytes = (2 * (48 * layer + 2048 * 49152)
                  + 48 * 2 * 17 * 801 * 2048 * 2 + 17 * 2048 * 2)
    wave_flops = (2 * 17 * (48 * layer + 2048 * 49152)
                  + 48 * 4 * 17 * 800 * 16 * 128)
    wave = max(wave_flops / FLOPS, wave_bytes / BYTES)
    # The programs (no table of prompts: the pairs are left out): 100 reads
    # of the 48 bodies' weights, 60 of the head's; 40 000 positions through
    # them.
    p_bytes = 100 * 48 * layer * 2 + 60 * 2048 * 49152 * 2
    p_flops = 2 * 40_000 * 48 * layer + 2 * 60 * 2048 * 49152
    programs = max(p_flops / FLOPS, p_bytes / BYTES)
    paper = 100.0 * (1000 * wave + programs) / 50.0
    traced = {"window_s": 4.0, "modules": {
        "jit_decode": {"count": 80, "mean_ms": 14.0, "total_s": 1.12},
        "jit_prefill": {"count": 8, "mean_ms": 20.0, "total_s": 0.16}},
        "program_ops": {"jit_decode": {"fusion_f32_1_": [1.0, 80]}}}
    renamed = {"window_s": 4.0, "modules": {
        "jit_piece_wave": {"count": 88, "mean_ms": 30.0, "total_s": 2.64}},
        "program_ops": {"jit_piece_wave": {"fusion_f32_1_": [1.0, 88]}}}
    got = read(ouro_ctx(traced))
    status = check(
        near(got, paper, 1e-9) and wave_bytes / BYTES > wave_flops / FLOPS
        and 20.0 < paper < 40.0,
        f"(b) 1000 waves of 17 lanes at 800 positions ({wave * 1e3:.3f} ms "
        f"each at the roofline) and 100 piece programs ({programs:.4f} s) "
        f"in 50 s: {got!r}% (on paper {paper!r})")
    same = [read(ouro_ctx(renamed)), read(ouro_ctx(None)),
            read(dict(ouro_ctx(None), traffic={}))]
    status |= check(
        all(v == got for v in same),
        f"(c) the trace's modules renamed (jit_decode -> jit_piece_wave), no "
        f"trace at all, no step_module in the traffic: the same number "
        f"{same}")
    clocked = read(ouro_ctx(traced, clock=True))
    status |= check(
        near(clocked, paper * 50.0 / 50.8, 1e-9),
        f"(c) snapshots 50.8 s apart by the harness's clock: the counters' "
        f"work over 50.8 s, {clocked!r}%, not over the window's 50")
    # With the harness's table (two prompts of 300 and 500 tokens whose first
    # token fell into the window): a position scores (300 x 301 / 2 + 500 x
    # 501 / 2) / 800 pairs a layer of a pass, 48 of them.
    ctx = ouro_ctx(None)
    ctx["req"] = {"first": np.asarray([120.0, 130.0, 90.0, 0.0]),
                  "prompt_len": np.asarray([300.0, 500.0, 1000.0, 700.0])}
    a_position = (300 * 301 / 2 + 500 * 501 / 2) / 800
    pairs = 40_000 * a_position * 48
    with_pairs = max((p_flops + 4 * pairs * 16 * 128) / FLOPS,
                     p_bytes / BYTES)
    status |= check(
        near(read(ctx), 100.0 * (1000 * wave + with_pairs) / 50.0, 1e-9),
        f"(b) with a table of prompts (300 and 500 tokens in the window) the "
        f"pieces' pairs are the counted positions x {a_position:.3f} a layer "
        f"and pass: {read(ctx)!r}%")
    bare = dict(ouro_ctx(None), snap_before=None, snap_after=None)
    no_waves = ouro_ctx(None)
    no_waves["snap_after"] = snap(dict(OURO, fetched_waves=0,
                                       fetched_lanes_live=0), 100)
    cpu = dict(ouro_ctx(None), device={"kind": "cpu", "platform": "cpu"})
    nothing = [read(bare), read(no_waves), read(cpu)]
    return status | check(
        all(v is None for v in nothing),
        f"no snapshots, a window without a wave, a rehearsal on the CPU: "
        f"nothing, never 0: {nothing}")


# -- (d) gpt2_small's live lanes ----------------------------------------------

def live_lanes() -> int:
    cfg = config("gpt2_small")
    fam = family.load("gpt")
    # 1000 waves of the bucket 48 with 33 live lanes at 830 positions each.
    c = dict(fetched_waves=1000, fetched_lanes_live=33_000,
             fetched_lanes_padded=15_000,
             fetched_positions_valid=33_000 * 830)
    ctx = {"cfg": cfg, "traffic": {"step_module": "jit_decode"},
           "device": {"kind": KIND}, "t0": 0.0, "t1": 50.0, "trace": None,
           "snap_before": snap(dict.fromkeys(c, 0), 0),
           "snap_after": snap(c, 0)}
    ctx["snap_after"]["profile"]["models"]["m:1"]["decode_waves"] = [
        {"bucket": 48, "waves": 1000, "device_s": 0.0}]
    ctx["snap_before"]["profile"]["models"]["m:1"]["decode_waves"] = [
        {"bucket": 48, "waves": 0, "device_s": 0.0}]
    (waves, (flops, nbytes)), = fam.step_mix(ctx)
    w = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 50257
    # by hand: the weights once (float32), 33 lanes x 830 rows of K and of V
    # in 12 layers read and one row each written, the lanes' embedding rows.
    hand = (w * 4 + 12 * 2 * 33 * 830 * 768 * 4 + 12 * 2 * 33 * 768 * 4
            + 33 * 768 * 4)
    padded = fam.decode_step(cfg, 48, 830)[1]
    got = reader(METRIC)(ctx)
    return check(
        waves == 1000 and nbytes == hand and nbytes < 0.8 * padded
        and near(got, 100.0 * 1000 * hand / BYTES / 50.0, 1e-9),
        f"(d) a wave of bucket 48 with 33 live lanes: {nbytes / 1e9:.4f} GB "
        f"(33 lanes' rows by hand {hand / 1e9:.4f}; 48 lanes' would be "
        f"{padded / 1e9:.4f}); 1000 of them in 50 s read {got!r}%")


# -- (e) the guard of the traced seconds' lanes -------------------------------

def guard() -> int:
    cfg = config("command_a_plus")
    fam = family.load("cohere_moe")
    slots = int(cfg["serve"]["kwargs"]["max_streams"])

    def traced(tokens_a_wave):
        """``tokens_a_wave`` streams, each with its token 0 before the traced
        seconds (47.5 to 51.5) and ten tokens inside them; 10 ``jit_decode``
        there."""
        n, per = tokens_a_wave, 11
        one = np.r_[47.0, 47.6 + 0.1 * np.arange(per - 1)]
        t = np.concatenate([one for _ in range(n)])
        ctx = {"cfg": cfg, "t0": 0.0, "t1": 52.0,
               "traffic": {"trace_seconds": 4, "trace_end_margin_s": 0.5},
               "trace": {"modules": {"jit_decode": {"count": 10}}},
               "ev_slot": np.repeat(np.arange(n), per), "ev_t": t,
               "req": {"prompt_len": np.full(n, 5000.0)}}
        return fam._traced_waves(ctx)
    at, over = traced(slots), traced(slots + 1)
    return check(
        slots == 24 and at is not None and near(at[0], 24.0)
        and near(at[1], 4095.0) and over is None,
        f"(e) the traced seconds' tokens over jit_decode's count: 24 lanes a "
        f"wave of 24 slots are read ({at}), 25 are not ({over}): waves ran "
        f"under another name and the window's counters are used")


# -- (f) the manifest ---------------------------------------------------------

def manifest() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    by = {x["name"]: x for x in m["per_layer"]}
    cells = [w["name"] for w in m["workloads"]]
    fams = {c["name"]: config(c["name"])["family"] for c in m["configs"]}
    mods = {n: family.load(f) for n, f in fams.items()}
    entry = by.get(METRIC, {})
    return check(
        OLD not in by and entry.get("workloads") == cells
        and (entry.get("unit"), entry.get("better"), entry.get("moves"),
             entry.get("source")) == ("%", "higher", "itl_mean_ms",
                                      "program_counter")
        and all(hasattr(x, "prefill_work") and hasattr(x, "step_mix")
                for x in mods.values())
        and not hasattr(reduce, "step_roofline"),
        f"(f) {METRIC} on all {len(cells)} cells, no {OLD}; a prefill "
        f"program's cost in every family: {sorted(set(fams.values()))}")


def main() -> int:
    assert roofline.peaks_for(KIND)["flops_per_s"] == FLOPS
    assert roofline.peaks_for(KIND)["bytes_per_s"] == BYTES
    return (pieces() | the_reader() | live_lanes() | guard() | manifest())


if __name__ == "__main__":
    sys.exit(main())
