"""PR 56's three readers of a carried wave (``benchmark/wavekernels.py``,
``benchmark/metrics/decode_attn_all_roofline.py``,
``window_attn_all_roofline.py``, ``piece_wave_roofline.py``) on hand-made
contexts at the two cells' published widths: a wave's attention kernels are
read from ``jit_prefill`` and ``jit_decode`` alike against the rows of the
traced seconds' tokens, the carrying program against the piece's work and the
wave's, and the parent of the PR (no ``fetched_waves_carried``) reads
nothing."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from test_benchmark_progspans import run_reader, snap  # noqa: E402

CELLS = {"command_a_plus.rag": "command_a_plus",
         "smallthinker_21b.mixed": "smallthinker_21b"}
GLOBAL, WINDOW, PROGRAM = ("decode_attn_all_roofline.itl",
                           "window_attn_all_roofline.itl",
                           "piece_wave_roofline.itl")
PEAK_BYTES = 819e9
# The traced seconds of the hand-made run: [T1 - 0.5 - 4 + START, + 4).
T0, T1, START = 100.0, 150.0, 0.05
LO = T1 - 0.5 - 4.0 + START
# (prompt, [(token's ordinal, its time)]) a stream; ordinal 0 is a prefill's.
STREAMS = [
    (3000, [(0, LO + 0.1), (1, LO + 0.2), (2, LO + 0.3)]),
    (9000, [(7, LO - 0.2), (8, LO + 0.0), (9, LO + 3.9), (10, LO + 4.1)]),
    (500, [(1, LO + 1.0)]),
]
# The contexts behind the tokens that count (P + k - 1 inside [LO, LO + 4)).
CONTEXTS = [3000, 3001, 9007, 9008, 500]


def config(cell):
    with open(os.path.join(BENCH, "configs", CELLS[cell] + ".json")) as f:
        return json.load(f)


def context(cell, ops, carried=(10, 90), modules=None, counters=None):
    """A run of ``cell`` whose trace holds ``ops`` (program -> group ->
    [seconds, events]) and whose streams are ``STREAMS``."""
    def side(n):
        moved = {"fetched_waves": 10 * n, "fetched_lanes_live": 180 * n,
                 **{k: v * n for k, v in (counters or {}).items()}}
        if carried is not None:
            moved["fetched_waves_carried"] = carried[n - 1]
        return snap({"gen.prefill_dispatch": (100 * n, 9)}, moved)

    slot, t = [], []
    for i, (_, tokens) in enumerate(STREAMS):
        lead = [T0 + 0.001 * k for k in range(tokens[0][0])]
        slot += [i] * (len(lead) + len(tokens))
        t += lead + [at for _, at in tokens]
    return {
        "cfg": config(cell), "t0": T0, "t1": T1,
        "traffic": {"trace_seconds": 4, "trace_end_margin_s": 0.5},
        "device": {"kind": "TPU v5 lite", "platform": "tpu"},
        "snap_before": side(1), "snap_after": side(2),
        "ev_slot": np.asarray(slot, np.int64),
        "ev_t": np.asarray(t, np.float64),
        "req": {"prompt_len": np.asarray([p for p, _ in STREAMS],
                                         np.float64)},
        "trace": {"start_call_s": START, "program_ops": ops,
                  "modules": modules or {}}}


def least_seconds(cell, ring):
    """By hand: each counted token's lane reads its rows of K and of V once
    and writes one of each (``Hkv x D`` bfloat16 values a row), in every
    layer of the kind; the kernel is the memory's at these widths."""
    cfg = config(cell)
    layout = cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]
    layers = sum(1 for w in layout if bool(w) == ring)
    row = cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    rows = [min(n, cfg["sliding_window_size"] - 1) if ring else n
            for n in CONTEXTS]
    return layers * sum(2 * (r + 1) * row for r in rows) / PEAK_BYTES


KERNELS = {GLOBAL: ("decode_wave_attention_bf16_1_25_25600_1024_", False),
           WINDOW: ("window_wave_attention_bf16_3_25_4096_1024_", True)}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("name", sorted(KERNELS))
class TestAttentionOverBothPrograms:
    def test_a_wave_that_rode_is_read_from_the_pieces_program(self, cell,
                                                              name):
        """Every wave rode: the kernel's events are ``jit_prefill``'s alone,
        and at twice its least seconds the share is 50%."""
        group, ring = KERNELS[name]
        least = least_seconds(cell, ring)
        ctx = context(cell, {"jit_prefill": {
            group: [2 * least, 110], "flash_attention_bf16_": [1.0, 440]}})
        assert run_reader(name)(ctx) == pytest.approx(50.0, rel=1e-6)

    def test_the_seconds_of_both_programs_add(self, cell, name):
        """Lone waves and carried ones: the same tokens, the kernel's time
        in ``jit_decode`` and in ``jit_prefill``; no other program's and no
        other kernel's."""
        group, ring = KERNELS[name]
        other = KERNELS[GLOBAL if name == WINDOW else WINDOW][0]
        least = least_seconds(cell, ring)
        ctx = context(cell, {
            "jit_decode": {group: [least, 30], other: [9.0, 30]},
            "jit_prefill": {group: [3 * least, 80]},
            "jit_apply": {group: [7.0, 1]}})
        assert run_reader(name)(ctx) == pytest.approx(25.0, rel=1e-6)

    def test_nothing_to_read(self, cell, name):
        """The parent counts no carried wave; a run may have no trace, a
        trace no event of the kernel, a context no token clock."""
        group, ring = KERNELS[name]
        ops = {"jit_prefill": {group: [1.0, 110]}}
        read = run_reader(name)
        assert read(context(cell, ops)) > 0
        assert read(context(cell, ops, carried=None)) is None
        assert read(context(cell, {"jit_prefill": {"fusion_": [1.0, 9]}})) \
            is None
        assert read({**context(cell, ops), "trace": None}) is None
        bare = context(cell, ops)
        del bare["ev_t"]
        assert read(bare) is None
        early = context(cell, ops)
        early["ev_t"] = early["ev_t"] - 10.0        # none in the traced 4 s
        assert read(early) is None


def test_the_accepted_readers_see_jit_decode_alone():
    """Why these readers exist: on the same context the accepted share of
    the kernel reads nothing where every wave rode."""
    group = KERNELS[WINDOW][0]
    ctx = context("command_a_plus.rag", {"jit_prefill": {group: [1.0, 110]}},
                  counters={"fetched_rows_window": 900,
                            "fetched_rows_global": 900,
                            "expert_pairs_local": 90, "experts_touched": 90,
                            "fetched_positions_valid": 900})
    assert run_reader("window_attn_roofline.itl")(ctx) is None
    assert run_reader(WINDOW)(ctx) > 0


PIECES = {"prefill_positions_valid": 51200, "prefill_pairs_window": 10 ** 8,
          "prefill_pairs_global": 10 ** 8, "prefill_heads": 5,
          "fetched_rows_window": 10 ** 6, "fetched_rows_global": 10 ** 6,
          "expert_pairs_local": 400, "experts_touched": 300}


@pytest.mark.parametrize("cell", sorted(CELLS))
class TestTheCarryingProgram:
    def program(self, cell, carried):
        return context(cell, {}, carried, counters=PIECES, modules={
            "jit_prefill": {"count": 100, "mean_ms": 35.0, "total_s": 3.5}})

    def test_with_no_wave_it_is_the_pieces_share(self, cell):
        """No wave rode in the window: the program's work is its piece's,
        ``piece_roofline``'s number where that reader reads the cell."""
        ctx = self.program(cell, (10, 10))
        got = run_reader(PROGRAM)(ctx)
        assert 0 < got < 100
        piece = run_reader("piece_roofline.itl")(ctx)
        assert piece is None or got == pytest.approx(piece, rel=1e-9)

    def test_a_wave_adds_its_own_work_and_no_weight(self, cell):
        """Nine programs in ten carried a wave: the share rises by the
        wave's rows and operations, which are far less than a lone wave's
        step (that reads every weight again)."""
        import family
        import roofline

        none = run_reader(PROGRAM)(self.program(cell, (10, 10)))
        most = run_reader(PROGRAM)(self.program(cell, (10, 100)))
        assert none < most < 100
        fam = family.load(config(cell)["family"])
        lone = roofline.min_seconds(*fam.decode_step(
            config(cell), 18.0, 10 ** 5 / 180 / 3, 10 ** 5 / 180,
            40 / 4, 30 / 4), roofline.PEAKS["TPU v5 lite"])[0]
        assert (most - none) / 100 * 35.0e-3 < 0.9 * lone

    def test_nothing_from_the_parent(self, cell):
        assert run_reader(PROGRAM)(self.program(cell, None)) is None
        ctx = self.program(cell, (10, 100))
        ctx["trace"]["modules"] = {}
        assert run_reader(PROGRAM)(ctx) is None


def test_the_manifest_holds_the_three_last_for_the_two_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    layers = {GLOBAL: "kernels", WINDOW: "kernels",
              PROGRAM: "model execution"}
    # (Last until PR 57 put the seven of the set-up timeline behind them,
    # and PR 59 its one behind those.)
    assert manifest["per_layer"][-11:-8] == [
        {"name": name, "unit": "%", "better": "higher",
         "source": "device_trace", "layer": layers[name],
         "moves": "itl_mean_ms",
         "workloads": ["smallthinker_21b.mixed", "command_a_plus.rag"]}
        for name in (GLOBAL, WINDOW, PROGRAM)]
