#!/usr/bin/env python3
"""Controls of ``command_a_plus.rag``'s comparison: the served program with
one thing about the model wrong, or in a precision below the one the
configuration states, run through the whole harness (server, probe,
reference, ``judge``), so that the comparison that decides ``correct`` says
what it reads of each, and no side script does.

    python3 benchmark/testdata/cohere_moe_controls.py sequential_block \\
        --seed 2147483999 [--seconds 10] [--rehearse-cpu]

builds a copy of the benchmark beside a link to the program in a temporary
directory, with the configuration's ``serve.backend`` naming one of the
classes below, and runs ``benchmark/run.py --workload command_a_plus.rag``
there.  Same weights (the classes derive from the served backend), same
traffic, same probe, same limits; the reference stays the published model.
Each must come out not correct:

- ``sequential_block``: the experts read a norm of ``x + attn`` (a block
  that adds twice), not what the attention read;
- ``rms_norm``: the norm subtracts no mean;
- ``rotate_half``: the rotary pairs are lanes ``(i, i + 64)``;
- ``rope_on_full``: the full layers take rotary positions too;
- ``global_first``: the period is ``[F, W, W, W]``;
- ``shared_summed``: the four shared experts are summed, not averaged;
- ``softmax_router``: a softmax over the chosen logits weighs the experts;
- ``window_4095``: a window of one key fewer;
- ``e4m3``: every product's operands through float8 e4m3, the nearest
  precision below the configuration's bfloat16.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from client_tpu.models.cohere_moe import CohereMoeBackend  # noqa: E402
from client_tpu.models.layers import rms_norm, rope  # noqa: E402

CELL, CONFIG = "command_a_plus.rag", "command_a_plus.json"


class SequentialBlock(CohereMoeBackend):
    def _after_rows(self, lp, x, o, live, tile_m):
        x = x + self._mm(o, lp["wo"])
        h = self._norm(x, lp["ln"])
        y, counts, top_i = self._experts(lp, h, live, tile_m)
        return x + y + self._shared(lp, h), counts, (top_i,)


class RmsNorm(CohereMoeBackend):
    def _norm(self, x, g):
        return rms_norm(x, g, self.norm_eps)


class RotateHalf(CohereMoeBackend):
    def _rotate(self, t, pos):
        return rope(t, pos, self.rope_theta)


class RopeOnFull(CohereMoeBackend):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.rotate = {"ring": True, "rows": True}


class GlobalFirst(CohereMoeBackend):
    """Serves ``[F, W, W, W]``; ``published_kinds`` is what the configuration
    states, which the reference computes (``benchmark/models/cohere_moe.py``
    ``backend_forward``)."""

    def __init__(self, **kw):
        published = CohereMoeBackend(**kw).layer_kinds
        types = list(kw["layer_types"])
        super().__init__(**{**kw, "layer_types": [types[3]] + types[:3]})
        self.published_kinds = published


class SharedSummed(CohereMoeBackend):
    def _shared(self, lp, h):
        return self._dense_expert(h, lp["sgu"], lp["sd"])


class SoftmaxRouter(CohereMoeBackend):
    router_score = "softmax"


class Window4095(CohereMoeBackend):
    """Serves ``window - 1`` keys; ``published_window`` is the
    configuration's."""

    def __init__(self, **kw):
        super().__init__(**{**kw, "window": kw["window"] - 1})
        self.published_window = kw["window"]


class E4m3Operands(CohereMoeBackend):
    """The projections', the shared and the routed experts', the output's and
    the head's operands through float8 e4m3."""

    @staticmethod
    def _e4(x):
        import jax.numpy as jnp

        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    def _mm(self, x, w):
        return super()._mm(self._e4(x), self._e4(w))

    def _experts(self, lp, h, live, tile_m, routing=None):
        # (The router has read h as it is.)
        routing = self.route(lp, h) if routing is None else routing
        lp = {**lp, "egu": self._e4(lp["egu"]), "ed": self._e4(lp["ed"])}
        return super()._experts(lp, self._e4(h), live, tile_m,
                                routing=routing)


CONTROLS = {"sequential_block": SequentialBlock, "rms_norm": RmsNorm,
            "rotate_half": RotateHalf, "rope_on_full": RopeOnFull,
            "global_first": GlobalFirst, "shared_summed": SharedSummed,
            "softmax_router": SoftmaxRouter, "window_4095": Window4095,
            "e4m3": E4m3Operands}


def main() -> int:
    which, rest = sys.argv[1], sys.argv[2:]
    cls = CONTROLS[which].__name__
    with tempfile.TemporaryDirectory(prefix="cm_control_") as tmp:
        shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        os.symlink(os.path.join(ROOT, "client_tpu"),
                   os.path.join(tmp, "client_tpu"))
        path = os.path.join(tmp, "benchmark", "configs", CONFIG)
        with open(path) as f:
            cfg = json.load(f)
        cfg["serve"]["backend"] = f"testdata.cohere_moe_controls:{cls}"
        with open(path, "w") as f:
            json.dump(cfg, f)
        return subprocess.run(
            [sys.executable, os.path.join(tmp, "benchmark", "run.py"),
             "--workload", CELL, *rest], cwd=tmp).returncode


if __name__ == "__main__":
    sys.exit(main())
