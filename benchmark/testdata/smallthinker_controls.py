#!/usr/bin/env python3
"""Controls of ``smallthinker_21b.mixed``'s comparison: the served program
with one thing about the model wrong, or in a precision below the one the
configuration states, run through the whole harness (server, probe,
reference, ``judge``), so that the comparison that decides ``correct`` says
what it reads of each, and no side script does.

    python3 benchmark/testdata/smallthinker_controls.py window_plus \\
        --seed 2147483999 [--seconds 10] [--rehearse-cpu]

builds a copy of the benchmark beside a link to the program in a temporary
directory, with the configuration's ``serve.backend`` naming one of the
classes below, and runs ``benchmark/run.py --workload
smallthinker_21b.mixed`` there.  Same weights (the classes derive from the
served backend), same traffic, same probe, same limits; the reference stays
the published model.  Each must come out not correct:

- ``window_plus`` / ``window_minus``: a window of one key more or fewer
  (4097: a ring of nine pieces; 4095: the ring's oldest live row masked);
- ``rotated_global``: the global layers take rotary positions too;
- ``router_reads_x``: the router reads the residual stream, not ``N1(x)``;
- ``e4m3``: every dense matmul's operands through float8 e4m3, the nearest
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

from client_tpu.models.smallthinker import SmallThinkerBackend  # noqa: E402

CELL, CONFIG = "smallthinker_21b.mixed", "smallthinker_21b.json"


class _OtherWindow(SmallThinkerBackend):
    """Serves ``window + delta`` keys; ``published_window`` is what the
    configuration states, which the reference computes
    (``benchmark/models/smallthinker.py`` ``backend_forward``)."""

    delta = 0

    def __init__(self, **kw):
        super().__init__(**{**kw, "window": kw["window"] + self.delta})
        self.published_window = kw["window"]


class WindowPlusOne(_OtherWindow):
    delta = 1


class WindowMinusOne(_OtherWindow):
    delta = -1


class RotatedGlobal(SmallThinkerBackend):
    """Every layer rotates q and k, the global ones too."""

    def __init__(self, **kw):
        super().__init__(**{**kw, "rope_layout": (1,)})


class RouterReadsX(SmallThinkerBackend):
    """The router's input is x, not what the attention reads."""

    def _router_input(self, lp, x):
        return x


class E4m3Operands(SmallThinkerBackend):
    """The projections', the output's and the head's operands through float8
    e4m3: the nearest precision below the configuration's bfloat16."""

    @staticmethod
    def _e4(x):
        import jax.numpy as jnp

        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    def _mm(self, x, w):
        return super()._mm(self._e4(x), self._e4(w))


CONTROLS = {"window_plus": WindowPlusOne, "window_minus": WindowMinusOne,
            "rotated_global": RotatedGlobal, "router_reads_x": RouterReadsX,
            "e4m3": E4m3Operands}


def main() -> int:
    which, rest = sys.argv[1], sys.argv[2:]
    cls = CONTROLS[which].__name__
    with tempfile.TemporaryDirectory(prefix="st_control_") as tmp:
        shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        os.symlink(os.path.join(ROOT, "client_tpu"),
                   os.path.join(tmp, "client_tpu"))
        path = os.path.join(tmp, "benchmark", "configs", CONFIG)
        with open(path) as f:
            cfg = json.load(f)
        cfg["serve"]["backend"] = f"testdata.smallthinker_controls:{cls}"
        with open(path, "w") as f:
            json.dump(cfg, f)
        return subprocess.run(
            [sys.executable, os.path.join(tmp, "benchmark", "run.py"),
             "--workload", CELL, *rest], cwd=tmp).returncode


if __name__ == "__main__":
    sys.exit(main())
