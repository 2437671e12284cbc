#!/usr/bin/env python3
"""Controls of ``kimi_linear.longgen``'s comparison: the served program in a
precision below the one the configuration states, run through the whole
harness (server, probe, reference, ``judge``), so that the comparison that
decides ``correct`` says what it reads of each, and no side script does.

    python3 benchmark/testdata/kimi_linear_controls.py bf16_state \\
        --seed 2147483999 [--seconds 10] [--rehearse-cpu]

builds a copy of the benchmark beside a link to the program in a temporary
directory, with the configuration's ``serve.backend`` naming one of the
classes below, and runs ``benchmark/run.py --workload kimi_linear.longgen``
there.  Same weights (the classes derive from the served backend), same
traffic, same probe, same limits.  ``bf16_state`` and ``e4m3`` come out not
correct by ``LOGIT_RMS`` (``e4m3`` by every limit); ``bf16_decay`` reads what
the served program reads (``benchmark/models/kimi_linear.py`` says why).
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

from client_tpu.models.kimi_linear import KimiLinearBackend  # noqa: E402

CELL, CONFIG = "kimi_linear.longgen", "kimi_linear.json"


class Bf16State(KimiLinearBackend):
    """The recurrent state kept in bfloat16 (the kernel, its oracle and the
    chunked form compute in float32 and round what they store)."""

    def init_arena(self, capacity: int):
        import jax.numpy as jnp

        arena = super().init_arena(capacity)
        return {**arena, "s": arena["s"].astype(jnp.bfloat16)}


class Bf16Decay(KimiLinearBackend):
    """The decay through bfloat16: ``g`` rounded, and ``exp(g)`` rounded."""

    def _kda_inputs(self, lp, h, ext):
        import jax.numpy as jnp

        q, k, v, g, beta, gate = super()._kda_inputs(lp, h, ext)
        bf16 = jnp.bfloat16
        a = jnp.exp(g.astype(bf16).astype(jnp.float32)).astype(bf16)
        # (The smallest normal float32 where exp underflows: a log of 0
        # would put a NaN into the chunked form's differences.)
        return q, k, v, jnp.log(jnp.maximum(a.astype(jnp.float32), 2e-38)
                                ), beta, gate


class E4m3Operands(KimiLinearBackend):
    """Every matmul's operands through float8 e4m3: the nearest precision
    below the configuration's bfloat16."""

    @staticmethod
    def _e4(x):
        import jax.numpy as jnp

        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    def _mm(self, x, w):
        return super()._mm(self._e4(x), self._e4(w))

    def _heads_mm(self, eq, x, w):
        return super()._heads_mm(eq, self._e4(x), self._e4(w))


CONTROLS = {"bf16_state": Bf16State, "bf16_decay": Bf16Decay,
            "e4m3": E4m3Operands}


def main() -> int:
    which, rest = sys.argv[1], sys.argv[2:]
    cls = CONTROLS[which].__name__
    with tempfile.TemporaryDirectory(prefix="kimi_control_") as tmp:
        shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        os.symlink(os.path.join(ROOT, "client_tpu"),
                   os.path.join(tmp, "client_tpu"))
        path = os.path.join(tmp, "benchmark", "configs", CONFIG)
        with open(path) as f:
            cfg = json.load(f)
        cfg["serve"]["backend"] = f"testdata.kimi_linear_controls:{cls}"
        with open(path, "w") as f:
            json.dump(cfg, f)
        return subprocess.run(
            [sys.executable, os.path.join(tmp, "benchmark", "run.py"),
             "--workload", CELL, *rest], cwd=tmp).returncode


if __name__ == "__main__":
    sys.exit(main())
