#!/usr/bin/env python3
"""Controls of ``nemotron3_nano_30b.assistant``'s comparison: the served
program with one thing about the model wrong, or in a precision below the one
the configuration states, run through the whole harness (server, probe,
reference, ``judge``), so that the comparison that decides ``correct`` says
what it reads of each, and no side script does.

    python3 benchmark/testdata/nemotron_h_controls.py bf16_state \\
        --seed 2147483999 [--seconds 10] [--rehearse-cpu]

builds a copy of the benchmark beside a link to the program in a temporary
directory, with the configuration's ``serve.backend`` naming one of the
classes below, and runs ``benchmark/run.py --workload
nemotron3_nano_30b.assistant`` there.  Same weights (the classes derive from
the served backend), same traffic, same probe, same limits; the reference
stays the published model.  Each must come out not correct:

- ``bf16_state``: the recurrent state's leaf in bfloat16 (the state-space
  layers' nearest precision below the float32 the configuration states);
- ``e4m3``: every dense matmul's operands through float8 e4m3, the nearest
  precision below the configuration's bfloat16;
- ``rotated``: the attention layers rotate q and k (RoPE over the whole head
  at ``rope_theta`` 10000, the keys the config carries and the model does not
  use);
- ``norm_all``: the gated norm behind the state taken over all 4096 channels
  and not over each group's 512;
- ``no_skip``: the skip term ``D x`` left out.
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

from client_tpu.models.nemotron_h import NemotronHBackend  # noqa: E402

CELL, CONFIG = "nemotron3_nano_30b.assistant", "nemotron3_nano_30b.json"


class Bf16State(NemotronHBackend):
    """The state's leaf in bfloat16: every step rounds what it writes."""

    def init_arena(self, capacity: int):
        import jax.numpy as jnp

        arena = super().init_arena(capacity)
        return {**arena, "s": arena["s"].astype(jnp.bfloat16)}


class E4m3Operands(NemotronHBackend):
    """The projections', the shared expert's and the head's operands through
    float8 e4m3."""

    @staticmethod
    def _e4(x):
        import jax.numpy as jnp

        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    def _mm(self, x, w):
        return super()._mm(self._e4(x), self._e4(w))


class RotatedAttention(NemotronHBackend):
    """The attention layers take rotary positions."""

    def _project(self, lp, x, pos):
        from client_tpu.models.evabyte import rope

        q, k, v = super()._project(lp, x, pos)
        return rope(q, pos, 10000.0), rope(k, pos, 10000.0), v


class NormOverAll(NemotronHBackend):
    """One norm over all of ``d_inner``."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.norm_groups = 1


class NoSkipTerm(NemotronHBackend):
    """``y = S C``, without ``D x``."""

    def _ssm_output(self, lp, y, x, z):
        return super()._ssm_output(lp, y, x * 0.0, z)


CONTROLS = {"bf16_state": Bf16State, "e4m3": E4m3Operands,
            "rotated": RotatedAttention, "norm_all": NormOverAll,
            "no_skip": NoSkipTerm}


def main() -> int:
    which, rest = sys.argv[1], sys.argv[2:]
    cls = CONTROLS[which].__name__
    with tempfile.TemporaryDirectory(prefix="nh_control_") as tmp:
        shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        os.symlink(os.path.join(ROOT, "client_tpu"),
                   os.path.join(tmp, "client_tpu"))
        path = os.path.join(tmp, "benchmark", "configs", CONFIG)
        with open(path) as f:
            cfg = json.load(f)
        cfg["serve"]["backend"] = f"testdata.nemotron_h_controls:{cls}"
        with open(path, "w") as f:
            json.dump(cfg, f)
        return subprocess.run(
            [sys.executable, os.path.join(tmp, "benchmark", "run.py"),
             "--workload", CELL, *rest], cwd=tmp).returncode


if __name__ == "__main__":
    sys.exit(main())
