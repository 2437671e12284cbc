#!/usr/bin/env python3
"""Controls of ``ouro_2b6.fewshot``'s comparison: the served program with one
thing about the model wrong, or in a precision below the one the configuration
states, run through the whole harness (server, probe, reference, ``judge``),
so that the comparison that decides ``correct`` says what it reads of each,
and no side script does.

    python3 benchmark/testdata/ouro_controls.py one_pass \\
        --seed 2147483999 [--seconds 10] [--rehearse-cpu]

builds a copy of the benchmark beside a link to the program in a temporary
directory, with the configuration's ``serve.backend`` naming one of the
classes below, and runs ``benchmark/run.py --workload ouro_2b6.fewshot``
there.  Same weights (the classes derive from the served backend), same
traffic, same probe, same limits; the reference stays the published model.
Each must come out not correct:

- ``one_pass``: the layers run once (``passes`` 1);
- ``shared_cache``: every pass reads and writes the last pass's leaves: the
  report's decoding shortcut, an approximation of the model;
- ``norm_at_end``: the final norm after the fourth pass only, nothing between
  passes;
- ``pre_norm``: the sandwich's second norms (``ln2``, ``ln4``) left out;
- ``unrotated``: no RoPE;
- ``e4m3``: every dense product's operands through float8 e4m3, the nearest
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

from client_tpu.models.layers import rms_norm  # noqa: E402
from client_tpu.models.ouro import OuroBackend  # noqa: E402

CELL, CONFIG = "ouro_2b6.fewshot", "ouro_2b6.json"


class OnePass(OuroBackend):
    """The layers run once; the reference keeps the published passes."""

    def __init__(self, **kw):
        self.published_passes = int(kw.get("passes", 4))
        super().__init__(**{**kw, "passes": 1})


class SharedCache(OuroBackend):
    """Every pass reads and writes the last pass's leaves."""

    def _layer_kind(self, li):
        kind, ki = super()._layer_kind(li)
        return kind, (self.passes - 1) * self.n_layers + ki % self.n_layers


class NormAtEnd(OuroBackend):
    """Nothing between passes: the final norm before the head alone."""

    def _between_passes(self, p, x):
        return x

    def _logits(self, p, x):
        return self._mm(rms_norm(x, p["lnf"], self.rms_eps), p["head"])


class PreNorm(OuroBackend):
    """A sub-block's output is added as it is."""

    def _after_attention(self, lp, x, o):
        import jax

        f = self.d_ff
        x = x + self._mm(o.reshape(o.shape[0], -1), lp["wo"])
        gu = self._mm(rms_norm(x, lp["ln3"], self.rms_eps), lp["wgu"])
        return x + self._mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], lp["wd"])


class Unrotated(OuroBackend):
    """No position enters."""

    def _project(self, lp, x, pos):
        return self._heads(lp, rms_norm(x, lp["ln1"], self.rms_eps))


class E4m3Operands(OuroBackend):
    """Every dense product's operands through float8 e4m3."""

    @staticmethod
    def _e4(x):
        import jax.numpy as jnp

        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    def _mm(self, x, w):
        return super()._mm(self._e4(x), self._e4(w))


CONTROLS = {"one_pass": OnePass, "shared_cache": SharedCache,
            "norm_at_end": NormAtEnd, "pre_norm": PreNorm,
            "unrotated": Unrotated, "e4m3": E4m3Operands}


def main() -> int:
    which, rest = sys.argv[1], sys.argv[2:]
    cls = CONTROLS[which].__name__
    with tempfile.TemporaryDirectory(prefix="ouro_control_") as tmp:
        shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        os.symlink(os.path.join(ROOT, "client_tpu"),
                   os.path.join(tmp, "client_tpu"))
        path = os.path.join(tmp, "benchmark", "configs", CONFIG)
        with open(path) as f:
            cfg = json.load(f)
        cfg["serve"]["backend"] = f"testdata.ouro_controls:{cls}"
        with open(path, "w") as f:
            json.dump(cfg, f)
        return subprocess.run(
            [sys.executable, os.path.join(tmp, "benchmark", "run.py"),
             "--workload", CELL, *rest], cwd=tmp).returncode


if __name__ == "__main__":
    sys.exit(main())
