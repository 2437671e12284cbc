#!/usr/bin/env python3
"""Controls of ``granite4_h_micro.helpdesk``'s comparison: the served program
with one thing about the model wrong, or in a precision below the one the
configuration states, run through the whole harness (server, probe,
reference, ``judge``), so that the comparison that decides ``correct`` says
what it reads of each, and no side script does.

    python3 benchmark/testdata/granite_hybrid_controls.py bf16_state \\
        --seed 2147483999 [--seconds 10] [--rehearse-cpu]

builds a copy of the benchmark beside a link to the program in a temporary
directory, with the configuration's ``serve.backend`` naming one of the
classes below, and runs ``benchmark/run.py --workload
granite4_h_micro.helpdesk`` there.  Same weights (the classes derive from the
served backend), same traffic, same probe, same limits; the reference stays
the published model (a control keeps the published multipliers in
``published``).  Each must come out not correct:

- ``embedding_1``, ``residual_1``, ``attention_1``, ``logits_1``: one of the
  four multipliers left at 1 (``embedding_multiplier`` 12,
  ``residual_multiplier`` 0.22, ``attention_multiplier`` 1/64,
  ``logits_scaling`` 8);
- ``sqrt_scale``: the scores scaled by ``1 / sqrt(64)`` and not by 1/64;
- ``rotated``: the attention layers rotate q and k (RoPE over the whole head
  at ``rope_theta`` 10000, the key the config carries and the model does not
  use);
- ``untied_head``: a head of its own (seeded ``1 / sqrt(d)``) and not the
  embedding's rows;
- ``norm_groups_8``: the gated norm behind the state over 8 groups of 512
  channels and not over all 4096;
- ``gate_after_norm``: ``RMSNorm(y) * w * silu(z)`` and not ``RMSNorm(y *
  silu(z)) * w``;
- ``bf16_state``: the recurrent state's leaf in bfloat16 (the state-space
  layers' nearest precision below the float32 the configuration states);
- ``e4m3``: every dense matmul's operands through float8 e4m3, the nearest
  precision below the configuration's bfloat16.

A control that passed would mean the tolerance is too loose or the seeded
scales hide the term.  None does: at the seeded scales (the embedding at 1/12,
so that the stream starts at unit rms) every one of the eleven is refused on
the CPU at the tiny preset (tests/test_granite_hybrid_rehearsal.py) and at the
published widths on the chip (PERF.md section 6, PR 59), so nothing was
reseeded for a control's sake.  The weakest is ``rotated``: scores under 1/64
are small at the seeded scales (a near-uniform softmax in 4 of 40 layers), so
rotating q and k moves a logit by 0.0027 rms where the served program is off
by 0.0012; the rms limits stand under it (the family module,
``models/granite_hybrid.py``, has each limit beside its readings).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from client_tpu.models.granite_hybrid import GraniteHybridBackend  # noqa: E402

CELL, CONFIG = "granite4_h_micro.helpdesk", "granite4_h_micro.json"
_MULTIPLIERS = ("embedding_multiplier", "residual_multiplier", "attn_scale",
                "logits_scaling")


class _OneMultiplier(GraniteHybridBackend):
    """One multiplier (``which``) served at ``served()``, 1 unless a control
    says otherwise; the reference reads ``published``."""

    which = ""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.published = types.SimpleNamespace(
            **{name: getattr(self, name) for name in _MULTIPLIERS})
        setattr(self, self.which, self.served())

    def served(self) -> float:
        return 1.0


class EmbeddingAtOne(_OneMultiplier):
    which = "embedding_multiplier"


class ResidualAtOne(_OneMultiplier):
    which = "residual_multiplier"


class AttentionAtOne(_OneMultiplier):
    which = "attn_scale"


class LogitsAtOne(_OneMultiplier):
    which = "logits_scaling"


class SqrtScale(_OneMultiplier):
    """Scores over ``sqrt(head_dim)``, as every other served decoder's."""

    which = "attn_scale"

    def served(self) -> float:
        return self.head_dim ** -0.5


class RotatedAttention(GraniteHybridBackend):
    """The attention layers take rotary positions."""

    def _project(self, lp, x, pos):
        from client_tpu.models.layers import rope

        q, k, v = super()._project(lp, x, pos)
        return rope(q, pos, 10000.0), rope(k, pos, 10000.0), v


class UntiedHead(GraniteHybridBackend):
    """A head of its own."""

    def _init_params(self):
        from client_tpu.models.seeded import SeededWeight

        d = self.d_model
        return {**super()._init_params(),
                "head": SeededWeight((self._seed, 1 << 19), (d, self.vocab),
                                     d ** -0.5, dtype=self.dtype)}

    def _logits(self, p, x):
        from client_tpu.models.layers import rms_norm

        return self._mm(rms_norm(x, p["lnf"], self.rms_eps),
                        p["head"]) / self.logits_scaling


class NormGroupsOf512(GraniteHybridBackend):
    """The gated norm a group of 512 channels."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.norm_groups = self.d_inner // min(512, self.d_inner // 2)


class GateAfterNorm(GraniteHybridBackend):
    """``RMSNorm(y) * w * silu(z)``."""

    def _ssm_output(self, lp, y, x, z):
        import jax
        import jax.numpy as jnp

        y = (y + lp["skip"][:, None] * x).reshape(z.shape)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + self.rms_eps)
        return y * lp["gnorm"].astype(jnp.float32) * jax.nn.silu(z)


class Bf16State(GraniteHybridBackend):
    """The state's leaf in bfloat16: every step rounds what it writes.  (Made
    in bfloat16, not cast: 6.1 GB of float32 and its half do not fit the chip
    together.)"""

    def _state_arena(self, r: int, dt) -> dict:
        import jax
        import jax.numpy as jnp

        leaves = jax.eval_shape(lambda: GraniteHybridBackend._state_arena(
            self, r, dt))
        return {"s": jnp.zeros(leaves["s"].shape, jnp.bfloat16),
                "conv": jnp.zeros(leaves["conv"].shape, dt)}


class E4m3Operands(GraniteHybridBackend):
    """The projections', the feed-forwards' and the head's operands through
    float8 e4m3."""

    @staticmethod
    def _e4(x):
        import jax.numpy as jnp

        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    def _mm(self, x, w):
        return super()._mm(self._e4(x), self._e4(w))


CONTROLS = {"embedding_1": EmbeddingAtOne, "residual_1": ResidualAtOne,
            "attention_1": AttentionAtOne, "logits_1": LogitsAtOne,
            "sqrt_scale": SqrtScale, "rotated": RotatedAttention,
            "untied_head": UntiedHead, "norm_groups_8": NormGroupsOf512,
            "gate_after_norm": GateAfterNorm, "bf16_state": Bf16State,
            "e4m3": E4m3Operands}


def main() -> int:
    which, rest = sys.argv[1], sys.argv[2:]
    cls = CONTROLS[which].__name__
    with tempfile.TemporaryDirectory(prefix="gh_control_") as tmp:
        shutil.copytree(BENCH, os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        os.symlink(os.path.join(ROOT, "client_tpu"),
                   os.path.join(tmp, "client_tpu"))
        path = os.path.join(tmp, "benchmark", "configs", CONFIG)
        with open(path) as f:
            cfg = json.load(f)
        cfg["serve"]["backend"] = f"testdata.granite_hybrid_controls:{cls}"
        with open(path, "w") as f:
            json.dump(cfg, f)
        return subprocess.run(
            [sys.executable, os.path.join(tmp, "benchmark", "run.py"),
             "--workload", CELL, *rest], cwd=tmp).returncode


if __name__ == "__main__":
    sys.exit(main())
