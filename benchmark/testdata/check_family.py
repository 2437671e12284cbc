#!/usr/bin/env python3
"""The ``bert`` family's plain reference against the program's own
BertBackend, at a tiny size on the CPU (run by selftest.py in a child, with
JAX_PLATFORMS=cpu): the weights are the backend's, the inputs a probe as
``probe`` would send it, the answer the backend's own ``apply``."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import family  # noqa: E402
from client_tpu.models.bert import BertBackend  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
backend = BertBackend(name="tiny", seq_len=16, hidden=64, n_layers=2,
                      n_heads=4, ffn=128, vocab=512)
params = backend._init_params()
rng = np.random.default_rng(5)
mask = np.ones((4, 16), np.int32)
for i, n in enumerate((16, 8, 4, 3)):
    mask[i, n:] = 0
ids = (rng.integers(0, 512, (4, 16)) * mask).astype(np.int32)
out = backend._build_apply()(params, {"input_ids": ids,
                                      "attention_mask": mask})
probe = {"ids": ids.tolist(), "mask": mask.tolist(),
         "logits": np.asarray(out["logits"], np.float32).tolist(),
         "pooled_output": np.asarray(out["pooled_output"],
                                     np.float32).tolist()}
bert = family.load("bert")
f32 = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), params)
verdict = bert.check(f32, probe, backend)
print("ok   " if verdict["ok"] else "FAIL ", "bert family against the "
      f"program's BertBackend (2 layers, CPU): {verdict}")
probe["logits"] = (np.asarray(probe["logits"]) + 0.2).tolist()
wrong = bert.check(f32, probe, backend)
print("ok   " if not wrong["ok"] else "FAIL ",
      "an answer 0.2 off is refused")
sys.exit(0 if verdict["ok"] and not wrong["ok"] else 1)
