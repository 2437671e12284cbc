"""Model families, found by name.

A configuration file says ``"family": "<name>"``; everything that depends
on the architecture lives in ``benchmark/models/<name>.py``, which exports

- ``encode_request(cfg, model, rows, prompt_len, output_len, rng)``: the
  bytes of one HTTP request of the cell's traffic;
- ``probe(server, cfg, traffic, seed)``: sends the probe the reference
  judges (after the window, untimed) and returns what the server answered;
- ``check(params, probe, backend)``: the plain float32 forward pass and
  the comparison that decides ``correct``; returns a verdict with ``ok``;
- ``step_mix(ctx)``: ``[(count, (flops, bytes)), ...]`` of the decode waves
  the window ran, from shapes and the program's counters alone (no trace, no
  program's name), and ``prefill_work(ctx)``: ``(flops, bytes)`` of all its
  prefill programs: the numerator of the whole step's share of the roofline
  (``reduce.step_mfu_roofline``).  A family that prefills by pieces gives
  ``piece_step(cfg, positions, pairs_window, pairs_global, programs, heads)``
  and calls ``reduce.pieces_work``.

A configuration of another architecture adds a module there and edits
nothing.  The plain operations below are shared by the references.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))
_loaded: dict = {}


def load(name: str):
    """benchmark/models/<name>.py as a module."""
    if name not in _loaded:
        path = os.path.join(HERE, "models", name + ".py")
        if not os.path.exists(path):
            raise FileNotFoundError(
                f"no model family {name!r}: add {path}")
        spec = importlib.util.spec_from_file_location("family_" + name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[name] = mod
    return _loaded[name]


def http_request(path: str, head: dict, tail: bytes) -> bytes:
    """One KServe v2 HTTP-binary request: JSON header, then raw tensors."""
    hj = json.dumps(head, separators=(",", ":")).encode()
    return (f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: application/octet-stream\r\n"
            f"Inference-Header-Content-Length: {len(hj)}\r\n"
            f"Content-Length: {len(hj) + len(tail)}\r\n\r\n"
            ).encode() + hj + tail


def layer_norm(x, g, b, eps):
    import jax.numpy as jnp

    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
