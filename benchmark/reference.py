#!/usr/bin/env python3
"""The plain reference's process, and the verdict that decides ``correct``.

    JAX_PLATFORMS=cpu python3 benchmark/reference.py <config.json> <probe.json> <verdict.json> <backend kwargs> <seconds to wait> <cores>

Runs on the host CPU in a child of its own (the chip belongs to the server).
It builds the weights while the server warms up, then waits for the probe
file the harness writes after the window (the probe inputs and what the
server answered), lets the configuration's model family
(``benchmark/models/<family>.py``) compute its plain float32 ``jax.numpy``
forward pass at ``precision=highest`` and compare, and writes the verdict.
The only thing taken from the program is the *weights* (data, made from the
same seed by the backend's own initialiser); the forward passes are written
from the published equations, with the departures the configuration file
lists.  Each family states its tolerance, with the reason, beside its
comparison.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    cfg_path, probe_path, verdict_path = sys.argv[1:4]
    kwargs = json.loads(sys.argv[4])
    deadline = time.monotonic() + float(sys.argv[5])
    cores = [int(c) for c in sys.argv[6].split(",") if c]
    if cores:
        os.sched_setaffinity(0, set(cores))
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)

    import jax
    import numpy as np

    import family
    from traffic import load_json

    jax.config.update("jax_default_matmul_precision", "highest")
    cfg = load_json(cfg_path)
    mod_name, cls_name = cfg["serve"]["backend"].split(":")
    backend = getattr(importlib.import_module(mod_name), cls_name)(
        name=cfg["serve"]["model_name"], **kwargs)
    params = jax.tree_util.tree_map(            # the weights are data
        lambda a: np.asarray(a, np.float32), backend._init_params())
    print("REFERENCE_READY", flush=True)
    while not os.path.exists(probe_path):
        if time.monotonic() > deadline:
            return 4
        time.sleep(0.2)
    with open(probe_path) as f:
        probe = json.load(f)
    t0 = time.monotonic()
    verdict = family.load(cfg["family"]).check(params, probe, backend)
    verdict["reference_s"] = time.monotonic() - t0
    with open(verdict_path + ".tmp", "w") as f:
        json.dump(verdict, f)
    os.replace(verdict_path + ".tmp", verdict_path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
