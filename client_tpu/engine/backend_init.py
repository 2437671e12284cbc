"""Eager, logged JAX backend initialization.

Round-1 postmortem: the first ``jax.device_put`` used to happen lazily on a
scheduler *worker* thread, so PjRt client creation (20s+ on a contended TPU)
ran invisibly inside the first inference, and callers saw only a bare 504
timeout with no way to distinguish "compiling" from "dead".  The fix is to
initialize the backend eagerly on the *calling* (normally main) thread, with
progress logged to stderr, before any scheduler thread exists.

``ensure_backend`` is idempotent and thread-safe; ``TpuEngine.__init__`` and
``bench.py`` both call it first thing.  A watchdog thread logs every few
seconds while PjRt initialization is in flight so a hang is visible and
attributable (a hung native call cannot be interrupted from Python, so past
``hard_timeout_s`` the watchdog escalates its log level rather than raising
into a stack that could not unwind anyway).

Which device: ``JAX_PLATFORMS`` is JAX's own variable and the only switch —
``cpu`` for the test suite, ``tpu`` for anything that is to be measured
(with ``tpu`` a missing chip is JAX's hard error; with the variable unset
JAX falls back to the CPU when the TPU fails to initialize).  Nothing here
edits ``jax_platforms``; the start-up log names what came up.

Compile cache: every engine, bench and tool passes through here, so this is
where JAX's persistent compilation cache gets its directory.  With
``JAX_COMPILATION_CACHE_DIR`` set JAX reads it itself and nothing is set in
code; otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path
(the path is part of the cache key, so a directory that moves never hits).
"""

from __future__ import annotations

import logging
import os
from client_tpu import config as envcfg
import threading
from client_tpu.utils import lockdep
import time

log = logging.getLogger("client_tpu.engine")
if not log.handlers:  # default to visible stderr progress; apps may override
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[client_tpu] %(asctime)s %(message)s"))
    log.addHandler(_h)
    log.setLevel(envcfg.env_str("CLIENT_TPU_LOGLEVEL"))

# <checkout>/.jax_cache: three levels up from client_tpu/engine/.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = lockdep.Lock("engine.backend_init")
_devices: list | None = None
_init_seconds: float | None = None


def backend_ready() -> bool:
    return _devices is not None


def init_seconds() -> float | None:
    """Wall seconds the PjRt client took to come up (None before init)."""
    return _init_seconds


def platform() -> str:
    """``platform`` of device 0 as ``ensure_backend`` found it ("tpu",
    "cpu")."""
    return ensure_backend()[0].platform


def pallas_interpret() -> bool:
    """``interpret=`` for every Pallas call site: compiled by Mosaic on a
    TPU, interpreted anywhere else (the hermetic CPU suite runs the same
    kernel bodies).  A process started with ``JAX_PLATFORMS=tpu`` either
    has TPU devices or failed in ``jax.devices()`` — it cannot reach True."""
    return platform() != "tpu"


def _configure_compile_cache(jax) -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # JAX's default writes only executables that took >= 1 s to compile.
    # BERT-base is seconds a bucket, but `simple`, the decode waves and
    # the Pallas kernels compile in well under a second each and a cold
    # `--warmup` server pays hundreds of them.  Cache everything.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def ensure_backend(hard_timeout_s: float = 300.0) -> list:
    """Initialize the JAX backend on the calling thread, with progress logs.

    Returns ``jax.devices()``.  Safe to call repeatedly/concurrently; only the
    first call pays the cost.  The reference counterpart is tritonserver's
    eager CUDA context creation at server start (the piece the reference
    dlopens; our engine owns it, SURVEY.md §7 step 3).
    """
    global _devices, _init_seconds
    if _devices is not None:
        return _devices
    with _lock:
        if _devices is not None:
            return _devices
        t0 = time.monotonic()
        done = threading.Event()

        def _watchdog() -> None:
            warned_hard = False
            while not done.wait(5.0):
                waited = time.monotonic() - t0
                if waited > hard_timeout_s and not warned_hard:
                    warned_hard = True
                    log.error(
                        "JAX backend init exceeded %.0fs — the PjRt plugin "
                        "is likely hung or the chip is held by another "
                        "process; thread stuck in make_c_api_client",
                        hard_timeout_s)
                else:
                    log.info("JAX backend still initializing (%.0fs)...",
                             waited)

        wd = threading.Thread(target=_watchdog, name="jax-init-watchdog",
                              daemon=True)
        wd.start()
        try:
            import jax

            _configure_compile_cache(jax)
            log.info("initializing JAX backend (JAX_PLATFORMS=%s)...",
                     os.environ.get("JAX_PLATFORMS") or "unset")
            devices = jax.devices()
        finally:
            done.set()
        _init_seconds = time.monotonic() - t0
        _devices = devices
        # The program's one compile listener hears every jit from here on;
        # the init just paid is the first set-up span of /v2/profile.
        from client_tpu.observability import spans
        from client_tpu.observability.profiler import (
            install_compile_listener, profiler)

        install_compile_listener()
        profiler().record_process_start(int(t0 * 1e9))
        profiler().record_startup(spans.STARTUP_BACKEND_INIT,
                                  int(t0 * 1e9), time.monotonic_ns())
        log.info("JAX backend ready in %.1fs: platform=%s device_kind=%s "
                 "devices=%d compile_cache=%s",
                 _init_seconds, devices[0].platform, devices[0].device_kind,
                 len(devices), jax.config.jax_compilation_cache_dir)
        return devices
