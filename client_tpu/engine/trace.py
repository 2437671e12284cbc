"""Trace extension: device-level profiling behind the KServe-style
``/v2/trace/setting`` route.

The reference stack has only hand-rolled client timers (SURVEY.md §5.1 —
RequestTimers, common.h:509-589); the server side it talks to exposes
Triton's trace-setting extension. Here the TPU-native equivalent wraps
``jax.profiler``: activating the trace captures XLA/TPU device events
(executable launches, HBM transfers, per-op device time) into a TensorBoard/
Perfetto-compatible log directory, covering every model the engine serves
while active.

Settings vocabulary (mirrors Triton's trace_setting fields where they make
sense): ``trace_level`` — ``["OFF"]`` or ``["TIMESTAMPS"]`` (device events);
``log_dir`` — where the trace is written (``trace_file`` accepted as an
alias on update).

The trace runs with the Python call tracer off (``python_tracer_level=0``):
with it on every Python call of the host path is instrumented, a 2 s trace
takes 22 s to stop and the traced seconds no longer resemble the untraced
ones (PERF.md).  What the host was doing is in the trace all the same: while
it is active the program's own ``gen.*``/``exec.*`` spans
(:mod:`client_tpu.observability.spans`) are ``TraceAnnotation``s on the same
clock as the device operations.
"""

from __future__ import annotations

from client_tpu.observability import spans as _spans
from client_tpu.utils import lockdep

from client_tpu.engine.types import EngineError


class TraceManager:
    """Engine-wide device trace control (jax.profiler start/stop)."""

    def __init__(self):
        self._lock = lockdep.Lock("engine.trace")
        self._log_dir = ""
        self._active = False

    def setting(self) -> dict:
        with self._lock:
            return {
                "trace_level": ["TIMESTAMPS"] if self._active else ["OFF"],
                "log_dir": self._log_dir,
            }

    def update(self, d: dict) -> dict:
        """Apply a settings delta; returns the resulting settings."""
        level = d.get("trace_level")
        log_dir = d.get("log_dir", d.get("trace_file"))
        if isinstance(level, str):
            level = [level]
        want_active = (None if level is None
                       else any(lv and lv.upper() != "OFF" for lv in level))
        with self._lock:
            # Deactivation first: {"trace_level": ["OFF"], "log_dir": new}
            # is the natural stop-and-redirect call and must succeed.
            # Deactivating when no trace is active is a no-op, and a jax
            # error on stop (jax never actually started one — e.g. an
            # earlier start failed halfway, or something else stopped the
            # process-wide profiler) must not wedge this manager active:
            # either way the trace is not running, which is what the
            # caller asked for.
            if want_active is False and self._active:
                import jax

                _spans.set_trace_active(False)
                try:
                    jax.profiler.stop_trace()
                # tpulint: allow[swallowed-exception] already stopped
                except Exception:  # noqa: BLE001 — already stopped
                    pass
                self._active = False
            if log_dir:
                if self._active:
                    raise EngineError(
                        "cannot change log_dir while a trace is active", 400)
                self._log_dir = str(log_dir)
            if want_active and not self._active:
                if not self._log_dir:
                    raise EngineError(
                        "trace activation requires a log_dir", 400)
                import jax

                try:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    jax.profiler.start_trace(self._log_dir,
                                             profiler_options=options)
                except Exception as exc:
                    # A failed start must not leave _active=True (the
                    # next OFF would then call stop_trace on a profiler
                    # that never started). Best-effort stop clears any
                    # half-initialised jax profiler state so a later
                    # start can succeed.
                    try:
                        jax.profiler.stop_trace()
                    # tpulint: allow[swallowed-exception] reviewed fail-open
                    except Exception:  # noqa: BLE001
                        pass
                    raise EngineError(
                        f"failed to start device trace: {exc}", 500)
                self._active = True
                _spans.set_trace_active(True)
        return self.setting()

    def shutdown(self) -> None:
        with self._lock:
            if self._active:
                import jax

                _spans.set_trace_active(False)
                try:
                    jax.profiler.stop_trace()
                # tpulint: allow[swallowed-exception] best-effort on teardown
                except Exception:  # noqa: BLE001 — best-effort on teardown
                    pass
                self._active = False
