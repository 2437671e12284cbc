"""Model execution: JAX callables compiled per batch bucket.

TPU-first executor design:

- a model backend supplies a *pure* ``apply(inputs) -> outputs`` pytree
  function (optionally closed over weights) which the engine wraps in
  ``jax.jit`` once — XLA's jit cache then keys on concrete shapes/dtypes;
- XLA wants static shapes, so variable client batches are padded up to a
  small set of pre-declared buckets (powers of two by default,
  ``ModelConfig.effective_buckets``) before entering the jitted call — this is
  the TPU answer to Triton's dynamic batch shapes (SURVEY.md §7 hard part 5);
- inputs move host→HBM via ``jax.device_put`` (or are already device-resident
  when supplied through ``tpu_shared_memory``), outputs come back as numpy
  unless the client asked for device placement.

Backends implement the small :class:`ModelBackend` protocol; the model zoo in
``client_tpu.models`` provides concrete ones.
"""

from __future__ import annotations

import threading
from client_tpu.utils import lockdep
from dataclasses import dataclass
from typing import Any, Callable, Iterator

import numpy as np

from client_tpu import faults
from client_tpu.engine.backend_init import log as _log
from client_tpu.engine.config import ModelConfig
from client_tpu.engine.types import DeadlineExpired, EngineError, now_ns
from client_tpu.observability import roofline as _roofline
from client_tpu.observability import spans as _spans
from client_tpu.observability.profiler import (
    clear_compile_scope as _clear_compile_scope,
    profiler as _profiler,
    set_compile_scope as _set_compile_scope,
)
from client_tpu.protocol.dtypes import wire_to_np_dtype


@dataclass
class ExecPhases:
    """Absolute-ns boundaries of one execution's three device phases.

    Measured (not fabricated): staging blocks until inputs are committed to
    HBM, infer blocks until the executable finishes, fetch covers the D2H
    copies.  This is the per-execution truth behind the statistics RPC's
    compute_input / compute_infer / compute_output split (reference
    inference_profiler.cc:836-908 differences these per window).
    """

    start: int = 0        # staging begins (device_put)
    input_end: int = 0    # inputs resident in HBM
    infer_end: int = 0    # XLA executable complete
    output_end: int = 0   # outputs on host (or staged to shm)
    # First call for this input signature: the infer interval includes the
    # XLA trace+compile, measured here so schedulers/frontends can flag
    # the request cold (Server-Timing `compile`, trace span args) and the
    # profiler can keep compile time out of the duty-cycle window.
    compile_ns: int = 0


class ModelBackend:
    """Protocol for model implementations.

    Required: ``config`` attribute and :meth:`make_apply` *or*
    :meth:`make_apply_params`. Decoupled models implement :meth:`generate`
    instead of/alongside ``make_apply``.
    """

    config: ModelConfig

    # Optional orbax checkpoint directory; when set, param-backends restore
    # their weights from it instead of using the random init (see
    # client_tpu.engine.checkpoint and load_or_init_params).
    weights_path: str | None = None

    def load_or_init_params(self, init_fn):
        """``init_fn()`` builds the params tree (random init); when
        ``weights_path`` is set, the same-structured tree is restored from
        the checkpoint instead (structure/shape mismatches fail the model
        load with a clear error)."""
        if self.weights_path:
            import jax

            from client_tpu.engine.checkpoint import load_params

            # Abstract target: same structure/shape/dtype check without
            # materializing (and immediately discarding) the random init.
            abstract = jax.eval_shape(init_fn)
            return load_params(self.weights_path, abstract)
        return init_fn()

    def make_apply_params(
        self,
    ) -> tuple[Callable[[Any, dict], dict], Any] | None:
        """Optional: ``(apply(params, inputs), placed_params)``.

        Backends with real weights should implement this instead of closing
        ``apply`` over them: closed-over arrays become XLA *constants*, which
        bakes hundreds of MB into the program and blows compile time (BERT-base
        measured 167s as constants vs 4.5s as arguments on a v5e chip).  The
        returned params pytree must already be placed (``jax.device_put``,
        sharded for mesh backends); the engine passes it as the first jit
        argument on every execution.
        """
        return None

    def make_apply(self) -> Callable[[dict], dict]:
        """Compat / host-model entry: ``apply(inputs)`` with weights bound.

        Param-backends get this for free via :meth:`make_apply_params`;
        parameterless or host-side backends override it directly.
        """
        pair = self.make_apply_params()
        if pair is None:
            raise NotImplementedError
        fn, params = pair
        return lambda inputs: fn(params, inputs)

    def generate(self, inputs: dict[str, np.ndarray],
                 parameters: dict[str, Any]) -> Iterator[dict[str, np.ndarray]]:
        raise EngineError(
            f"model '{self.config.name}' does not support decoupled execution")

    # Sequence models: apply signature is (state, inputs) -> (state, outputs)
    # and initial_state() supplies per-sequence state. See sequence.py.
    def initial_state(self):
        return None


class Model:
    """A loaded model: backend + jitted executable + bucket padding."""

    def __init__(self, backend: ModelBackend, jit: bool = True):
        import jax

        self.backend = backend
        self.config = backend.config
        # Per-input validation metadata, built once — the config is immutable
        # after load, and per-request dict/dtype rebuilds showed up at
        # ~15us/request in the host-path profile.
        self._input_meta = {
            t.name: (t,
                     np.dtype(wire_to_np_dtype(t.data_type))
                     if t.data_type != "BYTES" else None,
                     tuple(t.dims))
            for t in self.config.input
        }
        self._lock = lockdep.Lock("engine.model")
        self._apply = None
        self._jitted = False
        self._params = None
        self._takes_params = False
        if not self.config.ensemble_scheduling:
            pair = backend.make_apply_params()
            if pair is not None:
                # Weights travel as jit arguments (device-resident, possibly
                # mesh-sharded) — never as closure constants. See
                # ModelBackend.make_apply_params.
                apply_fn, self._params = pair
                self._takes_params = True
                # HBM census attribution: the placed pytree is the
                # model's device-resident weight set. overwrite=False so
                # leaves the backend already tagged with a more specific
                # component (DLRM embedding tables) keep that owner.
                from client_tpu.observability.memory import hbm_census

                hbm_census().tag(self.config.name, "weights", self._params,
                                 overwrite=False)
            else:
                apply_fn = backend.make_apply()
            jittable = getattr(backend, "jittable", True)
            self._jitted = jit and jittable
            # The XLA module is jit_apply whatever the backend called its
            # function (a lambda would be jit__lambda_): the device-trace
            # reduction finds the batcher's step by that name.
            self._apply = (jax.jit(_spans.named_step(apply_fn,
                                                     _spans.STEP_APPLY))
                           if self._jitted else apply_fn)
        self._jax = jax
        # Live execution states for timeout diagnostics ("compiling" vs
        # "dead"), keyed by executing thread so concurrent instances don't
        # clobber each other (dict ops are GIL-atomic). Read via `.state`.
        self._states: dict[int, str] = {}
        self._compiled: set = set()  # input-signature tuples already traced

    def raw_apply(self) -> Callable[[dict], Any]:
        """The jitted executable with the calling convention resolved:
        ``raw_apply()(staged_inputs)`` regardless of whether weights travel
        as a jit argument. For benchmarking/diagnostics that bypass the
        scheduler; staging and fetch are the caller's business."""
        if self._apply is None:
            raise EngineError(
                f"model '{self.config.name}' has no executable", 500)
        if self._takes_params:
            return lambda inputs: self._apply(self._params, inputs)
        return self._apply

    @property
    def state(self) -> str:
        """Summary of in-flight executions ('idle' when none)."""
        active = list(self._states.values())
        return "; ".join(active) if active else "idle"

    def _set_state(self, s: str, step: str = _spans.STEP_APPLY,
                   bucket=0) -> None:
        """Brackets (with :meth:`_clear_state`) every jit call site of this
        model: ``s`` for timeout diagnostics, ``(step, bucket)`` for the
        compile listener — a compilation inside the bracket is booked on
        this model's scope."""
        self._states[threading.get_ident()] = s
        _set_compile_scope(self.config.name, self.config.version, step,
                           bucket or 0)

    def _clear_state(self) -> None:
        self._states.pop(threading.get_ident(), None)
        _clear_compile_scope()

    # -- shape/validation helpers -------------------------------------------

    def validate_inputs(self, inputs: dict[str, np.ndarray],
                        batched: bool) -> int:
        """Check names/dtypes/shapes; returns the request batch size (1 if
        the model is unbatched)."""
        cfg = self.config
        batch = 1
        declared = self._input_meta
        for name, (t, _, _) in declared.items():
            if name not in inputs and not t.optional:
                raise EngineError(
                    f"missing input '{name}' for model '{cfg.name}'")
        for name, arr in inputs.items():
            entry = declared.get(name)
            if entry is None:
                raise EngineError(
                    f"unexpected input '{name}' for model '{cfg.name}'")
            tc, np_dt, dims = entry
            if np_dt is not None and np_dt != arr.dtype:
                raise EngineError(
                    f"input '{name}': dtype {arr.dtype} != declared "
                    f"{tc.data_type}")
            shape = list(arr.shape)
            if cfg.max_batch_size > 0 and batched and not tc.ragged:
                if len(shape) != len(dims) + 1:
                    raise EngineError(
                        f"input '{name}': expected batched rank {len(dims)+1}, "
                        f"got shape {shape}")
                batch = shape[0]
                shape = shape[1:]
            if len(shape) != len(dims):
                raise EngineError(
                    f"input '{name}': rank mismatch, {shape} vs dims {dims}")
            for got, want_d in zip(shape, dims):
                if want_d != -1 and got != want_d:
                    raise EngineError(
                        f"input '{name}': shape {shape} incompatible with "
                        f"dims {dims}")
        if cfg.max_batch_size > 0 and batch > cfg.max_batch_size:
            raise EngineError(
                f"batch size {batch} exceeds max_batch_size "
                f"{cfg.max_batch_size} for '{cfg.name}'")
        # Ragged backends (DLRM CSR) check cross-tensor structure the
        # per-tensor loop can't see: offsets monotonicity, nnz ceilings,
        # offsets/indices length agreement.
        check = getattr(self.backend, "validate_ragged", None)
        if check is not None and batched:
            check(inputs, batch)
        return batch

    def pick_bucket(self, batch: int) -> int:
        """Smallest ladder bucket covering ``batch`` units along the
        model's padding axis (rows, or summed lookups for ragged DLRM)."""
        for b in self.config.effective_buckets():
            if b >= batch:
                return b
        return self.config.axis_capacity()

    # -- execution ----------------------------------------------------------

    def execute(self, inputs: dict[str, np.ndarray],
                batch_size: int | None = None) -> dict[str, np.ndarray]:
        """Run one (possibly padded) batch; see :meth:`execute_timed`."""
        outputs, _ = self.execute_timed(inputs, batch_size=batch_size)
        return outputs

    def execute_timed(
        self, inputs: dict[str, np.ndarray], batch_size: int | None = None,
        fetch_outputs: bool = True, deadline_ns: int = 0,
        pad_to: int | None = None, synthetic: bool = False,
    ) -> tuple[dict[str, np.ndarray], ExecPhases]:
        """Run one (possibly padded) batch through the jitted executable.

        ``batch_size``: true batch before padding; outputs are sliced back.
        ``fetch_outputs=False`` (in-process device-resident tpu-shm plane):
        skip the D2H fetch and return HBM-resident ``jax.Array`` outputs —
        the caller is directing every output into a device region, so
        pulling the batch to host only to ``device_put`` it straight back
        would be pure staging waste.
        ``deadline_ns`` (absolute ``now_ns()``; 0 = none): raise
        :class:`DeadlineExpired` instead of dispatching when the batch's
        end-to-end budget has already lapsed.
        ``pad_to`` overrides bucket selection (normally
        ``pick_bucket(batch_size)``): the autotuner uses it to compile a
        candidate bucket that is not yet in the ladder — without the
        override the rows would pad up to the next *existing* bucket and
        XLA would cache the wrong shape.
        ``synthetic=True`` (warmup / tuner compile probes): the execution
        is excluded from the profiler's traffic statistics — a full-fill
        dummy batch would otherwise poison the bucket's ``max_rows`` and
        fill evidence, suppressing ladder suggestions for real traffic.
        Compile telemetry is still recorded (a compile is a compile).
        Returns the outputs plus measured :class:`ExecPhases` — each phase is
        bounded by a real device sync (device_put committed / executable
        done / D2H complete), so the statistics the scheduler records are
        observations, not allocations of a single wall-time number.
        """
        if self._apply is None:
            raise EngineError(
                f"model '{self.config.name}' is an ensemble; "
                "execute composing models instead", 500)
        # Deadline backstop: the scheduler filters expired requests at
        # dequeue and pre-dispatch, but batch assembly takes time — this
        # closes the race so device dispatch never runs for a batch whose
        # every member has given up (deadline_ns is the LATEST member
        # deadline; 0 means at least one member has no deadline).
        if deadline_ns > 0 and now_ns() >= deadline_ns:
            raise DeadlineExpired(
                f"end-to-end deadline expired before execution of model "
                f"'{self.config.name}'")
        # Chaos site: model execution — the deepest injection point,
        # exercising the scheduler's batch-failure fan-out and the
        # frontends' 5xx translation from a device-level fault.
        try:
            faults.fire("model.execute")
        except faults.FaultInjected as exc:
            raise EngineError(str(exc), exc.status or 503) from None
        cfg = self.config
        phases = ExecPhases(start=now_ns())
        if pad_to is None and cfg.axis_capacity() > 0 \
                and batch_size is not None:
            pad_to = self.pick_bucket(batch_size)

        # The three phases are exec.* annotations while a device trace is
        # active (their aggregate times are the profiler's host_s/device_s).
        ann = _spans.begin(_spans.EXEC_STAGE)
        try:
            self._set_state(f"staging inputs (bucket={pad_to})",
                            bucket=pad_to)
            # Ragged backends own their padding: the generic row-pad below
            # would stretch every tensor's leading dim to the *lookup*
            # bucket, which is only right for the indices tensor. The hook
            # converts CSR {indices, offsets} into the model's static-shape
            # device layout (padded indices + segment ids, rows padded to
            # max_batch_size) and nothing downstream pads again.
            pre_stage = getattr(self.backend, "pre_stage", None)
            if pre_stage is not None:
                inputs = pre_stage(inputs, pad_to)
            # Multi-chip backends declare per-input shardings (e.g. batch
            # over "dp"); device_put then scatters straight onto the mesh
            # and GSPMD propagates layouts from there (parallel/serving.py).
            shardings = getattr(self.backend, "input_shardings", None) or {}
            staged = {}
            for name, arr in inputs.items():
                if arr.dtype == np.object_ or not self._jitted:
                    staged[name] = arr  # BYTES / host models stay host-side
                    continue
                if pre_stage is None and pad_to is not None \
                        and arr.shape[0] < pad_to:
                    pad_width = [(0, pad_to - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
                    if isinstance(arr, self._jax.Array):
                        # device-resident (tpu-shm region): pad on device,
                        # don't round-trip through host
                        import jax.numpy as jnp

                        arr = jnp.pad(arr, pad_width)
                    else:
                        arr = np.pad(arr, pad_width)
                sharding = shardings.get(name)
                staged[name] = (self._jax.device_put(arr, sharding)
                                if sharding is not None
                                else self._jax.device_put(arr))
            # No device sync here: the H2D commit pipelines with executable
            # dispatch under async dispatch, so input_end bounds the *host*
            # staging work (concat/pad/enqueue); syncing would add a device
            # round-trip per batch just to sharpen a timestamp.
            phases.input_end = now_ns()

            sig = tuple(sorted((n, tuple(a.shape), str(getattr(a, "dtype", "")))
                               for n, a in staged.items()))
            first = self._jitted and sig not in self._compiled
            self._set_state(
                f"compiling bucket={pad_to} (first call, XLA compile can "
                "take 20-40s on TPU)" if first
                else f"executing (bucket={pad_to})", bucket=pad_to)
            _spans.end(ann)
            ann = _spans.begin(_spans.EXEC_RUN)
            outputs = (self._apply(self._params, staged)
                       if self._takes_params else self._apply(staged))
            if not isinstance(outputs, dict):
                raise EngineError(
                    f"model '{cfg.name}' returned {type(outputs)}, "
                    "expected dict", 500)
            device_outs = [v for v in outputs.values()
                           if isinstance(v, self._jax.Array)]
            # Enqueue all D2H copies *before* waiting on compute: each copy
            # starts the moment its buffer is ready, exactly as the untimed
            # path pipelined it, so the block below costs one host wake-up,
            # not a serialization of compute against transfer. (Outputs
            # spanning other processes' devices can't be host-copied here;
            # they go through the allgather below instead.)
            if fetch_outputs:
                for val in device_outs:
                    if val.is_fully_addressable:
                        val.copy_to_host_async()
            if device_outs:
                # Executable-complete boundary (device buffers ready).
                self._jax.block_until_ready(device_outs)
            if first:
                self._compiled.add(sig)
                phases.compile_ns = now_ns() - phases.input_end
                _log.info("model '%s': compiled bucket=%s in %.1fs",
                          cfg.name, pad_to, phases.compile_ns / 1e9)
                _profiler().record_compile(
                    cfg.name, cfg.version, pad_to, phases.compile_ns,
                    axis=cfg.padding_axis)
                # Static roofline numerator, once per first-call trace:
                # the lowering is trace-cached by the execution above, so
                # this is dict work — and it never .compile()s (an AOT
                # compile would not share the jit dispatch cache).
                cost = _roofline.capture_cost_model(
                    self._apply,
                    (self._params, staged) if self._takes_params
                    else (staged,))
                _profiler().record_cost_model(
                    cfg.name, cfg.version, pad_to, cost,
                    axis=cfg.padding_axis)
            phases.infer_end = now_ns()
            self._set_state("fetching outputs", bucket=pad_to)
            _spans.end(ann)
            ann = _spans.begin(_spans.EXEC_FETCH)
            host: dict[str, np.ndarray] = {}
            for name, val in outputs.items():
                if not fetch_outputs and isinstance(val, self._jax.Array):
                    # Device-resident return: skip the batch trim — slicing
                    # a jax.Array dispatches an execution; the caller
                    # windows per-request ranges with zero-dispatch views
                    # (padding sits past every real request's range).
                    host[name] = val
                    continue
                arr = self._fetch_host(val)
                # Lookup-bucketed models pad the *lookup* axis; output rows
                # are already exact (the backend padded rows statically), so
                # slicing to batch_size==nnz here would corrupt them whenever
                # a row count collides with a lookup bucket.
                if pad_to is not None and batch_size is not None \
                        and cfg.padding_axis == "rows" \
                        and arr.ndim >= 1 and arr.shape[0] == pad_to:
                    arr = arr[:batch_size]
                host[name] = arr
            phases.output_end = now_ns()
            if synthetic:
                return host, phases  # dummy rows are not traffic
            # Efficiency attribution: one profiler record per batch (not
            # per request) keeps the always-on cost under a microsecond.
            _profiler().record_execution(
                cfg.name, cfg.version, pad_to,
                rows=batch_size if batch_size is not None else 1,
                device_ns=phases.infer_end - phases.input_end,
                host_ns=(phases.input_end - phases.start)
                + (phases.output_end - phases.infer_end),
                cold=bool(phases.compile_ns),
                axis=cfg.padding_axis)
            return host, phases
        finally:
            # Always clear: a raise mid-compile must not leave a stale
            # "compiling" state to misdirect later timeout diagnostics.
            self._clear_state()
            _spans.end(ann)

    def _fetch_host(self, val) -> np.ndarray:
        """Device→host fetch that works under multihost: an output sharded
        over a global mesh spans devices this process cannot address, so a
        plain ``np.asarray`` raises — allgather the shards first (one
        compiled collective, cached per sharding/shape; on a pod it rides
        DCN exactly like the data-parallel gradient traffic)."""
        if isinstance(val, self._jax.Array) and not val.is_fully_addressable:
            from jax.experimental import multihost_utils

            val = multihost_utils.process_allgather(val, tiled=True)
        return np.asarray(val)

    def execute_stateful(self, state, inputs: dict[str, np.ndarray]):
        """Sequence-model step: ``apply(state, inputs) -> (state, outputs)``.

        State is an explicit pytree living in HBM between requests; the whole
        step is jitted, so repeated steps of a sequence reuse one executable.
        """
        if self._apply is None:
            raise EngineError(
                f"model '{self.config.name}' has no executable", 500)
        staged = {
            name: arr if arr.dtype == np.object_ else self._jax.device_put(arr)
            for name, arr in inputs.items()
        }
        try:
            self._set_state("executing sequence step")
            new_state, outputs = self._apply(state, staged)
            if not isinstance(outputs, dict):
                raise EngineError(
                    f"model '{self.config.name}' returned {type(outputs)}, "
                    "expected dict", 500)
            for val in outputs.values():
                if isinstance(val, self._jax.Array) \
                        and val.is_fully_addressable:
                    val.copy_to_host_async()
            host = {name: self._fetch_host(val)
                    for name, val in outputs.items()}
            return new_state, host
        finally:
            self._clear_state()

    def warm_bucket(self, bucket: int) -> float:
        """Compile the executable for one batch bucket by executing zero
        inputs at exactly ``bucket`` rows (``pad_to`` override — the
        bucket need not be in the ladder yet). Runs on the *caller's*
        thread: the autotuner pays the XLA compile here, off the
        scheduler hot path, before promoting the bucket. Returns the
        measured compile seconds (0.0 when the shape was already cached
        or the model can't take dummy zeros, e.g. BYTES inputs)."""
        cfg = self.config
        cap = cfg.axis_capacity()
        if self._apply is None or cap <= 0:
            return 0.0
        bucket = int(bucket)
        if not 1 <= bucket <= cap:
            raise EngineError(
                f"bucket {bucket} out of range 1..{cap} "
                f"for model '{cfg.name}'")
        synth = getattr(self.backend, "synthetic_inputs", None)
        if synth is not None:
            # Ragged backends build their own dummy batch for a lookup
            # bucket — generic [bucket]+dims zeros have the wrong axis.
            inputs = synth(bucket)
        else:
            inputs = {}
            for tc in cfg.input:
                if tc.data_type == "BYTES":
                    return 0.0  # zeros can't stand in for string inputs
                dims = [d if d != -1 else 1 for d in tc.dims]
                inputs[tc.name] = np.zeros(
                    [bucket] + dims, dtype=wire_to_np_dtype(tc.data_type))
        _, phases = self.execute_timed(
            inputs, batch_size=bucket, pad_to=bucket, synthetic=True)
        return phases.compile_ns / 1e9

    def swap_buckets(self, buckets: list[int]) -> list[int]:
        """Atomically replace the bucket ladder. The new ladder is
        deduplicated, clamped to ``1..axis_capacity()`` (max_batch_size,
        or max_lookups for lookup-bucketed models), and always keeps the
        capacity itself so ``pick_bucket`` covers every legal batch. Safe
        concurrent with in-flight executions: readers see either the old
        or the new list (reference assignment), and a batch that already
        picked a retired bucket still runs — its executable stays in the
        jit cache. Returns the ladder applied."""
        cfg = self.config
        cap = cfg.axis_capacity()
        if cap <= 0:
            raise EngineError(
                f"model '{cfg.name}' is unbatched; no bucket ladder")
        new = sorted({int(b) for b in buckets
                      if 1 <= int(b) <= cap}
                     | {cap})
        cfg.batch_buckets = new
        return new

    def warmup(self) -> None:
        """Pre-compile every bucket with zero inputs so first real requests
        don't pay XLA compile latency (first compile ~20-40s on TPU)."""
        cfg = self.config
        if self._apply is None or getattr(self.backend, "generative", False):
            # A generative backend's tokens come from its scheduler's
            # programs, which that warms; no request reaches its
            # full-context ``apply`` (a diagnostic entry), and compiling it
            # here cost a published-size decoder 8.5 s of every launch.
            return
        _log.info("model '%s': warmup over buckets %s",
                  cfg.name, cfg.effective_buckets())
        synth = getattr(self.backend, "synthetic_inputs", None)
        for bucket in cfg.effective_buckets():
            if synth is not None:
                inputs = synth(max(bucket, 1))
            else:
                inputs = {}
                for tc in cfg.input:
                    if tc.data_type == "BYTES":
                        continue
                    dims = [d if d != -1 else 1 for d in tc.dims]
                    shape = ([bucket] if cfg.max_batch_size > 0 else []) + dims
                    inputs[tc.name] = np.zeros(
                        shape, dtype=wire_to_np_dtype(tc.data_type))
                if len(inputs) < len([t for t in cfg.input
                                      if t.data_type != "BYTES"]):
                    continue
            try:
                self.execute_timed(
                    inputs,
                    batch_size=bucket if cfg.axis_capacity() > 0 else None,
                    synthetic=True)
            except EngineError:
                raise
            except Exception:
                # Models with data-dependent preprocessing may reject zeros;
                # warmup is best-effort.
                return
