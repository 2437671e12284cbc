"""Roofline attribution: XLA's static cost model joined to measured time.

Every other efficiency signal in the stack is host-timed — duty cycle,
fill ratio, and wave latency say how *long* a bucket runs, not how well
it uses the chip. This module supplies the other axis of the roofline
plot: the numerator (static FLOPs / bytes accessed per executable, from
XLA's HLO cost analysis) and the denominator (per-device-kind peak
specs), so the profiler can turn its measured device seconds into
achieved FLOP/s, achieved bytes/s, arithmetic intensity, and MFU/MBU
per (model, version, bucket).

Three deliberately separable pieces:

- :func:`capture_cost_model` — pull ``flops`` / ``bytes accessed`` out
  of ``jitted.lower(*args).cost_analysis()``. The lowering is
  trace-cached after the first real call, so this costs well under a
  millisecond and **never** triggers a backend compile (we never call
  ``.compile()`` here: AOT-compiled executables do not share the jit
  dispatch cache, so compiling one would double every compile).
  ``memory_analysis()`` only exists on *compiled* executables, which the
  jit path never hands out — :func:`capture_memory_analysis` covers
  callers that do hold one. Capture never raises: a backend without a
  cost model (interpret-mode Pallas, exotic plugins) degrades to an
  annotated ``{"available": False, "reason": ...}``.
- the **peak-spec registry** — bf16 peak FLOP/s and HBM bytes/s per
  chip, keyed by the ``device_kind`` string jax reports, overridable
  via ``CLIENT_TPU_ROOFLINE`` (inline JSON or ``@file``). On CPU peaks
  resolve to None and every ratio degrades to ``None`` / ``bound:
  unknown`` — measured-only, never an error. A *TPU* whose kind matches
  no row is a start-up error (:func:`require_device_peaks`): a chip the
  table does not know must not serve with its MFU silently omitted.
- :func:`bucket_roofline` — the pure join: static cost × warm calls
  over measured device seconds, against the resolved peaks. The static
  model counts the *padded* bucket, so padded-fraction × total FLOPs is
  exactly the FLOPs spent multiplying zeros.

Trust the static model only as far as it goes: XLA counts algebraic
FLOPs after fusion/DCE on the optimized HLO, so a bucket that lowers to
a gather (DLRM embedding-bag) legitimately reports ~0 flops and its MFU
is meaningless — look at MBU instead; that asymmetry is what the
``bound`` classification (arithmetic intensity vs the ridge point) is
for.

``bert_flops_per_example`` lives here (not in side-effect-heavy
``bench.py``) so tools/mfu_diag.py and bench share one denominator
without importing a benchmark harness.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

from client_tpu import config as envcfg

__all__ = [
    "ENV_VAR",
    "PEAK_SPECS",
    "PeakSpec",
    "RooflineConfig",
    "bert_flops_per_example",
    "bucket_roofline",
    "capture_cost_model",
    "capture_memory_analysis",
    "classify_bound",
    "detect_device_kind",
    "require_device_peaks",
    "reset_roofline",
    "roofline_config",
    "roofline_context",
]

ENV_VAR = "CLIENT_TPU_ROOFLINE"


@dataclass(frozen=True)
class PeakSpec:
    """Per-chip peak rates. Either field may be None (partially known
    hardware): the ratios that need it degrade to None, the others
    still compute."""

    flops_per_s: float | None   # dense bf16 peak FLOP/s per chip
    bytes_per_s: float | None   # peak HBM bandwidth, bytes/s per chip
    source: str = "registry"

    def ridge(self) -> float | None:
        """Arithmetic intensity (flops/byte) at which the roofline
        bends: below it a kernel is bandwidth-bound, above compute."""
        if not self.flops_per_s or not self.bytes_per_s:
            return None
        return self.flops_per_s / self.bytes_per_s

    def as_dict(self) -> dict:
        return {"flops_per_s": self.flops_per_s,
                "bytes_per_s": self.bytes_per_s,
                "source": self.source}


# Public spec-sheet bf16 peaks per chip, keyed by (lowercased)
# ``device_kind``. HBM numbers are the vendor-quoted bandwidth.
PEAK_SPECS: dict[str, PeakSpec] = {
    "tpu v2": PeakSpec(45e12, 700e9),
    "tpu v3": PeakSpec(123e12, 900e9),
    "tpu v4": PeakSpec(275e12, 1228e9),
    "tpu v5 lite": PeakSpec(197e12, 819e9),
    "tpu v5e": PeakSpec(197e12, 819e9),
    "tpu v5p": PeakSpec(459e12, 2765e9),
    "tpu v6 lite": PeakSpec(918e12, 1640e9),
    "tpu v6e": PeakSpec(918e12, 1640e9),
}

# -- CLIENT_TPU_ROOFLINE ------------------------------------------------------


@dataclass
class RooflineConfig:
    """``CLIENT_TPU_ROOFLINE`` knobs. Grammar matches the other
    observability knobs, defaulting ON: unset/``1``/``on`` captures with
    registry peaks, ``0``/``off`` disables capture, else inline JSON or
    ``@file`` with ``peak_flops`` / ``peak_bytes_per_s`` (forces the
    peaks regardless of detected kind — the only way to get MFU on a
    CPU dev host) and/or ``device_kinds`` (extra registry rows:
    ``{"kind": {"peak_flops": ..., "peak_bytes_per_s": ...}}``)."""

    capture: bool = True
    peak_flops: float | None = None
    peak_bytes_per_s: float | None = None
    device_kinds: dict[str, PeakSpec] | None = None

    @classmethod
    def from_dict(cls, data: dict) -> "RooflineConfig":
        known = {"capture", "peak_flops", "peak_bytes_per_s",
                 "device_kinds"}
        unknown = set(data) - known
        if unknown:
            raise ValueError(
                f"{ENV_VAR}: unknown key(s) {sorted(unknown)}; "
                f"valid: {sorted(known)}")
        cfg = cls()
        if "capture" in data:
            if not isinstance(data["capture"], bool):
                raise ValueError(
                    f"{ENV_VAR}: key 'capture' expects a boolean, "
                    f"got {data['capture']!r}")
            cfg.capture = data["capture"]
        for key in ("peak_flops", "peak_bytes_per_s"):
            if key in data:
                setattr(cfg, key, _positive_number(key, data[key]))
        if "device_kinds" in data:
            kinds = data["device_kinds"]
            if not isinstance(kinds, dict):
                raise ValueError(
                    f"{ENV_VAR}: key 'device_kinds' expects an object")
            cfg.device_kinds = {}
            for kind, spec in kinds.items():
                if not isinstance(spec, dict):
                    raise ValueError(
                        f"{ENV_VAR}: device_kinds[{kind!r}] expects an "
                        "object with peak_flops / peak_bytes_per_s")
                extra = set(spec) - {"peak_flops", "peak_bytes_per_s"}
                if extra:
                    raise ValueError(
                        f"{ENV_VAR}: device_kinds[{kind!r}] unknown "
                        f"key(s) {sorted(extra)}")
                cfg.device_kinds[kind.strip().lower()] = PeakSpec(
                    _positive_number(f"device_kinds[{kind!r}].peak_flops",
                                     spec["peak_flops"])
                    if "peak_flops" in spec else None,
                    _positive_number(
                        f"device_kinds[{kind!r}].peak_bytes_per_s",
                        spec["peak_bytes_per_s"])
                    if "peak_bytes_per_s" in spec else None,
                    source="env")
        return cfg

    @classmethod
    def from_env(cls, environ=None) -> "RooflineConfig":
        raw = envcfg.env_text(ENV_VAR, environ)
        if raw.lower() in ("0", "false", "off"):
            return cls(capture=False)
        if not raw or raw.lower() in ("1", "true", "on"):
            return cls()
        if raw.startswith("@"):
            try:
                with open(raw[1:]) as f:
                    raw = f.read()
            except OSError as exc:
                raise ValueError(
                    f"{ENV_VAR}: cannot read '{raw[1:]}': {exc}") from None
        try:
            data = json.loads(raw)
        except json.JSONDecodeError as exc:
            raise ValueError(f"{ENV_VAR}: invalid JSON ({exc})") from None
        if not isinstance(data, dict):
            raise ValueError(f"{ENV_VAR}: expected a JSON object")
        return cls.from_dict(data)

    def resolve_peaks(self, device_kind: str) -> PeakSpec | None:
        """Peaks for a detected kind: an explicit env ``peak_flops`` /
        ``peak_bytes_per_s`` pair wins outright (that is the CPU-host
        escape hatch), then env ``device_kinds`` rows, then the built-in
        registry; None when nothing matches (``peaks: unknown``)."""
        if self.peak_flops is not None or self.peak_bytes_per_s is not None:
            return PeakSpec(self.peak_flops, self.peak_bytes_per_s,
                            source="env")
        kind = device_kind.strip().lower()
        for table, src in ((self.device_kinds or {}, "env"),
                           (PEAK_SPECS, "registry")):
            spec = table.get(kind)
            if spec is None:
                # Substring match: libtpu has reported both "TPU v5e"
                # and "TPU v5 lite" for the same part across versions.
                for key, candidate in table.items():
                    if key and key in kind:
                        spec = candidate
                        break
            if spec is not None:
                return PeakSpec(spec.flops_per_s, spec.bytes_per_s,
                                source=src)
        return None


def _positive_number(key: str, raw) -> float:
    if isinstance(raw, bool) or not isinstance(raw, (int, float)):
        raise ValueError(f"{ENV_VAR}: key '{key}' expects a number, "
                         f"got {raw!r}")
    value = float(raw)
    if value <= 0:
        raise ValueError(f"{ENV_VAR}: key '{key}' must be > 0")
    return value


def roofline_config(environ=None) -> RooflineConfig:
    """Parse ``CLIENT_TPU_ROOFLINE`` (fresh each call — it is a few
    string compares for the common unset case). Raises ValueError on a
    malformed value; the engine resolves it once at startup so operators
    fail fast, while the snapshot path catches and annotates instead."""
    return RooflineConfig.from_env(environ)


# -- device detection ---------------------------------------------------------

_detected_kind: str | None = None


def detect_device_kind() -> str:
    """``device_kind`` of device 0 ("TPU v5 lite", "cpu", ...); cached
    for the process — a backend cannot change under a running server.
    "unknown" when jax is absent or unhappy, never an exception."""
    global _detected_kind
    if _detected_kind is None:
        try:
            import jax

            devices = jax.devices()
            kind = getattr(devices[0], "device_kind", "") if devices else ""
            _detected_kind = str(kind) or "unknown"
        except Exception:  # noqa: BLE001 — detection is advisory
            _detected_kind = "unknown"
    return _detected_kind


def roofline_context(environ=None) -> dict:
    """The resolved roofline environment for snapshot headers:
    ``{"device_kind", "peaks": {...} | "unknown"}`` plus a
    ``config_error`` annotation instead of a raise when the env knob is
    malformed (the profile surface must render regardless)."""
    try:
        cfg = roofline_config(environ)
    except ValueError as exc:
        return {"device_kind": detect_device_kind(), "peaks": "unknown",
                "config_error": str(exc)}
    kind = detect_device_kind()
    peaks = cfg.resolve_peaks(kind)
    return {
        "device_kind": kind,
        "peaks": peaks.as_dict() if peaks else "unknown",
    }


def resolve_peaks(environ=None) -> PeakSpec | None:
    """Peaks only (gauge refresh path); None on malformed env too —
    fail-fast belongs to engine startup, not the scrape loop."""
    try:
        return roofline_config(environ).resolve_peaks(detect_device_kind())
    except ValueError:
        return None


def require_device_peaks(devices, environ=None) -> None:
    """Engine start-up check: a ``tpu`` device whose ``device_kind``
    matches no peaks row (registry or ``CLIENT_TPU_ROOFLINE``) raises.
    Other platforms pass — their peaks stay ``"unknown"``."""
    dev = devices[0]
    if dev.platform != "tpu":
        return
    if roofline_config(environ).resolve_peaks(dev.device_kind) is None:
        raise RuntimeError(
            f"no roofline peaks for TPU device_kind {dev.device_kind!r}: "
            f"add a row to PEAK_SPECS (client_tpu/observability/"
            f"roofline.py) or name it in {ENV_VAR} device_kinds")


def reset_roofline() -> None:
    """Forget the cached device-kind detection (tests)."""
    global _detected_kind
    _detected_kind = None


# -- static cost capture ------------------------------------------------------


def capture_cost_model(jitted, args=(), kwargs=None,
                       config: RooflineConfig | None = None) -> dict:
    """Static cost of one jitted callable at one signature, via
    ``jitted.lower(*args).cost_analysis()``.

    Returns ``{"available": True, "flops", "bytes_accessed",
    "transcendentals"}`` or ``{"available": False, "reason": ...}`` —
    never raises, never compiles (see module docstring). Call it right
    after the first real execution: the lowering is then trace-cached
    and this is sub-millisecond dict work.
    """
    if config is None:
        try:
            config = roofline_config()
        except ValueError:
            # Malformed env: the engine fail-fasted at startup if it
            # could; a late mutation must not break the serve path.
            config = RooflineConfig()
    if not config.capture:
        return {"available": False, "reason": f"disabled by {ENV_VAR}"}
    try:
        lower = getattr(jitted, "lower", None)
        if lower is None:
            return {"available": False,
                    "reason": "callable has no .lower (not jitted)"}
        lowered = lower(*args, **(kwargs or {}))
        analysis = lowered.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else None
        if not isinstance(analysis, dict):
            return {"available": False,
                    "reason": "cost_analysis returned "
                              f"{type(analysis).__name__}"}
        flops = analysis.get("flops")
        byts = analysis.get("bytes accessed")
        if flops is None and byts is None:
            return {"available": False,
                    "reason": "cost_analysis has neither 'flops' nor "
                              "'bytes accessed'"}
        return {
            "available": True,
            # XLA uses -1 as "unknown" for some ops; clamp, don't poison.
            "flops": max(0.0, float(flops or 0.0)),
            "bytes_accessed": max(0.0, float(byts or 0.0)),
            "transcendentals": max(
                0.0, float(analysis.get("transcendentals") or 0.0)),
        }
    except Exception as exc:  # noqa: BLE001 — degrade, never 500
        return {"available": False,
                "reason": f"{type(exc).__name__}: {exc}"[:200]}


def capture_memory_analysis(compiled) -> dict:
    """``memory_analysis()`` where a *compiled* executable is actually in
    hand (the jit dispatch path never exposes one — see module
    docstring); same never-raise contract as cost capture."""
    try:
        mem = compiled.memory_analysis()
        if mem is None:
            return {"available": False,
                    "reason": "memory_analysis returned None"}
        out = {"available": True}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            value = getattr(mem, attr, None)
            if value is not None:
                out[attr] = int(value)
        return out
    except Exception as exc:  # noqa: BLE001 — degrade, never 500
        return {"available": False,
                "reason": f"{type(exc).__name__}: {exc}"[:200]}


# -- the join -----------------------------------------------------------------


def classify_bound(intensity: float | None,
                   peaks: PeakSpec | None) -> str:
    """``compute`` | ``bandwidth`` | ``unknown``: arithmetic intensity
    against the device ridge point. Unknown when either side is."""
    if intensity is None or peaks is None:
        return "unknown"
    ridge = peaks.ridge()
    if ridge is None:
        return "unknown"
    return "bandwidth" if intensity < ridge else "compute"


def bucket_roofline(cost: dict | None, calls: int, device_s: float,
                    padded_fraction: float = 0.0,
                    peaks: PeakSpec | None = None) -> dict:
    """Join one bucket's static cost model with its measured warm-call
    device seconds. ``calls`` must be the *warm* execution count —
    ``device_s`` excludes cold (compiling) calls, so the rates divide
    like with like. Cost-model-less buckets return the annotated
    absence the satellite demands, with ``bound: unknown``."""
    if not cost or not cost.get("available"):
        return {
            "cost_model": "unavailable",
            "reason": (cost or {}).get("reason", "not captured"),
            "bound": "unknown",
        }
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes_accessed", 0.0))
    calls = max(0, int(calls))
    intensity = (flops / byts) if byts > 0 else None
    out = {
        "cost_model": "xla",
        "flops_per_call": flops,
        "bytes_per_call": byts,
        "arithmetic_intensity": round(intensity, 4)
        if intensity is not None else None,
        "total_flops": flops * calls,
        "total_bytes": byts * calls,
        # The static model prices the padded bucket, so the padded row
        # fraction of its FLOPs was spent multiplying zeros.
        "padding_wasted_flops": flops * calls * max(
            0.0, min(1.0, padded_fraction)),
        "achieved_flops_per_s": None,
        "achieved_bytes_per_s": None,
        "mfu": None,
        "mbu": None,
        "bound": classify_bound(intensity, peaks),
    }
    if device_s > 0 and calls > 0:
        achieved_f = flops * calls / device_s
        achieved_b = byts * calls / device_s
        out["achieved_flops_per_s"] = achieved_f
        out["achieved_bytes_per_s"] = achieved_b
        if peaks and peaks.flops_per_s:
            out["mfu"] = round(achieved_f / peaks.flops_per_s, 6)
        if peaks and peaks.bytes_per_s:
            out["mbu"] = round(achieved_b / peaks.bytes_per_s, 6)
    return out


# -- shared analytic denominators --------------------------------------------


def bert_flops_per_example(seq_len=128, hidden=768, n_layers=12, ffn=3072):
    """Analytic forward FLOPs for one BERT-base example (2*MAC convention):
    per layer 4 QKVO projections + 2 attention einsums + 2 FFN matmuls.
    Shared by bench's MFU probe and tools/mfu_diag.py — one denominator,
    one place to get it wrong."""
    s, h, f = seq_len, hidden, ffn
    per_layer = 8 * s * h * h + 4 * s * s * h + 4 * s * h * f
    return n_layers * per_layer
