"""Central registry for every ``CLIENT_TPU_*`` environment variable.

Before this module, ~20 env reads were scattered across the tree, each
with its own inline default and no single place that said what knobs
exist — so a typo'd variable name failed silently and docs drifted from
code. Every ``CLIENT_TPU_*`` read now goes through the accessors here
against a declared :class:`EnvVar` (name, default, parser, doc line),
which gives three properties at once:

* one source of truth the docs table is *generated* from
  (``python -m client_tpu.config --markdown`` → docs/CONFIG.md);
* tpulint (tools/analyze, check ``env-registry``) can statically verify
  that no code path reads ``os.environ["CLIENT_TPU_..."]`` directly and
  that every registered name is documented;
* reading an *unregistered* name raises at the call site instead of
  silently returning a default.

The accessors accept an ``environ`` mapping so config objects keep their
testable ``from_env(environ={...})`` signatures. Stdlib-only: safe to
import from anywhere (including ``client_tpu.utils.lockdep``) without
cycles.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = [
    "EnvVar",
    "register",
    "registered",
    "env_text",
    "env_str",
    "env_int",
    "env_float",
    "env_flag",
    "render_markdown_table",
]


@dataclass(frozen=True)
class EnvVar:
    """One declared environment knob."""

    name: str
    default: str       # raw default applied when unset ("" = unset/off)
    kind: str          # str | int | float | flag | json — documentation +
                       # which accessor the readers use
    doc: str           # one generated docs-table line
    subsystem: str     # docs-table grouping


_REGISTRY: dict[str, EnvVar] = {}


def register(name: str, default: str, kind: str, doc: str,
             subsystem: str) -> str:
    """Declare one variable; returns the name so modules can bind it to
    their legacy ``ENV_VAR`` constants."""
    if not name.startswith("CLIENT_TPU_"):
        raise ValueError(f"env registry only covers CLIENT_TPU_*: {name!r}")
    if name in _REGISTRY:
        raise ValueError(f"env var {name!r} registered twice")
    _REGISTRY[name] = EnvVar(name, default, kind, doc, subsystem)
    return name


def registered() -> dict[str, EnvVar]:
    return dict(_REGISTRY)


def _var(name: str) -> EnvVar:
    var = _REGISTRY.get(name)
    if var is None:
        raise KeyError(
            f"env var {name!r} is not registered in client_tpu.config — "
            "add a register(...) entry (and regenerate docs/CONFIG.md)")
    return var


def env_text(name: str, environ=None) -> str:
    """Raw stripped value; the registered default when unset. The JSON-ish
    knobs (``@file`` indirection, ``1``/``on`` grammars) parse this
    themselves — the registry owns the *name and default*, not the
    grammar."""
    var = _var(name)
    environ = os.environ if environ is None else environ
    raw = environ.get(name)
    if raw is None:
        return var.default
    return raw.strip()


def env_str(name: str, environ=None) -> str:
    text = env_text(name, environ)
    return text if text else _var(name).default


def env_int(name: str, environ=None) -> int:
    text = env_text(name, environ)
    try:
        return int(text if text else _var(name).default)
    except ValueError:
        raise ValueError(
            f"{name} expects an integer, got {text!r}") from None


def env_float(name: str, environ=None) -> float:
    text = env_text(name, environ)
    try:
        return float(text if text else _var(name).default)
    except ValueError:
        raise ValueError(
            f"{name} expects a number, got {text!r}") from None


def env_flag(name: str, environ=None) -> bool:
    """Boolean knob: unset, ``""``, ``0``, ``false``, ``off`` → False;
    anything else → True."""
    return env_text(name, environ).lower() not in ("", "0", "false", "off")


# ---------------------------------------------------------------------------
# The registry. Grouped by subsystem; kept alphabetical within a group so
# the generated docs table is stable across regenerations.
# ---------------------------------------------------------------------------

# -- engine ------------------------------------------------------------------
register(
    "CLIENT_TPU_AUTOTUNE", "", "json",
    "Bucket-ladder autotuner: unset/`0`/`off` disables (no thread, no "
    "arena); `1`/`on` takes defaults; else inline JSON or `@/path.json`.",
    "engine")
register(
    "CLIENT_TPU_SELFDRIVE", "", "json",
    "Self-drive closed loops (dispatch retune, SLO-burn admission "
    "tightening, drift re-placement): unset/`0`/`off` disables; `1`/`on` "
    "takes defaults; else inline JSON or `@/path.json` (interval_s, "
    "fill_low, wait_high_s, burn_factor, rebalance_cooldown_s, "
    "max_moves_per_window, ... — see docs/SELFDRIVING.md).",
    "engine")
register(
    "CLIENT_TPU_GEN_CHUNK", "1", "int",
    "Decode chunk K: one device dispatch advances every stream K tokens "
    "(divides per-wave host overhead by K; adds ≤K−1 waves of TTFT).",
    "engine")
register(
    "CLIENT_TPU_SEQ_PIPELINE", "2", "int",
    "Sequence-batcher dispatch-ahead depth (waves in flight before the "
    "worker blocks on the oldest fetch).",
    "engine")
register(
    "CLIENT_TPU_TRACE_BUFFER", "512", "int",
    "Engine request-trace span-store capacity (GET /v2/trace/requests).",
    "engine")
register(
    "CLIENT_TPU_WARMUP", "", "flag",
    "Pre-compile every batch bucket at model load in the embedded engine "
    "so no XLA compile lands inside a measurement window.",
    "engine")

# -- server frontends --------------------------------------------------------
register(
    "CLIENT_TPU_STREAM_PENDING_LIMIT", "1024", "int",
    "Per-stream pending-response backlog (HTTP generate_stream / gRPC "
    "stream) before the slow-consumer shed cancels the request.",
    "server")
register(
    "CLIENT_TPU_STREAM_WRITER_DELAY_MS", "0", "float",
    "Test knob: per-message stream-writer delay (ms) that forces a "
    "writer backlog so coalescing/shed paths are deterministically "
    "exercisable.",
    "server")

# -- admission / SLO ---------------------------------------------------------
register(
    "CLIENT_TPU_ADMISSION", "", "json",
    "Admission-controller limits: inline JSON or `@/path.json`; unset "
    "admits everything (in-flight accounting only).",
    "admission")
register(
    "CLIENT_TPU_SLO", "", "json",
    "SLO objectives (availability/latency burn tracking): inline JSON or "
    "`@/path.json`; unset disables tracking entirely.",
    "admission")
register(
    "CLIENT_TPU_QOS", "", "json",
    "Tenant QoS classes (inline JSON or `@/path.json`): named classes "
    "with WFQ weights, token-bucket quotas, inflight/queue caps, "
    "class→priority mapping, preempt/protect flags, plus the "
    "tenant→class table; unset disables QoS entirely (priority-heap "
    "scheduling, shared admission gates only). See docs/QOS.md.",
    "admission")

# -- observability -----------------------------------------------------------
register(
    "CLIENT_TPU_COSTS", "", "json",
    "Per-tenant cost ledger (GET /v2/costs, tpu_cost_* metrics): `0`/"
    "`off` disables; unset/`1`/`on` defaults; else inline JSON or "
    "`@/path.json` (window_s, max_tenants, tenants, top_talker_*).",
    "observability")
register(
    "CLIENT_TPU_EVENT_BUFFER", "1024", "int",
    "Capacity of the operational event-journal ring (GET /v2/events).",
    "observability")
register(
    "CLIENT_TPU_LOG", "", "str",
    "`json` attaches a JSON-lines handler to the `client_tpu` logger and "
    "mirrors journal events to the same stream.",
    "observability")
register(
    "CLIENT_TPU_LOGLEVEL", "INFO", "str",
    "Level of the `client_tpu.engine` logger's default stderr handler, "
    "applied when `engine.backend_init` is first imported.",
    "observability")
register(
    "CLIENT_TPU_MEMORY", "", "json",
    "HBM census / memory-pressure events: `0`/`off` disables pressure "
    "events; unset/`1`/`on` defaults; else inline JSON or `@/path.json`.",
    "observability")
register(
    "CLIENT_TPU_BLACKBOX", "", "json",
    "Incident blackbox (journal-triggered postmortem bundles on disk, "
    "GET /v2/debug/bundles): `0`/`off` disables; unset/`1`/`on` defaults "
    "(all triggers, ~48 MiB bundle ring under $TMPDIR); else inline JSON "
    "or `@/path.json` with `dir`, `triggers`, `window_s`, `debounce_s`, "
    "`cooldown_s`, `max_bundles`, `max_bundle_bytes`, `max_total_bytes`.",
    "observability")
register(
    "CLIENT_TPU_PROFILE_WINDOW_S", "60", "float",
    "Efficiency-profiler sliding-window length in seconds.",
    "observability")
register(
    "CLIENT_TPU_ROOFLINE", "", "json",
    "Roofline attribution (XLA cost-model capture + MFU/MBU peaks): "
    "`0`/`off` disables capture; unset/`1`/`on` defaults (detected "
    "device-kind peaks); else inline JSON or `@/path.json` with "
    "`peak_flops`, `peak_bytes_per_s`, `device_kinds`, `capture`.",
    "observability")
register(
    "CLIENT_TPU_TIMESERIES", "", "json",
    "Flight recorder (1 Hz signal ring, GET /v2/timeseries): `0`/`off` "
    "disables; unset/`1`/`on` defaults; else inline JSON or `@/path.json`.",
    "observability")

# -- shm data planes ---------------------------------------------------------
register(
    "CLIENT_TPU_REPLAY_PRIORITY", "8", "int",
    "InferRequest priority tools/replay.py stamps on shadow traffic; at "
    "or above the admission `shadow_priority` threshold the request is "
    "classed shadow and sheds first.",
    "shm")
register(
    "CLIENT_TPU_REPLAY_TENANT", "shadow", "str",
    "Cost-ledger tenant tag tools/replay.py stamps on its shm traffic "
    "(`--tenant` overrides) so shadow device/HBM spend is attributable.",
    "shm")
register(
    "CLIENT_TPU_REPLAY_SHAPE", "steady", "str",
    "Default load shape for tools/replay.py `--rate` pacing: `steady`, "
    "`diurnal` (raised cosine to `--peak-rate`), or `flash_crowd` "
    "(rectangular peak burst each `--shape-period`).",
    "shm")
register(
    "CLIENT_TPU_SHM_REAPER_INTERVAL_MS", "1.0", "float",
    "Idle sleep (ms) of the engine-side multi-ring reaper thread between "
    "sweeps that admitted nothing.",
    "shm")
register(
    "CLIENT_TPU_SHM_REAPER_SPAN", "32", "int",
    "Per-ring slot cap per reaper sweep — the fairness quantum that "
    "keeps one hot producer from starving the other reaped rings.",
    "shm")
register(
    "CLIENT_TPU_STAGED_BUDGET", "0", "int",
    "Total payload bytes of staged datasets the engine will hold "
    "attached at once; `0` means unlimited.",
    "shm")
register(
    "CLIENT_TPU_STAGED_PATH", "", "str",
    "Default staged-dataset shm key for tools/replay.py (`--dataset-key` "
    "overrides).",
    "shm")

# -- router / fleet ----------------------------------------------------------
register(
    "CLIENT_TPU_FLEET_MONITOR", "", "json",
    "Fleet drift monitor: unset/`0`/`off` disables; `1`/`on` defaults; "
    "else inline JSON or `@/path.json` (interval_s, threshold, "
    "min_replicas, window_s).",
    "router")
register(
    "CLIENT_TPU_ROUTER_TRACE_BUFFER", "512", "int",
    "Router span-store capacity (stitched traces on /v2/trace/requests).",
    "router")

# -- diagnostics -------------------------------------------------------------
register(
    "CLIENT_TPU_FAULTS", "", "json",
    "Deterministic fault-injection plan (inline JSON or `@/path.json`); "
    "unset injects nothing.",
    "diagnostics")
register(
    "CLIENT_TPU_LOCKDEP", "", "flag",
    "Enable runtime lock-order and blocking-under-lock checking "
    "(client_tpu.utils.lockdep). Test/CI harnesses only — named locks "
    "created while enabled record acquisition chains and raise on "
    "ordering cycles; zero-overhead plain threading primitives otherwise.",
    "diagnostics")


# ---------------------------------------------------------------------------
# Docs generation
# ---------------------------------------------------------------------------

def render_markdown_table() -> str:
    """The generated env-var table embedded in docs/CONFIG.md between the
    ``<!-- env-table:begin -->`` / ``<!-- env-table:end -->`` markers
    (tpulint's env-registry check verifies every registered name appears
    there)."""
    lines = [
        "| Variable | Subsystem | Kind | Default | Description |",
        "|---|---|---|---|---|",
    ]
    for name in sorted(_REGISTRY):
        v = _REGISTRY[name]
        default = f"`{v.default}`" if v.default else "*(unset)*"
        lines.append(
            f"| `{v.name}` | {v.subsystem} | {v.kind} | {default} "
            f"| {v.doc} |")
    return "\n".join(lines)


if __name__ == "__main__":
    print(render_markdown_table())
