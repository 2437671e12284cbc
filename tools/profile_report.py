#!/usr/bin/env python
"""Render a ``/v2/profile`` snapshot as a per-bucket cost table.

Input is either a live server base URL (``http://host:port``) or a path to
a saved JSON snapshot (e.g. ``curl $base/v2/profile > prof.json``). For
each model the report shows, per bucket: execution and row counts, fill
ratio, cumulative and per-call-EWMA device time, the padding-waste
device-seconds estimate, and compile cost — followed by the profiler's
bucket-ladder suggestion when one fires.

    python tools/profile_report.py http://127.0.0.1:8000
    python tools/profile_report.py http://127.0.0.1:8000 --model simple
    python tools/profile_report.py prof.json

``--fleet`` points the same tool at a *router* and renders the
federated ``/v2/fleet/profile``: a fleet summary table (one row per
replica with its drift scores) followed by each replica's per-bucket
cost table.

    python tools/profile_report.py http://127.0.0.1:8080 --fleet

``--timeseries`` renders the flight recorder (``/v2/timeseries`` or a
saved export) as one unicode sparkline per signal (per-model signals
get one line per model); ``--memory`` renders the HBM census
(``/v2/memory``) as an owner table with plan-vs-actual drift.

    python tools/profile_report.py http://127.0.0.1:8000 --timeseries
    python tools/profile_report.py http://127.0.0.1:8000 --memory

``--roofline`` renders the roofline attribution: device kind and peak
specs, then per model/bucket the static FLOPs per call, arithmetic
intensity, achieved FLOP/s and bytes/s, MFU/MBU, padding-wasted FLOPs,
and the compute/bandwidth bound classification.

    python tools/profile_report.py http://127.0.0.1:8000 --roofline

``--setup`` renders the set-up timeline ("why was this launch slow",
docs/OBSERVABILITY.md): the launcher's phases from the operating system's
start of the process to "serving", every compilation's trace / lower /
backend spans under the phase that caused them, and ``compiles.by_scope``
sorted by seconds with the persistent cache's hits and misses.

    python tools/profile_report.py http://127.0.0.1:8000 --setup

``--loops`` renders the self-drive closed-loop state (docs/SELFDRIVING.md):
the dispatch tuner's per-model phase and recent decisions, the admission
loop's tightened rate ratios, or — against a router status body — the
fleet rebalancer's damping state.

    python tools/profile_report.py http://127.0.0.1:8000 --loops
"""

from __future__ import annotations

import argparse
import json
import sys
from urllib.parse import quote, urlparse
from urllib.request import urlopen

_COLS = ("bucket", "axis", "execs", "cold", "rows", "padded", "fill",
         "device_s", "ewma_ms", "waste_s", "compiles", "compile_s")


def load_snapshot(source: str, model: str = "", fleet: bool = False,
                  endpoint: str = "", timeout_s: float = 10.0) -> dict:
    """Fetch from a server base URL or read a saved JSON file.
    ``endpoint`` overrides the path (``/v2/timeseries``, ``/v2/memory``);
    the default is the profile surface (fleet-aware)."""
    if urlparse(source).scheme in ("http", "https"):
        url = source.rstrip("/") + (
            endpoint or ("/v2/fleet/profile" if fleet else "/v2/profile"))
        if model and endpoint == "/v2/timeseries":
            url += f"?model={quote(model)}"
        elif model and not fleet and not endpoint:
            url += f"?model={quote(model)}"
        with urlopen(url, timeout=timeout_s) as resp:
            return json.load(resp)
    with open(source) as f:
        snap = json.load(f)
    if model and not fleet and not endpoint:
        snap = dict(snap, models={k: v for k, v in snap["models"].items()
                                  if v.get("model") == model})
    return snap


def _bucket_row(b: dict) -> tuple:
    # "rows" vs "lookups": a 512-lookup ragged bucket is not a 512-row
    # batch — the axis column keeps the two ladders readable side by side.
    return (b["bucket"], b.get("axis", "rows"),
            b["executions"], b["cold_executions"], b["rows"],
            b["padded_rows"], f"{b['fill_ratio']:.3f}",
            f"{b['device_s']:.4f}",
            f"{b['device_s_per_call_ewma'] * 1e3:.3f}",
            f"{b['padding_waste_device_s']:.4f}",
            b["compilations"], f"{b['compile_s']:.3f}")


def render(snap: dict, out=None) -> None:
    w = (out or sys.stdout).write
    w(f"window_s={snap.get('window_s')} "
      f"duty_cycle={snap.get('duty_cycle')}\n")
    models = snap.get("models", {})
    if not models:
        w("no recorded executions yet\n")
        return
    for mkey in sorted(models):
        m = models[mkey]
        w(f"\nmodel {m['model']} (version {m['version']}): "
          f"device {m['device_s']:.4f}s, host {m['host_s']:.4f}s, "
          f"padding waste {m['padding_waste_device_s']:.4f}s, "
          f"{m['compilations']} compile(s) totalling "
          f"{m['compile_s']:.3f}s\n")
        rows = [_COLS] + [_bucket_row(b) for b in m["buckets"]]
        widths = [max(len(str(r[i])) for r in rows)
                  for i in range(len(_COLS))]
        for r in rows:
            w("  " + "  ".join(str(v).rjust(widths[i])
                               for i, v in enumerate(r)) + "\n")
        sug = m.get("suggestion")
        if sug:
            w(f"  suggestion: add bucket {sug['bucket']} below "
              f"{sug['below']} (fill {sug['fill_ratio']:.3f}, est. saving "
              f"{sug['est_saving_device_s']:.4f} device-s) — "
              f"{sug['reason']}\n")


def _fmt_rate(v, scale: float = 1e9, suffix: str = "G") -> str:
    if v is None:
        return "-"
    return f"{v / scale:.2f}{suffix}"


def _roofline_row(kind: str, bucket, rl: dict, execs, device_s) -> tuple:
    if rl.get("cost_model") != "xla":
        return (kind, bucket, execs, f"{device_s:.4f}", "-", "-", "-",
                "-", "-", "-", "-",
                f"unavailable: {rl.get('reason', '?')}")
    mfu = rl.get("mfu")
    mbu = rl.get("mbu")
    return (kind, bucket, execs, f"{device_s:.4f}",
            _fmt_rate(rl.get("flops_per_call"), 1e9, "GF"),
            f"{rl['arithmetic_intensity']:.2f}"
            if rl.get("arithmetic_intensity") is not None else "-",
            _fmt_rate(rl.get("achieved_flops_per_s"), 1e9, "GF/s"),
            _fmt_rate(rl.get("achieved_bytes_per_s"), 1e9, "GB/s"),
            f"{mfu * 100:.2f}%" if mfu is not None else "-",
            f"{mbu * 100:.2f}%" if mbu is not None else "-",
            rl.get("bound", "unknown"),
            _fmt_rate(rl.get("padding_wasted_flops"), 1e9, "GF"))


_ROOF_COLS = ("kind", "bucket", "execs", "device_s", "flops/call", "AI",
              "achieved", "bytes/s", "mfu", "mbu", "bound", "pad_waste")


def render_roofline(snap: dict, out=None) -> None:
    """The achieved-vs-peak view: device kind and resolved peaks, then
    per model one row per bucket (and per decode-wave shape) with the
    static cost, achieved rates, MFU/MBU, and the bound classification.
    Cost-model-less buckets render their annotated absence, not zeros."""
    w = (out or sys.stdout).write
    ctx = snap.get("roofline", {})
    peaks = ctx.get("peaks")
    if isinstance(peaks, dict):
        peaks_s = (f"peak {_fmt_rate(peaks.get('flops_per_s'), 1e12, 'TF/s')}"
                   f" / {_fmt_rate(peaks.get('bytes_per_s'), 1e9, 'GB/s')}"
                   f" ({peaks.get('source')})")
    else:
        peaks_s = "peaks unknown (measured-only; set CLIENT_TPU_ROOFLINE)"
    w(f"device_kind={ctx.get('device_kind', 'unknown')}  {peaks_s}\n")
    if ctx.get("config_error"):
        w(f"  CONFIG ERROR: {ctx['config_error']}\n")
    models = snap.get("models", {})
    if not models:
        w("no recorded executions yet\n")
        return
    for mkey in sorted(models):
        m = models[mkey]
        mr = m.get("roofline", {})
        mfu = mr.get("mfu")
        mbu = mr.get("mbu")
        w(f"\nmodel {m['model']} (version {m['version']}): "
          f"{_fmt_rate(mr.get('total_flops'), 1e9, 'GF')} over "
          f"{m['device_s']:.4f}s covered "
          f"{mr.get('cost_model_coverage', 0) * 100:.0f}%"
          + (f", mfu {mfu * 100:.2f}%" if mfu is not None else "")
          + (f", mbu {mbu * 100:.2f}%" if mbu is not None else "")
          + f", bound {mr.get('bound', 'unknown')}\n")
        rows = [_ROOF_COLS]
        for b in m.get("buckets", ()):
            rows.append(_roofline_row(
                b.get("axis", "rows"), b["bucket"],
                b.get("roofline", {}),
                b["executions"] - b["cold_executions"], b["device_s"]))
        for wv in m.get("decode_waves", ()):
            rows.append(_roofline_row(
                f"wave*{wv['chunk']}", wv["bucket"], wv.get("roofline", {}),
                wv.get("dispatches", 0), wv["device_s"]))
        widths = [max(len(str(r[i])) for r in rows)
                  for i in range(len(_ROOF_COLS))]
        for r in rows:
            w("  " + "  ".join(str(v).rjust(widths[i])
                               for i, v in enumerate(r)).rstrip() + "\n")


def render_fleet(fleet_snap: dict, out=None) -> None:
    """The federated view: replica summary rows (with drift scores from
    the fleet section, flagged ``!`` above the monitor threshold when a
    drift report is present) followed by per-replica bucket tables."""
    w = (out or sys.stdout).write
    fleet = fleet_snap.get("fleet", {})
    replicas = fleet_snap.get("replicas", {})
    signals = fleet.get("signals", {})
    scores = fleet.get("drift_scores", {})
    drift = fleet_snap.get("drift") or {}
    threshold = drift.get("threshold")
    flagged = drift.get("flagged", {})
    names = sorted({s for per in signals.values() for s in per})
    w(f"fleet: {fleet.get('replica_count', len(replicas))} replica(s), "
      f"medians {fleet.get('medians', {})}"
      + (f", drift threshold {threshold}" if threshold is not None else "")
      + "\n")
    header = ("replica", "duty") + tuple(
        f"drift:{s}" for s in names) + ("flagged",)
    rows = [header]
    for rid in sorted(replicas):
        duty = replicas[rid].get("duty_cycle")
        row = [rid, f"{duty:.3f}" if duty is not None else "-"]
        for s in names:
            score = scores.get(rid, {}).get(s)
            mark = "!" if rid in flagged and s in flagged[rid] else ""
            row.append(f"{score:.3f}{mark}" if score is not None else "-")
        row.append(",".join(sorted(flagged.get(rid, {}))) or "-")
        rows.append(tuple(row))
    widths = [max(len(str(r[i])) for r in rows) for i in range(len(header))]
    for r in rows:
        w("  " + "  ".join(str(v).ljust(widths[i])
                           for i, v in enumerate(r)).rstrip() + "\n")
    for rid, err in sorted(fleet_snap.get("errors", {}).items()):
        w(f"  replica {rid}: FETCH FAILED ({err})\n")
    for rid in sorted(replicas):
        w(f"\n=== replica {rid} ===\n")
        render(replicas[rid], out=out)


# -- flight recorder sparklines ------------------------------------------------

_SPARKS = "▁▂▃▄▅▆▇█"


def sparkline(values: list[float], width: int = 60) -> str:
    """Map a series onto ▁..█ glyphs, newest-right, downsampled to
    ``width`` by bucket-mean. A flat series renders as all-▁."""
    vals = [float(v) for v in values]
    if not vals:
        return ""
    if len(vals) > width:
        # bucket-mean downsample: len(vals)/width samples per glyph
        step = len(vals) / width
        vals = [sum(vals[int(i * step):max(int(i * step) + 1,
                                           int((i + 1) * step))])
                / max(1, int((i + 1) * step) - int(i * step))
                for i in range(width)]
    lo, hi = min(vals), max(vals)
    span = hi - lo
    if span <= 0:
        return _SPARKS[0] * len(vals)
    return "".join(_SPARKS[min(len(_SPARKS) - 1,
                               int((v - lo) / span * len(_SPARKS)))]
                   for v in vals)


def render_timeseries(export: dict, out=None, width: int = 60) -> None:
    """One sparkline per signal; per-model signals one line per model.
    Each line carries the min/last/max so the glyph scale is readable."""
    w = (out or sys.stdout).write
    samples = export.get("samples", [])
    w(f"flight recorder: {len(samples)} sample(s), "
      f"interval {export.get('interval_s')}s, capacity "
      f"{export.get('capacity')}, dropped {export.get('dropped', 0)}, "
      f"next_seq {export.get('next_seq')}\n")
    if not samples:
        w("no samples recorded yet\n")
        return
    series: dict[str, list[float]] = {}
    for s in samples:
        for name, value in (s.get("signals") or {}).items():
            if isinstance(value, dict):
                for mname, v in value.items():
                    series.setdefault(f"{name}[{mname}]", []).append(
                        float(v))
            else:
                series.setdefault(name, []).append(float(value))
    if not series:
        w("no signals in the window\n")
        return
    label_w = max(len(k) for k in series)
    for name in sorted(series):
        vals = series[name]
        w(f"  {name.ljust(label_w)}  {sparkline(vals, width)}  "
          f"min={min(vals):.4g} last={vals[-1]:.4g} "
          f"max={max(vals):.4g}\n")


def _fmt_bytes(n) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return (f"{n:.0f}{unit}" if unit == "B"
                    else f"{n:.2f}{unit}")
        n /= 1024
    return f"{n:.2f}GiB"


def render_memory(report: dict, out=None) -> None:
    """The HBM census owner table: live bytes and buffer counts per
    (model, component), plan bytes and drift where the planner holds a
    reservation, then the unattributed remainder and totals."""
    w = (out or sys.stdout).write
    totals = report.get("totals", {})
    w(f"hbm census: committed {_fmt_bytes(totals.get('committed_bytes', 0))} "
      f"({totals.get('live_arrays', 0)} live arrays), "
      f"attributed {report.get('attributed_fraction', 0) * 100:.1f}%, "
      f"watermark {_fmt_bytes(report.get('watermark_bytes', 0))}\n")
    header = ("model", "component", "bytes", "buffers", "plan", "drift")
    rows = [header]
    for o in report.get("owners", []):
        rows.append((o["model"], o["component"], _fmt_bytes(o["bytes"]),
                     str(o["buffers"]),
                     _fmt_bytes(o["plan_bytes"])
                     if "plan_bytes" in o else "-",
                     f"{o['drift_bytes']:+d}"
                     if "drift_bytes" in o else "-"))
    unattr = report.get("unattributed_bytes", 0)
    rows.append(("", "unattributed", _fmt_bytes(unattr), "-", "-", "-"))
    widths = [max(len(str(r[i])) for r in rows) for i in range(len(header))]
    for r in rows:
        w("  " + "  ".join(str(v).ljust(widths[i])
                           for i, v in enumerate(r)).rstrip() + "\n")
    pressure = report.get("pressure")
    if pressure:
        mark = " OVER THRESHOLD" if pressure.get("over") else ""
        w(f"  pressure: {pressure['fraction'] * 100:.1f}% of limit "
          f"(threshold {pressure['threshold'] * 100:.0f}%){mark}\n")


def render_setup(snap: dict, out=None) -> None:
    """The set-up section: the ``startup`` timeline in the order things
    started (a compile span indented under the phase that caused it, with
    the program's name, its scope and whether the persistent cache held it),
    then every scope's compile cost, dearest first."""
    w = (out or sys.stdout).write
    spans = snap.get("startup") or []
    clock = snap.get("startup_clock") or {}
    w(f"set-up timeline: {len(spans)} span(s) relative to the launcher's "
      f"entry (monotonic {clock.get('entry_monotonic_s')}), "
      f"{clock.get('dropped', 0)} dropped\n")
    phases = [s for s in spans if "scope" not in s]
    for s in sorted(spans, key=lambda s: (s["start_s"], -s["end_s"])):
        compile_span = "scope" in s
        inside = any(p is not s and p["start_s"] <= s["start_s"]
                     and s["end_s"] <= p["end_s"] for p in phases)
        line = (f"{s['start_s']:10.3f} {s['end_s']:10.3f} "
                f"{s['end_s'] - s['start_s']:9.3f}  "
                f"{'  ' if inside else ''}{s['name']}")
        if compile_span:
            line += f" {s['fun_name']} [{s['scope'] or '-'}]"
            if "cache" in s:
                line += f" cache {s['cache']}"
            if "retrieval_s" in s:
                line += f" (read in {s['retrieval_s']:.3f}s)"
            if not s.get("cause"):
                line += " (outside any phase)"
        w(line + "\n")
    c = snap.get("compiles") or {}
    w(f"\ncompilations: {c.get('count', 0)} in {c.get('seconds', 0.0):.3f}s "
      f"of backend ({c.get('cache_hits', 0)} persistent-cache hit(s), "
      f"{c.get('cache_misses', 0)} miss(es) written), tracing "
      f"{c.get('trace_seconds', 0.0):.3f}s, lowering "
      f"{c.get('lower_seconds', 0.0):.3f}s\n")
    cols = ("scope", "count", "hits", "trace_s", "lower_s", "backend_s",
            "total_s")
    rows = [cols]
    by_scope = c.get("by_scope", {})
    for key in sorted(by_scope, key=lambda k: -(
            by_scope[k]["seconds"] + by_scope[k].get("trace_s", 0.0)
            + by_scope[k].get("lower_s", 0.0))):
        r = by_scope[key]
        trace_s, lower_s = r.get("trace_s", 0.0), r.get("lower_s", 0.0)
        rows.append((key or "(outside any scope)", r["count"],
                     r.get("hits", 0), f"{trace_s:.3f}", f"{lower_s:.3f}",
                     f"{r['seconds']:.3f}",
                     f"{trace_s + lower_s + r['seconds']:.3f}"))
    widths = [max(len(str(r[i])) for r in rows) for i in range(len(cols))]
    for r in rows:
        w("  " + "  ".join(
            str(v).ljust(widths[i]) if i == 0 else str(v).rjust(widths[i])
            for i, v in enumerate(r)) + "\n")


def render_loops(snap: dict, out=None) -> None:
    """The self-drive loop view: which closed loops are actuated right
    now and what they decided recently. Accepts an engine ``/v2/profile``
    snapshot (``selfdrive`` section: dispatch tuner + admission loop) or
    a router ``/v2/router/status`` body (``selfdrive`` section: the
    rebalancer's damping state)."""
    w = (out or sys.stdout).write
    sd = snap.get("selfdrive")
    if not sd:
        w("self-drive disabled (no 'selfdrive' section — set "
          "CLIENT_TPU_SELFDRIVE)\n")
        return
    if "rebalances" in sd:  # router status shape
        w(f"fleet rebalancer: {sd['rebalances']} rebalance(s), window "
          f"moves {sd['window_moves']}/{sd['window_budget']}, cooldown "
          f"remaining {sd['cooldown_remaining_s']}s\n")
        last = sd.get("last") or {}
        if last:
            w(f"  last: outcome={last.get('outcome')} "
              f"moves={last.get('moves')} flagged={last.get('flagged')} "
              f"truncated={last.get('truncated')} "
              f"rejected={last.get('rejected')}\n")
        return
    cfg = sd.get("config", {})
    w(f"self-drive: interval {cfg.get('interval_s')}s\n")
    dispatch = sd.get("dispatch", {})
    models = dispatch.get("models", {})
    w(f"dispatch loop: {dispatch.get('action_count', 0)} actuation(s)\n")
    for mkey in sorted(models):
        st = models[mkey]
        phase = ("tight" if st.get("tight") else "") or ""
        phase += ("+nudged" if st.get("nudged") else "")
        w(f"  {mkey}: {phase.lstrip('+') or 'idle'}\n")
    for d in dispatch.get("decisions", [])[-10:]:
        detail = {k: v for k, v in d.items()
                  if k not in ("action", "model", "version")}
        w(f"  recent: {d.get('action')} {d.get('model')}"
          f":{d.get('version')} {detail}\n")
    adm = sd.get("admission", {})
    tightened = adm.get("tightened", {})
    w(f"admission loop: {adm.get('action_count', 0)} actuation(s), "
      f"tightened {len(tightened)} model(s)\n")
    for m in sorted(tightened):
        w(f"  {m}: rate ratio {tightened[m]}\n")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("source", help="server base URL or saved snapshot path")
    p.add_argument("--model", default="", help="restrict to one model")
    p.add_argument("--fleet", action="store_true",
                   help="source is a router: render the federated "
                        "/v2/fleet/profile with per-replica drift")
    p.add_argument("--json", action="store_true",
                   help="dump the (filtered) snapshot as JSON instead")
    p.add_argument("--timeseries", action="store_true",
                   help="render the flight recorder (/v2/timeseries) "
                        "as per-signal sparklines")
    p.add_argument("--memory", action="store_true",
                   help="render the HBM census (/v2/memory) as an "
                        "owner/drift table")
    p.add_argument("--roofline", action="store_true",
                   help="render the roofline attribution of /v2/profile: "
                        "achieved vs peak FLOP/s and bytes/s per bucket "
                        "with the compute/bandwidth bound classification")
    p.add_argument("--setup", action="store_true",
                   help="render the set-up timeline of /v2/profile: the "
                        "launcher's phases, every compilation's trace / "
                        "lower / backend spans, and the compile cost by "
                        "scope with persistent-cache hits")
    p.add_argument("--loops", action="store_true",
                   help="render the self-drive closed-loop state "
                        "(the 'selfdrive' section of /v2/profile, or "
                        "of /v2/router/status for the rebalancer)")
    args = p.parse_args(argv)
    endpoint = ""
    if args.timeseries:
        endpoint = "/v2/timeseries"
    elif args.memory:
        endpoint = "/v2/memory"
    try:
        snap = load_snapshot(args.source, model=args.model,
                             fleet=args.fleet, endpoint=endpoint)
    except Exception as exc:  # noqa: BLE001 — CLI surface
        print(f"profile_report: cannot load {args.source}: {exc}",
              file=sys.stderr)
        return 1
    if args.json:
        json.dump(snap, sys.stdout, indent=2)
        sys.stdout.write("\n")
    elif args.setup:
        render_setup(snap)
    elif args.loops:
        render_loops(snap)
    elif args.timeseries:
        render_timeseries(snap)
    elif args.memory:
        render_memory(snap)
    elif args.fleet:
        render_fleet(snap)
    elif args.roofline:
        render_roofline(snap)
    else:
        render(snap)
    return 0


if __name__ == "__main__":
    sys.exit(main())
