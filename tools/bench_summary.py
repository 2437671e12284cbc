#!/usr/bin/env python3
"""Summarize BENCH_HISTORY.json: per-run probe records, newest run last.

The round-5 history schema appends one record per PROBE as it completes
(plus a run-status record), grouped by ``run_ts`` — this prints each run's
probes on one screen so reconciling them with the prose is mechanical.

``--check`` turns the tool into a regression gate: the newest run's
per-probe p99 latency is compared against the median of the prior runs
(same probe), and the process exits 1 when any probe regressed by more
than ``--threshold`` (default 25%). Fewer than two runs of a probe is a
pass — there is nothing to compare against.

Usage: python tools/bench_summary.py [path] [--runs N]
       python tools/bench_summary.py --check [path] [--threshold 0.25]
"""

import json
import os
import sys
import time


def _probe_runs(hist: list) -> dict:
    """{run_ts: {probe: record}} for probe records (run-status excluded).

    Records with ``status: "unavailable"`` (the pre-r06 placeholder for
    backend-init outages) or
    ``status: "backend_init_error"`` (the r06+ fail-fast diagnostic) are
    dropped: an outage run carries no performance signal, and letting
    its zeros into the p99/ips medians would mask real regressions."""
    runs: dict = {}
    for rec in hist:
        if not isinstance(rec, dict) or rec.get("run_ts") is None:
            continue
        if rec.get("probe") in (None, "run-status"):
            continue
        if rec.get("status") in ("unavailable", "backend_init_error"):
            continue
        runs.setdefault(rec["run_ts"], {})[rec["probe"]] = rec
    return runs


def _median(values: list) -> float:
    ordered = sorted(values)
    mid = len(ordered) // 2
    if len(ordered) % 2:
        return ordered[mid]
    return (ordered[mid - 1] + ordered[mid]) / 2


def check(hist: list, threshold: float = 0.25) -> int:
    """Gate the newest run against the median of prior runs per probe.
    Returns the exit status (1 on any >threshold p99 regression)."""
    runs = _probe_runs(hist)
    if not runs:
        print("bench-check: 0 run(s) with probe records — "
              "nothing to compare, pass")
        return 0
    latest_ts = max(runs)
    failures = 0
    if len(runs) < 2:
        print(f"bench-check: {len(runs)} run(s) with probe records — "
              "no prior runs to compare p99 against")
    else:
        for probe, rec in sorted(runs[latest_ts].items()):
            p99 = rec.get("p99_us")
            prior = [runs[ts][probe].get("p99_us")
                     for ts in runs
                     if ts != latest_ts and probe in runs[ts]]
            prior = [v for v in prior if v is not None]
            if p99 is None or not prior:
                print(f"bench-check: {probe}: no prior p99 to compare, "
                      "skip")
                continue
            base = _median(prior)
            ratio = (p99 / base - 1.0) if base > 0 else 0.0
            verdict = "FAIL" if ratio > threshold else "ok"
            print(f"bench-check: {probe}: p99 {p99:.1f}us vs median "
                  f"{base:.1f}us over {len(prior)} prior run(s) "
                  f"({ratio:+.1%}) {verdict}")
            if ratio > threshold:
                failures += 1
    # Interference gate: when the fan-in probe carries the cost ledger's
    # attribution, it must explain most of the measured p99 inflation —
    # an unexplained slowdown means the ledger lost track of who paid.
    # Records predating the ledger skip silently.
    fanin = runs[latest_ts].get("shm_fanin")
    if fanin is not None:
        r = fanin.get("shm_fanin") or fanin
        inter = r.get("interference") or {}
        if inter:
            explained = float(inter.get("explained_fraction") or 0.0)
            verdict = "FAIL" if explained < 0.8 else "ok"
            print(f"bench-check: shm_fanin: interference attribution "
                  f"explains {explained:.0%} of the p99 inflation "
                  f"(floor 80%) {verdict}")
            if explained < 0.8:
                failures += 1
        # QoS isolation gate: with the interactive class protected and
        # shadow demoted to the lowest WFQ lane, a full-rate shadow
        # replay may inflate live p99 by at most 10% (the pre-QoS bar
        # was 1.25x). Records predating QoS carry no ratio and skip.
        ratio = r.get("shadow_p99_ratio")
        if ratio is not None and r.get("qos") is not None:
            verdict = "FAIL" if ratio > 1.10 else "ok"
            print(f"bench-check: shm_fanin: live p99 under shadow "
                  f"replay {ratio}x (ceiling 1.10x with QoS) {verdict}")
            if ratio > 1.10:
                failures += 1
    # Gauntlet gate: the scenario record must carry the journal
    # evidence, not just healthy ratios — per-class SLOs held
    # (slo_pass), the governor throttled the drowning class during the
    # flash crowd (throttle_fired), and restored it once recovery
    # traffic diluted the burn (throttle_cleared).
    gauntlet = runs[latest_ts].get("gauntlet")
    if gauntlet is not None:
        bits = (("slo_pass", bool(gauntlet.get("slo_pass"))),
                ("throttle_fired", bool(gauntlet.get("throttle_fired"))),
                ("throttle_cleared",
                 bool(gauntlet.get("throttle_cleared"))))
        bad = [name for name, ok in bits if not ok]
        verdict = f"FAIL ({', '.join(bad)} unmet)" if bad else "ok"
        print("bench-check: gauntlet: "
              + " ".join(f"{name}={ok}" for name, ok in bits)
              + f" {verdict}")
        if bad:
            failures += 1
    # Self-driving gate: the chaos probe's journal-cursor evidence —
    # all three control loops fired AND cleared (loops_closed), the
    # dispatch retune actually recovered batch fill above its floor
    # (fill_recovered), and no loop flapped (bounded actuation).
    selfdriving = runs[latest_ts].get("selfdriving")
    if selfdriving is not None:
        bits = (("loops_closed", bool(selfdriving.get("loops_closed"))),
                ("fill_recovered",
                 bool(selfdriving.get("fill_recovered"))),
                ("bounded", bool(selfdriving.get("bounded"))),
                # Incident blackbox: the induced incidents must have
                # produced bundles (zero means the trigger path broke;
                # the probe itself fails on more-than-one-per-incident).
                ("blackbox_captured",
                 bool(selfdriving.get("blackbox_bundles"))))
        bad = [name for name, ok in bits if not ok]
        verdict = f"FAIL ({', '.join(bad)} unmet)" if bad else "ok"
        print("bench-check: selfdriving: "
              + " ".join(f"{name}={ok}" for name, ok in bits)
              + f" {verdict}")
        if bad:
            failures += 1
    if failures:
        print(f"bench-check: {failures} probe(s) regressed more than "
              f"{threshold:.0%} on p99", file=sys.stderr)
        return 1
    return 0


def main() -> int:
    argv = sys.argv[1:]
    args = [a for i, a in enumerate(argv) if not a.startswith("--")
            and (i == 0 or argv[i - 1] not in ("--runs", "--threshold"))]
    path = args[0] if args else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_HISTORY.json")
    n_runs = 3
    if "--runs" in sys.argv:
        n_runs = int(sys.argv[sys.argv.index("--runs") + 1])
    threshold = 0.25
    if "--threshold" in sys.argv:
        threshold = float(sys.argv[sys.argv.index("--threshold") + 1])
    with open(path) as f:
        hist = json.load(f)

    if "--check" in sys.argv:
        return check(hist, threshold)

    runs: dict = {}
    legacy = []
    for rec in hist:
        if not isinstance(rec, dict):
            continue
        ts = rec.get("run_ts")
        if ts is None:
            legacy.append(rec)  # pre-r5 end-of-run aggregate
        else:
            runs.setdefault(ts, []).append(rec)

    if legacy:
        print(f"{len(legacy)} legacy run aggregate(s) (pre-r5 schema); "
              f"latest:")
        last = legacy[-1]
        print(f"  ts={time.strftime('%F %T', time.localtime(last.get('ts', 0)))}"
              f" platform={last.get('platform')} config={last.get('config')}"
              f" ips={last.get('value')}")

    for ts in sorted(runs)[-n_runs:]:
        recs = runs[ts]
        first = recs[0]
        print(f"\n== run {time.strftime('%F %T', time.localtime(ts))} "
              f"platform={first.get('platform')} "
              f"config={first.get('config')} ({len(recs)} records)")
        for rec in recs:
            probe = rec.get("probe", "?")
            if rec.get("status") in ("unavailable", "backend_init_error"):
                print(f"  {probe}: {rec['status'].upper()} "
                      f"({rec.get('reason', 'no reason recorded')}) "
                      "— excluded from medians")
                continue
            eff_keys = ("fill_ratio", "duty_cycle", "xla_compiles",
                        "pad_waste_device_s", "wave_step_ms_p50",
                        "cache_hit_rate", "timeseries_samples",
                        "census_attr_fraction", "mfu", "mbu")
            view = {k: v for k, v in rec.items()
                    if k not in ("probe", "ts", "run_ts", "platform",
                                 "config", "windows") + eff_keys}
            print(f"  {probe}: {json.dumps(view, default=str)[:300]}")
            eff = {k: rec[k] for k in eff_keys if k in rec}
            if eff:
                print(f"    efficiency: {json.dumps(eff)}")
            if probe == "autotune":
                _print_autotune_delta(rec)
            if probe == "router":
                _print_router_delta(rec)
            if probe == "dlrm":
                _print_dlrm_delta(rec)
            if probe == "shm_ring":
                _print_shm_ring_delta(rec)
            if probe == "shm_fanin":
                _print_shm_fanin_delta(rec)
            if probe == "gauntlet":
                _print_gauntlet_delta(rec)
            if probe == "selfdriving":
                _print_selfdriving_delta(rec)
    return 0


def _print_autotune_delta(rec: dict) -> None:
    """The tuner-off vs tuner-on delta of the bench autotune probe: the
    before/after that proves (or disproves) the promotion paid off."""
    off, on = rec.get("off") or {}, rec.get("on") or {}
    if not off or not on:
        return
    def fmt(key, scale=1.0, unit=""):
        a, b = off.get(key), on.get(key)
        if a is None or b is None:
            return f"{key}: n/a"
        return (f"{key}: {a * scale:.4g}{unit} -> {b * scale:.4g}{unit} "
                f"({(b - a) * scale:+.4g}{unit})")
    print("    autotune delta (off -> on): "
          + "; ".join((fmt("fill_ratio"),
                       fmt("pad_waste_device_s", unit="s"),
                       fmt("ips"))))
    if rec.get("promotions") is not None:
        print(f"    promotions applied: {rec['promotions']} "
              f"(ladder {off.get('ladder')} -> {on.get('ladder')})")


def _print_dlrm_delta(rec: dict) -> None:
    """The DLRM probe's cached-vs-uncached story plus the sharded-parity
    bit: hot-row cache hit rate under Zipf traffic next to both phases'
    ips/p99, and whether 4-way sharded tables matched the oracle."""
    d = rec.get("dlrm") or rec
    device, cached = d.get("device") or {}, d.get("cached") or {}
    if not device or not cached:
        return
    print(f"    dlrm device -> cached: {device.get('ips')} ips / "
          f"p99 {device.get('p99_us')}us -> {cached.get('ips')} ips / "
          f"p99 {cached.get('p99_us')}us "
          f"(hit rate {cached.get('cache_hit_rate')})")
    if d.get("sharded_parity") is not None:
        print(f"    sharded-vs-oracle bit-identical: "
              f"{d['sharded_parity']}")


def _print_shm_ring_delta(rec: dict) -> None:
    """The shm-ring probe's data-plane story: batched-doorbell ring vs
    binary HTTP on the same model/payload, plus mean ring occupancy — the
    acceptance bar (ring strictly higher ips) reads off the ratio."""
    r = rec.get("shm_ring") or rec
    http, ring = r.get("http") or {}, r.get("ring") or {}
    if not http or not ring:
        return
    ratio = r.get("ring_vs_http_ips")
    print(f"    shm_ring http -> ring: {http.get('ips')} ips / "
          f"p99 {http.get('p99_us')}us -> {ring.get('ips')} ips / "
          f"p99 {ring.get('p99_us')}us"
          + (f" = {ratio}x" if ratio is not None else "")
          + (f" (occupancy {ring.get('occupancy_mean')}, "
             f"{r.get('lanes')} lanes x span {r.get('span')})"
             if ring.get("occupancy_mean") is not None else ""))


def _print_shm_fanin_delta(rec: dict) -> None:
    """The fan-in probe's two acceptance bars on one line each: N
    producer processes vs one on the reaper plane (>= 3x aggregate ips),
    and the live plane's p99 with shadow replay on vs off (<= 1.10x now
    that the shadow class rides the lowest-weight QoS lane)."""
    r = rec.get("shm_fanin") or rec
    single, fanin = r.get("single") or {}, r.get("fanin") or {}
    if single and fanin:
        ratio = r.get("fanin_vs_single_ips")
        print(f"    shm_fanin scaling: {single.get('ips')} ips (1 producer)"
              f" -> {fanin.get('ips')} ips "
              f"({fanin.get('producers')} producers)"
              + (f" = {ratio}x" if ratio is not None else ""))
    off, on = r.get("live_off") or {}, r.get("live_shadow") or {}
    if off and on:
        shed = r.get("shadow") or {}
        print(f"    live p99 under shadow replay: {off.get('p99_us')}us "
              f"off -> {on.get('p99_us')}us on = "
              f"{r.get('shadow_p99_ratio')}x "
              f"(shadow: {shed.get('completions')} done, "
              f"{shed.get('errors')} shed)")
        qos = r.get("qos") or {}
        if qos:
            print(f"    qos: shadow sheds {qos.get('shadow_sheds')}, "
                  f"interactive preemptions "
                  f"{qos.get('interactive_preemptions')}")
    inter = r.get("interference") or {}
    if inter:
        legs = [("co_batch", inter.get("co_batch_us_per_req")),
                ("queue_wait", inter.get("queue_wait_us_per_req")),
                ("queue_growth", inter.get("queue_growth_us_per_req")),
                ("device_contention",
                 inter.get("device_contention_us_per_req")),
                ("occupancy_dilation",
                 inter.get("occupancy_dilation_us"))]
        shown = " + ".join(f"{name} {v}us" for name, v in legs
                           if v is not None)
        rho = inter.get("foreign_occupancy")
        print(f"    interference attribution: {shown}"
              + (f" (foreign occupancy {rho})" if rho is not None else "")
              + f" explains {inter.get('explained_fraction')} of the "
              f"{inter.get('p99_inflation_us')}us p99 inflation")


def _print_gauntlet_delta(rec: dict) -> None:
    """The scenario gauntlet's story on three lines: live p99 across
    the baseline/diurnal/flash/mix phases, the flash crowd's journal
    evidence (throttle fired AND cleared), and the per-class verdict."""
    g = rec.get("gauntlet") or rec
    base, diur = g.get("baseline") or {}, g.get("diurnal") or {}
    flash, mix = g.get("flash") or {}, g.get("adversarial_mix") or {}
    if base and flash:
        print(f"    gauntlet live p99: {base.get('p99_us')}us base -> "
              f"{diur.get('p99_us')}us diurnal "
              f"({diur.get('p99_ratio')}x) -> {flash.get('p99_us')}us "
              f"flash ({flash.get('p99_ratio')}x) -> "
              f"{mix.get('vision_p99_us')}us mix")
        print(f"    gauntlet flash crowd: throttle x"
              f"{flash.get('throttle_fired')} "
              f"cleared={flash.get('throttle_cleared')}, flood "
              f"{flash.get('flood_completions')} done / "
              f"{flash.get('flood_sheds')} shed")
    print(f"    gauntlet verdict: slo_pass={g.get('slo_pass')} "
          f"(threshold {g.get('slo_threshold_us')}us, "
          f"dlrm {mix.get('dlrm_ok')}, gpt {mix.get('gpt_ok')}, "
          f"preemptions {g.get('preemptions')})")


def _print_selfdriving_delta(rec: dict) -> None:
    """The self-driving probe's story per loop: dispatch retune with
    the fill recovery it bought, SLO-burn tightening fire/clear, and
    the drift rebalance with its move count and post-move hosting."""
    r = rec.get("selfdriving") or rec
    d, a = r.get("dispatch") or {}, r.get("admission") or {}
    b = r.get("rebalance") or {}
    if d:
        print(f"    selfdriving retune: tighten x{d.get('tighten_fired')}"
              f" restore x{d.get('restore_fired')}, fill "
              f"{d.get('fill_before')} -> {d.get('fill_after')}")
    if a:
        print(f"    selfdriving burn: tighten x{a.get('tighten_fired')} "
              f"restore x{a.get('restore_fired')} "
              f"cleared={a.get('cleared')}, flood {a.get('flood_ok')} ok"
              f" / {a.get('flood_shed')} shed")
    if b:
        print(f"    selfdriving drift: drift x{b.get('drift_events')} ->"
              f" rebalance x{b.get('fired')} ({b.get('moves')} moves, "
              f"{b.get('outcome')}), serving_after="
              f"{b.get('serving_after')}")
    bb = r.get("blackbox") or {}
    if bb:
        print(f"    selfdriving blackbox: {r.get('blackbox_bundles')} "
              f"bundles (one_per_incident={bb.get('one_per_incident')}, "
              f"max capture {r.get('blackbox_capture_ms')}ms)")
    print(f"    selfdriving verdict: loops_closed={r.get('loops_closed')}"
          f" fill_recovered={r.get('fill_recovered')} "
          f"bounded={r.get('bounded')}")


def _print_router_delta(rec: dict) -> None:
    """The router probe's scale-out story: aggregate ips/p99 at replica
    count 1 vs 2 (both through the router) and the 2v1 ratio the
    acceptance bar (>=1.6x, p99 no worse) reads off."""
    x1, x2 = rec.get("x1") or {}, rec.get("x2") or {}
    if not x1 or not x2:
        return
    scale = rec.get("scale_2v1")
    cpus = rec.get("host_cpus")
    print(f"    router scale-out: {x1.get('ips')} ips / "
          f"p99 {x1.get('p99_us')}us (x1) -> {x2.get('ips')} ips / "
          f"p99 {x2.get('p99_us')}us (x2)"
          + (f" = {scale}x" if scale is not None else "")
          + (f" [host_cpus={cpus}: contention-bound, not scale-out]"
             if cpus is not None and cpus < 4 else ""))
    if x2.get("spread"):
        print(f"    replica spread (ok): {json.dumps(x2['spread'])}")


if __name__ == "__main__":
    sys.exit(main())
