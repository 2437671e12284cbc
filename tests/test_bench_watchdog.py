"""bench.py outage robustness (VERDICT r4 #1a/#1b/#7).

Runs the real bench entry point as a subprocess with the simulated-hang
knob and asserts the failure-mode contracts:

- backend-init hang -> ``status: "backend_init_error"`` within the init
  deadline and a NONZERO exit (an outage must be distinguishable from a
  perf collapse, and a driver must not file it as a green run);
- mid-run hang -> watchdog emits ``status: "partial-outage"`` carrying the
  sections that DID complete, and those sections' evidence has already been
  persisted to BENCH_HISTORY incrementally; the exit is NONZERO;
- one hung section -> the per-section deadline costs that section only, the
  emit names it in ``sections_failed`` and the exit is NONZERO;
- the emit is exactly one JSON line on stdout either way (driver schema)
  and names the device the run was on.

The hang cases let the ``autotune`` and ``gen`` smoke sections be the ones
that complete: their length is fixed by compiles and two-second phases (~5 s
and ~14 s on the CPU host), not by a stability search that may take three
windows or twelve, so a deadline only has to clear those.

Reference anchor for the discipline being protected: the stability
machinery of /root/reference/src/c++/perf_analyzer/inference_profiler.cc
(503-547) is only worth anything if the numbers it produces survive the run.
"""

import json
import os
import subprocess
import sys
import time


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def run_bench(tmp_path, extra_env, timeout=240, expect_rc=None):
    hist = tmp_path / "hist.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_HISTORY_PATH": str(hist),
        "BENCH_PEAK_FLOPS": "1e12",
        **extra_env,
    })
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO)
    if expect_rc is not None:
        assert proc.returncode == expect_rc, (
            f"expected rc={expect_rc}, got {proc.returncode}\n"
            f"stderr tail: {proc.stderr[-2000:]}")
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, (
        f"expected exactly one stdout JSON line, got {lines!r}\n"
        f"stderr tail: {proc.stderr[-2000:]}")
    out = json.loads(lines[0])
    history = json.loads(hist.read_text()) if hist.exists() else []
    return out, history


def test_init_hang_aborts_with_backend_init_error(tmp_path):
    # Round-6 contract: an init outage fails FAST with an unambiguous
    # diagnostic and a nonzero exit — rounds 4/5 each recorded a hollow
    # "unavailable" run (rc=0) that sat in the baseline looking like data.
    out, history = run_bench(tmp_path, {
        "BENCH_SIMULATE_HANG": "init",
        "BENCH_INIT_DEADLINE_S": "3",
    }, expect_rc=3)
    assert out["status"] == "backend_init_error"
    assert out["value"] == 0.0  # numeric for the driver schema
    assert "init exceeded" in out["reason"]
    # the outage itself is on the record
    assert any(h.get("probe") == "run-status"
               and h.get("status") == "backend_init_error" for h in history)


def test_midrun_hang_emits_partial_with_completed_sections(tmp_path):
    # Hang at the gen probe: the autotune section completes first, so the
    # partial must carry it and history must already hold it.  Filtered
    # run: the time-budget skip never applies to BENCH_SECTIONS captures
    # (they attempt exactly what was asked), so the hang genuinely reaches
    # gen and the run-level watchdog adjudicates.  The per-section
    # deadline (default 600s) stays above the watchdog on purpose: this
    # test pins the watchdog path, not the section guard.
    out, history = run_bench(tmp_path, {
        "BENCH_SECTIONS": "autotune,gen",
        "BENCH_SIMULATE_HANG": "gen",
        "BENCH_DEADLINE_S": "30",
        "BENCH_SMOKE": "1",
    }, timeout=400, expect_rc=1)  # a partial is not a green run
    assert out["status"] == "partial-outage"
    assert out["sections"] == "autotune,gen"
    assert out["partial"] is True
    assert out["metric"] == "inproc_simple_ips"
    assert out["value"] == 0.0  # no headline was asked for
    assert "autotune" in out["sections_completed"]
    # Every emit names the device the numbers came from.
    assert (out["platform"], out["device_kind"]) == ("cpu", "cpu")
    assert out["device_count"] >= 1
    assert "platform" not in out["sections_completed"]
    done_records = [h for h in history if h.get("probe") == "autotune"]
    assert done_records, "completed probe must persist before the hang"
    assert done_records[0]["platform"] == "cpu"
    assert any(h.get("probe") == "run-status"
               and h.get("status") == "partial-outage" for h in history)


def test_section_deadline_bounds_one_hung_probe(tmp_path):
    # Round-5 failure mode: a device stall during ONE section's engine
    # warmup hung the whole capture.  The per-section deadline
    # (BENCH_SECTION_DEADLINE_S) must abort just that section and let the
    # rest of the run proceed to a normal emit that names the casualty —
    # and exits nonzero.  Also pins the BENCH_SECTIONS filter: exactly the
    # named sections are attempted, and a run without the headline says so.
    # The deadline covers every section alike, so it has to clear the gen
    # section's honest runtime on this host as loaded as it is now (five
    # other test workers may share it: 35 s, 2.5x an idle host's 14 s, cut
    # the section that was to complete): a dry gen run is timed first, and
    # the deadline is 2.5x that, far below the run watchdog.
    dry_dir = tmp_path / "dry"
    dry_dir.mkdir()
    started = time.monotonic()
    dry, _ = run_bench(dry_dir, {"BENCH_SECTIONS": "gen", "BENCH_SMOKE": "1"},
                       timeout=400)
    assert dry["gen_tok_s"] > 0
    deadline_s = max(35, round(2.5 * (time.monotonic() - started)))
    out, history = run_bench(tmp_path, {
        "BENCH_SECTIONS": "bert,gen",
        "BENCH_SIMULATE_HANG": "bert",
        "BENCH_SECTION_DEADLINE_S": str(deadline_s),
        "BENCH_SMOKE": "1",
    }, timeout=900, expect_rc=1)
    assert out["status"] == "sections-filtered"
    assert out["sections"] == "bert,gen"
    assert out["value"] == 0.0  # numeric for the driver schema; the
    # distinct status is what marks "no headline measured"
    assert "windows" not in out  # simple probe really did not run
    assert out["sections_failed"] == ["bert"]
    assert "bert_b8_ips" not in out  # the hung probe contributed nothing
    assert out["gen_tok_s"] > 0  # the section after it ran
    probes = {h.get("probe") for h in history}
    assert "gen" in probes
    assert "simple" not in probes
    run_status = [h for h in history if h.get("probe") == "run-status"]
    assert run_status[-1]["sections_failed"] == ["bert"]


def test_headline_failure_is_not_mistaken_for_filtering(tmp_path):
    # A failed simple probe must read "headline-failed", not the
    # sections-filtered status that means "deliberately not measured".
    out, history = run_bench(tmp_path, {
        "BENCH_SECTIONS": "simple",
        "BENCH_SIMULATE_HANG": "simple",
        "BENCH_SECTION_DEADLINE_S": "3",
        "BENCH_SMOKE": "1",
    }, timeout=400, expect_rc=1)
    assert out["status"] == "headline-failed"
    assert out["value"] == 0.0
    assert out["sections_failed"] == ["simple"]
    assert any(h.get("probe") == "run-status"
               and h.get("status") == "headline-failed" for h in history)


def test_crash_emits_error_partial(tmp_path):
    # A crash (here: the BENCH_SECTIONS validation error itself) must still
    # produce the single self-describing JSON line, not an empty stdout
    # with rc=1 — including when the crash IS the filter validation, which
    # the emit path re-consults for its `sections` tag.
    hist = tmp_path / "hist.json"
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "BENCH_HISTORY_PATH": str(hist),
                "BENCH_SECTIONS": "bogus"})
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        timeout=120, env=env, cwd=REPO)
    assert proc.returncode != 0
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, lines
    out = json.loads(lines[0])
    assert out["status"] == "error"
    assert out["partial"] is True
    assert "bogus" in out["reason"]
    assert out["sections"] == "bogus"  # raw env preserved for the record
    history = json.loads(hist.read_text())
    assert any(h.get("probe") == "run-status" and h.get("status") == "error"
               for h in history)


def test_time_budget_skips_trailing_sections_cleanly(tmp_path):
    # A full run that would honestly outlast the watchdog must truncate
    # itself (sections_skipped) instead of running into a partial-outage
    # at the finish line.  BENCH_DEADLINE_S=190 lets the smoke `simple`
    # headline (~20-45s; never budget-skipped) complete while every other
    # section's estimate (the cheapest is 90s) crosses the budget
    # (deadline - 90s) and skips — so the test's length is the headline's.
    out, history = run_bench(tmp_path, {
        "BENCH_DEADLINE_S": "190",
        "BENCH_SMOKE": "1",
    }, timeout=400, expect_rc=0)
    assert out["status"] == "ok"
    assert (out["platform"], out["device_kind"]) == ("cpu", "cpu")
    assert out["partial"] is not True if "partial" in out else True
    assert out["value"] > 0
    assert "bert" in out["sections_skipped"]
    assert "ssd_net" in out["sections_skipped"]
    assert "simple" not in out["sections_skipped"]
    # the skip is a budget decision, not a failure
    assert "sections_failed" not in out


def test_sweep_concurrency_entry_point(tmp_path):
    # The headline knee sweep: per-point records append to history as each
    # point completes, and the emit is one JSON line keyed by concurrency.
    hist = tmp_path / "hist.json"
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "BENCH_HISTORY_PATH": str(hist),
                "BENCH_SMOKE": "1"})
    proc = subprocess.run(
        [sys.executable, BENCH, "--sweep-concurrency", "4,8"],
        capture_output=True, text=True, timeout=400, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-1000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert out["metric"] == "simple_concurrency_sweep"
    assert out["c4"]["ips"] > 0 and out["c8"]["ips"] > 0
    history = json.loads(hist.read_text())
    sweeps = [h for h in history if h.get("probe") == "simple_sweep"]
    assert [h["concurrency"] for h in sweeps] == [4, 8]
    assert all("sweep" in h.get("config", "") for h in sweeps)
