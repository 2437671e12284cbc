"""Bring-up contracts (PR 21): what must hold for the program to be trusted
on the chip, checked here without one.

- ``chip_smoke.py``'s parent path (and the other processes that start
  chip-holding children or only write shared memory) imports no JAX;
- its phase table cannot end 0 on a failing phase, and with no chip it
  exits non-zero naming backend initialisation;
- ``ensure_backend`` places the compile cache at the fixed in-tree path
  unless ``JAX_COMPILATION_CACHE_DIR`` is set, and then sets nothing;
- an import error in a zoo module propagates, and the launcher exits
  non-zero when a ``--zoo`` model fails to load.

No test here boots a server.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_off_chip_processes_import_no_jax():
    # One interpreter for all of them: the smoke parent with every client it
    # drives servers through, the replay producers (shared memory only) and
    # the router frontend (which starts in front of chip-holding replicas).
    code = (
        "import sys\n"
        "import chip_smoke\n"
        "import client_tpu.http, client_tpu.grpc\n"
        "import client_tpu.utils.shared_memory\n"
        "import tools.replay, client_tpu.router.__main__\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def _ctx():
    return {"mode": chip_smoke.Mode(rehearse_cpu=False)}


def _names_device(ctx):
    ctx["device"] = {"platform": "tpu", "kind": "stub", "count": 1}


def test_phase_table_passes_with_result_line_last(capsys):
    rc = chip_smoke.run_phases(
        [("C stub", _names_device), ("A stub", lambda ctx: None)], _ctx())
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "stub", "count": 1}}


def test_phase_table_exits_nonzero_on_a_failing_phase(capsys):
    ran = []

    def fails(ctx):
        raise chip_smoke.SmokeFailure("stub child exited with code 7")

    rc = chip_smoke.run_phases(
        [("C stub", _names_device), ("A stub", fails),
         ("B stub", lambda ctx: ran.append("B"))], _ctx())
    out = capsys.readouterr().out
    assert rc == 1
    assert ran == ["B"]  # later phases still report, none rescues the run
    assert "phase A stub: FAILED" in out and "exited with code 7" in out
    assert '"ok"' not in out  # no result line
    # Anything but SmokeFailure is a bug in the script: it propagates.
    with pytest.raises(ZeroDivisionError):
        chip_smoke.run_phases([("C stub", lambda ctx: 1 / 0)], _ctx())


def test_no_chip_exits_nonzero_naming_backend_init():
    # TPU_LIBRARY_PATH makes "no chip" true on any machine: libtpu cannot
    # load, so JAX_PLATFORMS=tpu is JAX's hard error in the first child.
    env = dict(os.environ, TPU_LIBRARY_PATH="/nonexistent/libtpu.so")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode not in (0, None)
    assert "JAX backend initialisation failed" in proc.stdout
    assert '"ok"' not in proc.stdout


def test_compile_cache_placed_from_outside_or_fixed_in_tree(monkeypatch):
    import jax

    from client_tpu.engine import backend_init

    before = jax.config.jax_compilation_cache_dir
    try:
        # Set from outside: JAX reads its own variable, code sets nothing.
        monkeypatch.setattr(backend_init, "_devices", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where/else")
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        backend_init.ensure_backend()
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        # Not set: the fixed path inside the checkout, git-ignored.
        monkeypatch.setattr(backend_init, "_devices", None)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        backend_init.ensure_backend()
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache") == backend_init.DEFAULT_CACHE_DIR
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_zoo_module_import_error_propagates(monkeypatch):
    import client_tpu.models as zoo

    zoo.model_names()  # everything imports today
    # A None entry makes the import machinery raise ImportError for that
    # module — what a renamed Pallas symbol inside it would cause.
    monkeypatch.delattr(zoo, "dlrm")
    monkeypatch.setitem(sys.modules, "client_tpu.models.dlrm", None)
    with pytest.raises(ImportError):
        zoo.model_names()
    with pytest.raises(ImportError):
        zoo.build_repository(["simple"])


def test_launcher_exits_nonzero_when_a_zoo_model_fails_to_load(
        monkeypatch, capsys):
    import client_tpu.models as zoo
    from client_tpu.server.__main__ import main

    def broken():
        raise RuntimeError("Mosaic failed to compile TPU kernel (stub)")

    zoo.model_names()
    monkeypatch.setitem(zoo._REGISTRY, "broken_probe", broken)
    monkeypatch.setattr(zoo, "_NON_DEFAULT", zoo._NON_DEFAULT
                        | {"broken_probe"})
    rc = main(["--zoo", "simple,broken_probe", "--http-port", "0",
               "--no-grpc"])
    err = capsys.readouterr().err
    assert rc == 1
    assert "model broken_probe failed to load" in err
    assert "Mosaic failed to compile" in err
    assert "serving http" not in err  # it never announced itself


def test_rehearsal_result_line_never_reads_ok(capsys):
    rc = chip_smoke.run_phases(
        [("C stub", _names_device)],
        {"mode": chip_smoke.Mode(rehearse_cpu=True)})
    out = capsys.readouterr().out
    assert rc == 0
    result = json.loads(out.strip().splitlines()[-1])
    assert result["ok"] is False and result["rehearsal"] is True
    assert all("REHEARSAL" in ln for ln in out.strip().splitlines()[:-1])
