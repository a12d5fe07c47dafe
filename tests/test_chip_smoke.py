"""chip_smoke.py on the CPU: the one-worker fleet, the wire and every
check of the smoke run end to end at a tiny scale factor; the platform
gate and the worker-died-before-ready path fail the way the chip run
relies on."""

import json
import os
import pathlib
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def _smoke(*args):
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--sf", "0.01", *args],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)


def test_passes_end_to_end_on_cpu_when_allowed():
    out = _smoke("--allow-cpu")
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    assert lines[-1]["ok"] is True
    assert lines[-1]["device"]["platform"] == "cpu"
    assert lines[-1]["device"]["count"] >= 1
    queries = {ln["query"]: ln for ln in lines
               if ln.get("metric") == "chip_smoke_query"}
    assert sorted(queries) == ["q1", "q18", "q3", "q5", "q9"]
    for q in queries.values():
        assert q["engine"] == ["tpu"] and q["parity"], q
        assert q["cold_compiles"] >= 1 and q["warm_compiles"] == 0, q
    setup = next(ln for ln in lines if ln.get("metric") == "chip_smoke_setup")
    assert setup["lineitem_rows"] == 60012 and setup["kv_engine"]


def test_refuses_a_platform_that_is_not_tpu():
    out = _smoke()
    assert out.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in out.stderr
    assert "JAX_PLATFORMS='cpu'" in out.stderr  # says why JAX is off-chip
    # stopped before any data was generated, and printed no result
    assert "generating lineitem" not in out.stderr
    assert '"ok"' not in out.stdout


def raising_seed(domain, seeded=False):
    raise RuntimeError("seed hook exploded on purpose")


def test_a_raising_seed_hook_fails_the_smoke_at_once(monkeypatch, capsys):
    """The worker dies before ready: Fleet.start raises with its stderr
    instead of waiting out the timeout, and nothing is left running."""
    monkeypatch.chdir(REPO)
    monkeypatch.setattr(chip_smoke, "SEED_HOOK",
                        "tests.test_chip_smoke:raising_seed")
    t0 = time.monotonic()
    assert chip_smoke.main(["--allow-cpu", "--sf", "0.01"]) == 1
    assert time.monotonic() - t0 < 60
    captured = capsys.readouterr()
    assert "exited with code 1 before ready" in captured.err
    assert "seed hook exploded on purpose" in captured.err
    assert '"ok"' not in captured.out
