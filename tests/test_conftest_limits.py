"""The per-test time limit of tests/conftest.py, driven through a pytest
of its own on a two-line file (the limit fails the test it fires in)."""
import os
import subprocess
import sys

import pytest

_TESTS = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_TESTS)


def _run_pytest(tmp_path, body, *args):
    test_file = tmp_path / "test_limited.py"
    test_file.write_text("import time\nimport pytest\n" + body)
    # no third-party plugin: their imports are most of such a run's time
    env = dict(os.environ, PYTHONPATH=_TESTS,
               PYTEST_DISABLE_PLUGIN_AUTOLOAD="1")
    # conftest.py rides in as a plugin: the file under test lives outside
    # tests/, where pytest would not collect it
    return subprocess.run(
        [sys.executable, "-m", "pytest", str(test_file), "-p", "conftest",
         "-c", os.path.join(_ROOT, "pytest.ini"), "--rootdir", str(tmp_path),
         "-p", "no:cacheprovider", "-q", *args],
        env=env, capture_output=True, text=True, timeout=120)


def test_sleep_past_the_limit_fails_with_limit_and_thread_dump(tmp_path):
    r = _run_pytest(
        tmp_path,
        "@pytest.mark.time_limit(1)\n"
        "def test_sleeps():\n    time.sleep(30)\n")
    out = r.stdout + r.stderr
    assert r.returncode == 1, out
    assert "time limit 1 s" in out
    assert "1 failed" in out
    # faulthandler's dump of all threads, naming the frame that slept
    assert "most recent call first" in out and "test_sleeps" in out


def test_under_the_limit_passes(tmp_path):
    r = _run_pytest(
        tmp_path,
        "@pytest.mark.time_limit(20)\n"
        "def test_quick():\n    time.sleep(0.05)\n")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "1 passed" in r.stdout


def test_limit_zero_disables(tmp_path):
    r = _run_pytest(
        tmp_path,
        "import signal\n"
        "@pytest.mark.time_limit(0)\n"
        "def test_unarmed():\n"
        "    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)\n"
        "def test_armed_by_default():\n"
        "    left, _ = signal.getitimer(signal.ITIMER_REAL)\n"
        "    assert 0 < left <= 180\n")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "2 passed" in r.stdout


def test_limit_is_disarmed_after_the_test(tmp_path):
    r = _run_pytest(
        tmp_path,
        "import signal\n"
        "@pytest.mark.time_limit(1)\n"
        "def test_a():\n    pass\n"
        "@pytest.mark.time_limit(0)\n"
        "def test_b():\n"
        "    time.sleep(1.5)\n"
        "    assert signal.getsignal(signal.SIGALRM) == signal.SIG_DFL\n")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "2 passed" in r.stdout


@pytest.mark.parametrize("phase", ["set-up", "tear-down"])
def test_limit_covers_fixture_phases(tmp_path, phase):
    before, after = (30, 0) if phase == "set-up" else (0, 30)
    r = _run_pytest(
        tmp_path,
        "@pytest.fixture\n"
        "def stuck():\n"
        f"    time.sleep({before})\n    yield\n    time.sleep({after})\n"
        "@pytest.mark.time_limit(1)\n"
        "def test_uses_it(stuck):\n    pass\n")
    out = r.stdout + r.stderr
    assert r.returncode == 1, out
    assert "time limit 1 s" in out and "1 error" in out


@pytest.mark.parametrize("marker", ["slow", "time_limit(5)"])
def test_markers_are_registered(tmp_path, marker):
    r = _run_pytest(
        tmp_path,
        f"@pytest.mark.{marker}\ndef test_marked():\n    pass\n",
        "--strict-markers")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "1 passed" in r.stdout
