"""The per-case limit of conftest.py (ISSUE 52): it fails a case that
outlasts it, with the line the case was stuck on in the report, and it
leaves no timer behind a case that passed."""

import os
import signal
import subprocess
import sys

import pytest

CHILD = '''
import signal, time, conftest
conftest.CASE_LIMIT_S = 1.5
def test_passes():
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0
def test_sleeps():
    time.sleep(60)
def teardown_module():
    print("TIMER_LEFT", signal.getitimer(signal.ITIMER_REAL))
'''


@pytest.fixture(scope="module")
def child_report(tmp_path_factory):
    """A child pytest (no -n) under this suite's conftest.py as a plugin."""
    path = tmp_path_factory.mktemp("case_limit") / "test_child.py"
    path.write_text(CHILD)
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [tests, os.path.dirname(tests)]))
    return subprocess.run(
        [sys.executable, "-m", "pytest", str(path), "-p", "conftest",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        env=env, cwd=path.parent, capture_output=True, text=True, timeout=120)


def test_a_case_over_the_limit_fails_with_its_stack(child_report):
    out = child_report.stdout
    assert child_report.returncode == 1, out + child_report.stderr
    assert "1 failed, 1 passed" in out and "error" not in out.lower()
    assert "time.sleep(60)" in out and "CASE_LIMIT_S = 1.5 s" in out
    assert "Timeout (0:00:00.5" in out       # faulthandler's dump, captured


def test_no_timer_is_left_after_a_case(child_report):
    assert "TIMER_LEFT (0.0, 0.0)" in child_report.stdout
    # nor by the harness around THIS case beyond the one it armed for it
    left, interval = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left and interval == 0
