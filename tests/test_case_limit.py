"""The suite bounds each case from inside (``tests/conftest.py
case_limit``): a case still running at ``CASE_LIMIT_S`` fails by name
with every thread's stack on stderr, and the run goes on."""

import os
import subprocess
import sys
import time

SUITE_CONFTEST = os.path.join(os.path.dirname(__file__), "conftest.py")


def test_a_case_over_the_limit_fails_by_name_with_every_threads_stack(tmp_path):
    (tmp_path / "conftest.py").write_text(
        "import importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('suite_conftest', {SUITE_CONFTEST!r})\n"
        "suite = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(suite)\n"
        "suite.CASE_LIMIT_S = 1\n"
        "case_limit = suite.case_limit\n")
    (tmp_path / "test_sleeps.py").write_text(
        "import time\n\n"
        "def test_sleeps():\n    time.sleep(60)\n\n"
        "def test_returns():\n    pass\n")
    began = time.monotonic()
    run = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    took = time.monotonic() - began
    said = run.stdout + run.stderr
    assert run.returncode == 1, said
    assert "1 failed, 1 passed" in run.stdout, said
    assert "test_sleeps.py::test_sleeps was still running after 1 s" in said, said
    # faulthandler's dump of the worker's threads, the sleeping frame in it
    assert "most recent call first" in said and "in test_sleeps" in said, said
    assert took < 15, took
