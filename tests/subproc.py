"""Watchdog-wrapped subprocess runner for the sharded test scripts.

The sharded equivalence tests spawn ``python -c SCRIPT`` children with 8
forced host devices; a wedged child (XLA deadlock, runaway compile) used to
hold the whole suite hostage until the outer CI timeout.  ``run_json`` puts
every child in its own process group and, when the watchdog fires,
SIGKILLs the *group* — grandchildren holding the stdout/stderr pipes can't
keep ``communicate()`` blocked — then fails the test with the captured
output tails instead of hanging.

For CPU tests only: the children run with ``JAX_PLATFORMS=cpu``.  A chip
belongs to one process, so nothing that drives a TPU starts children after
touching JAX.
"""

import json
import os
import signal
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_json(script: str, *, timeout: float = 600, env: dict | None = None):
    """Run ``python -c script`` under a hard watchdog; parse the last
    stdout line as JSON.

    The child gets ``PYTHONPATH=src`` and ``JAX_PLATFORMS=cpu`` (override
    via ``env``).  A non-zero exit asserts with the stderr tail; a timeout
    SIGKILLs the child's whole process group and asserts with both tails.
    """
    full_env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    if env:
        full_env.update(env)
    proc = subprocess.Popen(
        [sys.executable, "-c", script], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, env=full_env,
        start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        out, err = proc.communicate()
        raise AssertionError(
            f"subprocess watchdog fired after {timeout}s\n"
            f"--- stdout tail ---\n{(out or '')[-2000:]}\n"
            f"--- stderr tail ---\n{(err or '')[-2000:]}")
    assert proc.returncode == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1])
