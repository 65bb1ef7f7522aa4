"""A runner call that does not return in time ends the run, naming the stall."""
import os
import subprocess
import sys

from bench.cell import ROOT

SCRIPT = """
import sys, time
from bench.run import deadline

class Runner:
    iter_count = [7, 7, 6, 7]
    sync_count = 41

with deadline("the window", 0.5, Runner()):
    time.sleep(30)
print("{}")
"""


def test_stalled_window_exits_with_its_progress():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}")
    p = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    from bench.run import STALLED

    assert p.returncode == STALLED
    assert p.stdout == ""
    assert "STALLED: the window has not ended after 0s" in p.stderr
    assert "[7, 7, 6, 7]" in p.stderr and "sync count 41" in p.stderr
    assert "Thread" in p.stderr  # every thread's stack


def test_deadline_met_leaves_the_run_alone():
    import time

    from bench.run import deadline

    with deadline("the window", 5, object()):
        time.sleep(0.01)
