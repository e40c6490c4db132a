"""``spreader.fit4`` at toy size on four virtual CPU devices: the cell's
loop through the ``jit`` backend reads ``correct`` true against the
plain reference, and false with the butterfly's exchange left out; the
per-lane phase-1 stats the program returns are those of each shard run
alone; a contour budget too small for the clusters is reported as cut.
The checks run in one subprocess (``_bench_fit4.py``), which has to set
the device count before JAX starts."""
import os
import pathlib
import subprocess
import sys

import pytest

SCRIPT = pathlib.Path(__file__).with_name("_bench_fit4.py")


@pytest.fixture(scope="module")
def lines():
    root = pathlib.Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(root / "src"), str(root)])
    done = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                          capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return {ln.split(" ", 1)[0]: ln for ln in done.stdout.splitlines()}


def test_sound_run_is_correct(lines):
    assert lines["sound"] == "sound correct=True", lines


def test_lane_stats_equal_local_phase_stats(lines):
    assert lines["lane_stats"] == "lane_stats equal=True", lines


def test_tiny_contour_budget_sets_truncated(lines):
    got = dict(kv.split("=") for kv in lines["truncated"].split()[1:])
    assert int(got["sound"]) == 0 and int(got["tiny"]) > 0, lines


def test_butterfly_left_out_is_not_correct(lines):
    assert lines["no_exchange"] == "no_exchange correct=False", lines
