"""A run with the timed path broken underneath reads ``correct`` false.

Each case drives the rest of a run of ``spreader.fit`` at toy size on
the CPU (the harness's look for a chip is the command's, and is
skipped) with one fault of ``_bench_fit_faults.py`` planted in the
stream engine before it is built: a refresh that leaves its state
unchanged; half of each ingest batch left out; the merge of local
clusters over the shards left out; a global label altered where it is
made.  ``sound`` plants nothing and reads ``correct`` true."""
import os
import pathlib
import subprocess
import sys

import pytest

FIT_SCRIPT = pathlib.Path(__file__).with_name("_bench_fit_faults.py")


@pytest.mark.parametrize("fault", ["sound", "state_unchanged", "half_points",
                                   "exchange_left_out", "answer_altered"])
def test_fit_fault_is_not_correct(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = pathlib.Path(__file__).resolve().parents[2]
    env["PYTHONPATH"] = os.pathsep.join([str(root / "src"), str(root)])
    done = subprocess.run([sys.executable, str(FIT_SCRIPT), fault], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    want = fault == "sound"
    assert done.stdout.strip().splitlines()[-1] == f"{fault} correct={want}", done.stdout
