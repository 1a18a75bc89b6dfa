"""What decides ``correct`` fails when it should, on the CPU at tiny size.

The run's own comparison, with the harness's look for a chip skipped: sound
runs pass; the control (the reference one precision down, in the program's
place) fails a limit; and each fault of ``faults.py`` planted under the timed
path turns ``correct`` false."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import control  # noqa: E402
from testsizes import tiny  # noqa: E402

SEED = 2**33 + 29


@pytest.fixture(scope="module")
def sweep():
    return tiny("table9-500.sweep8")


@pytest.fixture(scope="module")
def sweep_sound(sweep):
    return control.readings(sweep, SEED, 2)


def test_sweep_sound_run_passes_and_control_fails(sweep_sound):
    assert sweep_sound["correct"], sweep_sound
    assert not sweep_sound["control_correct"], sweep_sound


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer", "inflated_fitness"])
def test_sweep_faults_turn_correct_false(sweep, fault):
    r = control.readings(sweep, SEED, 2, fault=fault, warm=False)
    assert not r["correct"], r


def test_sweep_stalled_step_turns_correct_false(sweep, sweep_sound):
    r = control.readings(sweep, SEED, 2, fault="stalled_step", warm=False)
    assert not r["correct"] and r["numbers"]["stalled"] > 0, r
    assert r["makespan_vs_lb"] > sweep_sound["makespan_vs_lb"], (r, sweep_sound)



def test_sweep_lost_exchange_on_four_devices():
    """Four virtual CPU devices, so that the sweep stripes; the exchange of
    the other devices' rows is left out."""
    code = (
        "import json, sys; sys.path.insert(0, %r)\n"
        "import control\nfrom testsizes import tiny\n"
        "cell = tiny('table9-500.sweep8.4chip')\n"
        "cell.traffic = dict(cell.traffic, group=4)\n"
        "sound = control.readings(cell, %d, 1)\n"
        "lost = control.readings(cell, %d, 1, fault='lost_exchange', warm=False)\n"
        "print(json.dumps([sound['correct'], lost['correct']]))\n" % (str(HERE), SEED, SEED))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == [True, False]
