"""``correct`` is decided by a comparison shown to fail: the harness runs
here past its look for a chip, at a size a test run holds, first sound and
then with the timed path broken underneath, and each fault the offline cell
can have makes ``correct`` come out false.  The bfloat16 control, the
reference put in the program's place, fails too.
"""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from perf_faultkit import (OFFLINE, control_checks,  # noqa: E402
                           frozen_state, half_the_particles, over, run_cell)
from repro.core import filters, smc  # noqa: E402
from repro.models import tracking  # noqa: E402


@pytest.fixture(scope="module")
def sound_offline():
    return run_cell(OFFLINE)


def test_sound_offline_run_is_correct(sound_offline):
    correct, checks = sound_offline
    assert correct, checks


def test_offline_frozen_state_is_not_correct(monkeypatch):
    monkeypatch.setattr(smc, "make_sir_step",
                        frozen_state(smc.make_sir_step))
    correct, checks = run_cell(OFFLINE)
    assert not correct and over(checks, "pos_med_px"), checks


def test_offline_half_the_particles_is_not_correct(monkeypatch):
    monkeypatch.setattr(tracking.TrackingSSM, "observation_log_prob",
                        half_the_particles(
                            tracking.TrackingSSM.observation_log_prob))
    correct, checks = run_cell(OFFLINE)
    assert not correct and over(checks, "ess_gap"), checks


def test_offline_altered_answer_is_not_correct(monkeypatch):
    """Every answer moved by 8 px, a spot missed by seven PSF widths:
    twice the cell's limit on the median gap, which a sound run on a
    slowly converging movie reads up to 0.5 px (PERF.md section 4)."""
    run = filters.ParallelParticleFilter.run

    def altered(self, key, obs):
        res = run(self, key, obs)
        return res._replace(estimates=res.estimates.at[:, 0].add(8.0))
    monkeypatch.setattr(filters.ParallelParticleFilter, "run", altered)
    correct, checks = run_cell(OFFLINE)
    assert not correct and over(checks, "pos_med_px"), checks


def test_offline_control_is_not_correct():
    """The reference in bfloat16 in the program's place."""
    correct, checks = control_checks(OFFLINE)
    assert not correct and over(checks, "pos_med_px"), checks
