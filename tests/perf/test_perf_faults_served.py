"""``correct`` is decided by a comparison shown to fail: the harness runs
here past its look for a chip, at a size a test run holds, first sound and
then with the timed path broken underneath, and each fault the served cell
can have makes ``correct`` come out false, and so does the bfloat16
control.
"""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from perf_faultkit import (SERVED, control_checks,  # noqa: E402
                           frozen_state, over, run_cell)
from repro.core import smc  # noqa: E402
from repro.serve import frontend, sessions  # noqa: E402


@pytest.fixture(scope="module")
def sound_served():
    return run_cell(SERVED)


def test_sound_served_run_is_correct(sound_served):
    correct, checks = sound_served
    assert correct, checks


def test_served_frozen_state_is_not_correct(monkeypatch):
    monkeypatch.setattr(smc, "make_sir_step",
                        frozen_state(smc.make_sir_step))
    correct, checks = run_cell(SERVED)
    assert not correct, checks


def test_served_half_the_batch_is_not_correct(monkeypatch):
    """The sessions in the upper half of the bank's slots left out of
    every step: their slots run masked and answer with the neutral
    output, the rest are stepped."""
    route = sessions.ParticleSessionServer._route

    def half(self, tier, slots):
        idx, active = route(self, tier, slots)
        return idx, active & (idx < self.capacity // 2)
    monkeypatch.setattr(sessions.ParticleSessionServer, "_route", half)
    correct, checks = run_cell(SERVED)
    assert not correct and over(checks, "pos_med_px"), checks


def test_served_altered_answer_is_not_correct(monkeypatch):
    fire = frontend.ParticleFrontend._fire

    def altered(self, work):
        return [(np.asarray(r[0]) + np.array([1.0, 0, 0, 0, 0]),) + r[1:]
                for r in fire(self, work)]
    monkeypatch.setattr(frontend.ParticleFrontend, "_fire", altered)
    correct, checks = run_cell(SERVED)
    assert not correct and over(checks, "pos_med_px"), checks


def test_served_control_is_not_correct():
    """The reference in bfloat16 in the place of the served answers."""
    correct, checks = control_checks(SERVED)
    assert not correct and over(checks, "pos_med_px"), checks
