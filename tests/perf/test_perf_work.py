"""Each configuration's work count (the FLOPs and HBM bytes one filter
step needs at least) against the arithmetic done by hand at a small
size."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def _config(name):
    model = harness.load_module(ROOT / "bench" / "configs" / f"{name}.py",
                                f"work_{name.replace('-', '_')}")
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    return model, cfg


@pytest.mark.parametrize("share", [0.0, 1.0])
def test_tracking_work_by_hand(share):
    model, cfg = _config("tracking-psf4")
    flops, nbytes = model.work(cfg, {"img": 16}, 1000, share)
    # per particle: advance 12, likelihood 8 x 81 pixels, weights 4,
    # weighted mean 2 x 5, ESS 2
    assert flops == 1000 * (12 + 648 + 4 + 10 + 2)
    # state 20 B read + written, log-weight 4 B written + read; when
    # resampling, ancestor 4 B written + read and the gathered state
    # read + written; the 16 x 16 frame read once
    assert nbytes == 1000 * (40 + 8 + share * (8 + 40)) + 16 * 16 * 4


def test_tracking_work_scales_with_the_patch():
    model, cfg = _config("tracking-psf4")
    small, _ = model.work({**cfg, "patch_radius": 1}, {"img": 16}, 10, 1.0)
    assert small == 10 * (12 + 8 * 9 + 16)
