"""chip_smoke.py off the chip: each phase's own checks pass at a tiny size
on the CPU (so a chip run fails only for what the chip does), and the
script refuses to report a result without a TPU."""
import os
import subprocess
import sys

import pytest

from repro.core import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
try:
    import chip_smoke
finally:
    sys.path.remove(REPO)

# the filter phases' density (4 particles per pixel) at 32x32 frames
TINY = dict(img=32, n_filter=4096, n_oracle=4096, serve_img=32, sessions=2,
            n_session=4096, capacity=2, n_per_chip=1024,
            n_domain_per_chip=512)

ONE_CHIP_PHASES = {
    "filter": lambda sz: [chip_smoke.phase_filter(sz, 0)],
    "oracle": lambda sz: chip_smoke.phase_oracle(sz, 0),
    "serve": lambda sz: [chip_smoke.phase_serve(sz, 0)],
}


@pytest.mark.parametrize("name", sorted(ONE_CHIP_PHASES))
def test_one_chip_phase_passes_at_tiny_size(name):
    for phase in ONE_CHIP_PHASES[name](chip_smoke.Sizes(**TINY)):
        assert phase.ok, phase.line()


def test_sharded_phases_pass_on_simulated_devices():
    """The ``--chips 4`` phases on four simulated CPU devices."""
    code = (f"import sys; sys.path.insert(0, {REPO!r}); import chip_smoke; "
            f"phases = chip_smoke.run_phases(chip_smoke.Sizes(**{TINY!r}), "
            f"0, 4); sys.exit(0 if all(p.ok for p in phases) else 1)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600,
                         env=runtime.host_device_env(4))
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert out.stdout.count("PHASE ") == 4


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_refuses_without_tpu(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable,
                          os.path.join(REPO, "chip_smoke.py"), *args],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
