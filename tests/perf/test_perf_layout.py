"""The benchmark's layout and its refusals: every cell's pieces are found
by name, and a run off the chip exits non-zero without a result line."""
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_pieces_by_name(cell):
    c = harness.load_cell(cell)
    assert c.chips in (1, 4)
    assert "limits" in c.traffic
    assert (ROOT / "bench" / "drivers" / f"{c.traffic['driver']}.py").is_file()
    assert {"setup_s"} < {m["name"] for m in c.end_to_end}
    assert c.per_layer
    for m in c.per_layer:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for fn in ("make_inputs", "work"):
        assert callable(getattr(c.model, fn))
    assert callable(c.ref.reference) and callable(c.ref.compare)


def test_names_units_and_layers_keep_the_contract():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_unknown_device_kind_is_refused():
    with pytest.raises(harness.Refusal):
        harness.peaks("TPU v0 imaginary")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_seeds_past_32_bits_give_distinct_31_bit_seeds():
    a = harness.sub_seeds(2**33 + 5, 4)
    assert a == harness.sub_seeds(2**33 + 5, 4)
    assert a != harness.sub_seeds(2**33 + 6, 4)
    assert all(0 <= s < 2**31 for s in a)


def test_run_off_the_chip_exits_nonzero_without_a_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "track-512-n1m", "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert proc.returncode != 0
    assert "refused" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
