"""What every cell shares: finding a cell's pieces by name, the device
check, the table of peaks, seeds, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its pieces are
found by the names there and nothing else, so a later cell adds files and
edits none:

* ``bench/configs/<config>.json``   the configuration as it is run;
* ``bench/configs/<config>.py``     how the program is built from it, the
  inputs it is fed, and the work one step needs;
* ``bench/configs/<config>.ref.py`` its plain reference and the comparison
  that decides ``correct``;
* ``bench/traffic/<traffic>.json``  the traffic mix: parameters read by
  the general driver that its ``driver`` key names;
* ``bench/drivers/<driver>.py``     that driver (``bench/window.py`` says
  what a driver defines);
* ``bench/metrics/<metric>.py``     one reader per per-layer metric.

A cell's chip count is its ``chips`` in ``BENCHMARK.json``; how the program
spreads over them (a mesh, a distributed resampler) is data of the mix that
the configuration's ``build_filter`` reads.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
from typing import Any

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"


class Refusal(RuntimeError):
    """The run cannot be measured here (no chip, too few, unknown kind)."""


def load_module(path: pathlib.Path, name: str):
    """Import the Python file ``path`` as module ``name``."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _safe(name: str) -> str:
    return "bench_" + "".join(c if c.isalnum() else "_" for c in name)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its pieces loaded."""

    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    model: Any            # bench/configs/<config>.py
    ref: Any              # bench/configs/<config>.ref.py
    traffic: dict         # bench/traffic/<traffic>.json
    end_to_end: list      # this cell's end-to-end metric entries
    per_layer: list       # this cell's per-layer metric entries


def benchmark() -> dict:
    """``BENCHMARK.json`` at the checkout root."""
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str, e2e_names: set | None) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    key lists; without one, every cell (an end-to-end metric, where
    ``e2e_names`` is None) or every cell that reports the end-to-end
    metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(name: str, traffic_override: dict | None = None,
              config_override: dict | None = None) -> Cell:
    """The cell ``name`` with its configuration, reference, traffic mix and
    the metrics it reports; the overrides replace keys of the mix and of
    the configuration (the rate sweep and the tests use them)."""
    spec = benchmark()
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    configs = {c["name"]: c for c in spec["configs"]}
    conf_entry = configs[w["config"]]
    config = json.loads((ROOT / conf_entry["file"]).read_text())
    config.update(config_override or {})
    base = BENCH / "configs" / w["config"]
    model = load_module(base.with_suffix(".py"), _safe(w["config"]))
    ref = load_module(base.parent / (w["config"] + ".ref.py"),
                      _safe(w["config"] + ".ref"))
    traffic = json.loads(
        (BENCH / "traffic" / (w["traffic"] + ".json")).read_text())
    traffic.update(traffic_override or {})
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, None)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(name, w["chips"], config, model, ref, traffic, e2e, layer)


def make_driver(cell: Cell, seed: int, seconds: float):
    """The driver ``bench/drivers/<driver>.py`` that the cell's traffic
    mix names, made for ``seed`` and windows of ``seconds``."""
    name = cell.traffic["driver"]
    module = load_module(BENCH / "drivers" / f"{name}.py",
                         _safe("driver." + name))
    return module.Driver(cell, seed, seconds)


def sub_seeds(seed: int, count: int) -> list[int]:
    """``count`` independent 31-bit seeds drawn from the run's ``--seed``
    (any whole number, also past 32 bits)."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    words = np.random.SeedSequence(seed).generate_state(count, np.uint32)
    return [int(w) >> 1 for w in words]


def peaks(kind: str) -> dict:
    """The published peaks of one chip of ``device_kind`` ``kind``."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Refusal(f"device kind {kind!r} is not in bench/peaks.json "
                      f"(have {sorted(table)})")
    return table[kind]


def check_devices(chips: int):
    """The first ``chips`` TPU devices; raises ``Refusal`` on any other
    platform or with fewer chips."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise Refusal(f"no TPU: JAX platform is {platform!r}")
    if len(devices) < chips:
        raise Refusal(f"{chips} chips asked for, {len(devices)} present")
    return devices[:chips]


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def limits_check(values: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: every number the cell's
    mix gives a limit at or under it.  A limit on a number the comparison
    did not compute is an error; numbers without a limit in this mix are
    not compared."""
    missing = set(limits) - set(values)
    if missing:
        raise KeyError(f"limits on numbers not computed: {sorted(missing)}")
    checks, ok = {}, True
    for name in sorted(limits):
        v, lim = float(values[name]), float(limits[name])
        ok = ok and math.isfinite(v) and v <= lim
        checks[name] = {"value": v if math.isfinite(v) else 1e300,
                        "limit": lim}
    return ok, checks


def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers as the last lines of standard error and
    the result as the last line of standard output, ``checks`` last."""
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps({**result, "checks": checks}), flush=True)
