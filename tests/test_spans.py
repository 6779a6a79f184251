"""Spans of the request plane and named scopes of the SIR step.

Contracts pinned here (DESIGN.md §15.4):

* ``Metrics.span`` sums a block's seconds into the counter ``ppf.<name>``
  and opens the profiler event of that name with its args as stats;
* a profiled frontend puts every ``ppf.serve.*`` span on its loop thread
  and every ``ppf.bank.*`` span on its step executor, joined by the
  ``step`` number, and each ``ppf.bank.dispatch`` starts before the
  device operations of the program it launches (one clock);
* ``queue_wait_s`` counts each frame from ``submit`` to its step;
* the SIR step, the fused step, the distributed step and the tier
  program name their phases with ``jax.named_scope``, and the scopes
  change nothing in the compiled program but its ``op_name`` metadata.
"""
import asyncio
import collections
import contextlib
import glob
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DRAConfig, SIRConfig, filters, particles, smc
from repro.serve import (FrontendConfig, Metrics, ParticleFrontend,
                         ParticleSessionServer)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "golden"))
sys.path.insert(0, os.path.join(REPO, "tests"))
try:
    import emesh
    from generate_session import lg_model
finally:
    del sys.path[:2]

LOOP_SPANS = {"ppf.serve.idle", "ppf.serve.hold", "ppf.serve.slots",
              "ppf.serve.step", "ppf.serve.deliver"}
BANK_SPANS = {"ppf.bank.step", "ppf.bank.stage", "ppf.bank.dispatch",
              "ppf.bank.read"}


def make_server(capacity=2, n=64):
    return ParticleSessionServer(
        model=lg_model(), sir=SIRConfig(n_particles=n, ess_frac=0.5),
        capacity=capacity)


# ---------------------------------------------------------------------------
# Metrics.span and the server's phases
# ---------------------------------------------------------------------------

def test_span_sums_seconds_into_the_counter_of_its_name():
    """Seconds add up under ``ppf.<name>``, also for a block that
    raises; the annotation takes stats known only inside the block."""
    m = Metrics()
    with m.span("serve.step", step=1) as annotation:
        time.sleep(0.02)
        annotation.set_metadata(sessions=2)
    with pytest.raises(RuntimeError):
        with m.span("serve.step", step=2):
            time.sleep(0.01)
            raise RuntimeError("step failed")
    counters = m.snapshot()["counters"]
    assert set(counters) == {"ppf.serve.step"}
    assert 0.03 <= counters["ppf.serve.step"] < 1.0
    assert m.snapshot()["series"] == {}


def test_server_step_brackets_stage_then_dispatch():
    """``step(span=...)`` opens ``bank.stage`` and then ``bank.dispatch``
    with the tier it runs; with nothing pending it stages and stops."""
    srv = make_server(capacity=4)
    handles = [srv.attach(jax.random.key(i)) for i in range(3)]
    calls = []

    @contextlib.contextmanager
    def span(name, **args):
        calls.append(("open", name, args))
        yield
        calls.append(("close", name))

    for h in handles:
        srv.submit(h, np.float32(0.1))
    assert srv.tier_for(3) == 4 and srv.tier_for(1) == 1
    assert srv.step(span=span) == 3
    assert calls == [("open", "bank.stage", {}), ("close", "bank.stage"),
                     ("open", "bank.dispatch", {"tier": 4}),
                     ("close", "bank.dispatch")]
    calls.clear()
    assert srv.step(span=span) == 0
    assert calls == [("open", "bank.stage", {}), ("close", "bank.stage")]
    assert srv.tier_hits == {1: 0, 2: 0, 4: 1}


def test_queue_wait_counts_each_frame_from_submit_to_its_step():
    """A lone frame on a two-stream plane waits for the deadline
    trigger: the wait is counted under ``queue_wait_s`` (and the loop's
    time under ``ppf.serve.hold``), never more than the frame's latency."""
    delay = 0.05

    async def main():
        async with ParticleFrontend(make_server(),
                                    FrontendConfig(max_delay=delay)) as fe:
            await fe.warmup(np.float32(0.0))
            a = await fe.open(jax.random.key(1))
            await fe.open(jax.random.key(2))
            await asyncio.sleep(0.01)              # both attached, idle
            res = await (await fe.submit(a, np.float32(0.3)))
            return res, fe.snapshot()["counters"]

    res, c = asyncio.run(main())
    assert c["frames"] == 1
    assert 0.9 * delay <= c["queue_wait_s"] <= res.latency
    assert c["ppf.serve.hold"] >= 0.8 * delay
    assert c["ppf.serve.idle"] > 0
    for name in LOOP_SPANS | BANK_SPANS:
        assert c[name] > 0, name
    assert c["ppf.bank.step"] <= c["ppf.serve.step"]


# ---------------------------------------------------------------------------
# The spans in a profiler trace
# ---------------------------------------------------------------------------

def _trace_events(trace_dir):
    """``(line, name, start_ns, end_ns, stats)`` of every event of the
    newest trace under ``trace_dir``."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    data = jax.profiler.ProfileData.from_file(path)
    return [(f"{plane.name}|{i}|{line.name}", e.name, e.start_ns, e.end_ns,
             dict(e.stats))
            for plane in data.planes for i, line in enumerate(plane.lines)
            for e in line.events]


def test_profiled_plane_spans_join_by_step_on_one_clock(tmp_path):
    """Under the profiler every loop and executor span appears under its
    plain name with its args as stats; the executor's spans of step n lie
    inside the loop's ``ppf.serve.step`` of step n; and each
    ``ppf.bank.dispatch`` starts before the operations of the tier
    program run it launches."""
    z = np.float32(0.4)

    async def main():
        async with ParticleFrontend(make_server(),
                                    FrontendConfig(max_delay=0.02)) as fe:
            await fe.warmup(z)
            a = await fe.open(jax.random.key(1))
            b = await fe.open(jax.random.key(2))
            await asyncio.sleep(0.01)
            jax.profiler.start_trace(str(tmp_path))
            try:
                await (await fe.submit(a, z))                 # hold, step 1
                await asyncio.sleep(0.01)                     # idle
                await asyncio.gather(*[await fe.submit(s, z)  # step 2
                                       for s in (a, b)])
                await asyncio.sleep(0.01)
                await (await fe.submit(b, z))                 # step 3
            finally:
                jax.profiler.stop_trace()

    asyncio.run(main())
    events = _trace_events(str(tmp_path))
    spans = [e for e in events if e[1].startswith("ppf.")]
    names = {e[1] for e in spans}
    assert names >= LOOP_SPANS | BANK_SPANS, names
    assert not any("#" in e[1] for e in spans)
    lines = {name: {e[0] for e in spans if e[1] == name} for name in names}
    loop_lines = set.union(*(lines[n] for n in LOOP_SPANS))
    bank_lines = set.union(*(lines[n] for n in BANK_SPANS))
    assert len(loop_lines) == 1 and len(bank_lines) == 1
    assert loop_lines != bank_lines                     # two threads
    for e in spans:
        if e[1] == "ppf.serve.slots":
            assert {"attached", "resumed", "parked"} <= set(e[4])
        if e[1] == "ppf.serve.hold":
            assert e[4]["pending"] >= 1

    by_step = collections.defaultdict(dict)
    for line, name, start, end, stats in spans:
        if "step" in stats:
            assert name not in by_step[stats["step"]], (name, stats)
            by_step[stats["step"]][name] = (start, end, stats)
    assert len(by_step) == 3
    for step, group in by_step.items():
        assert set(group) == {"ppf.serve.step", "ppf.serve.deliver"} \
            | BANK_SPANS, (step, set(group))
        lo, hi, stats = group["ppf.serve.step"]
        for name in BANK_SPANS:
            assert lo <= group[name][0] <= group[name][1] <= hi, name
        assert group["ppf.bank.step"][2]["sessions"] == stats["sessions"]
        assert group["ppf.bank.dispatch"][2]["tier"] == \
            group["ppf.bank.step"][2]["tier"]
    assert [by_step[s]["ppf.serve.step"][2]["sessions"]
            for s in sorted(by_step)] == [1, 2, 1]

    # op events of the tier programs, one group per program run
    runs = collections.defaultdict(list)
    for line, name, start, end, stats in events:
        if str(stats.get("hlo_module", "")).startswith("jit_tier_fn"):
            runs[stats.get("run_id")].append(start)
    firsts = sorted(min(v) for v in runs.values())
    dispatches = sorted(by_step[s]["ppf.bank.dispatch"][0] for s in by_step)
    assert len(firsts) == len(dispatches) == 3
    for d, first, step in zip(dispatches, firsts, sorted(by_step)):
        assert d <= first <= by_step[step]["ppf.bank.read"][1]


# ---------------------------------------------------------------------------
# Named scopes in the compiled step programs
# ---------------------------------------------------------------------------

SCOPE = re.compile(r"ppf\.[a-z]+(?:\.[a-z]+)?")


def _sir_step_program(backend: str, n: int = 64):
    model = lg_model()
    step = smc.make_sir_step(model, SIRConfig(n_particles=n,
                                              step_backend=backend))
    carry = smc.SIRCarry(jax.random.key(1), particles.init_ensemble(
        jax.random.key(0), model.init, n))
    return jax.jit(step).lower(carry, jnp.float32(0.3))


@pytest.mark.parametrize("backend,scopes", [
    ("composed", {"ppf.advance", "ppf.likelihood", "ppf.weights",
                  "ppf.resample", "ppf.gather"}),
    ("fused", {"ppf.advance", "ppf.likelihood", "ppf.weights",
               "ppf.gather"}),
])
def test_sir_step_names_its_phases(backend, scopes):
    """Each phase's operations carry its scope in their ``op_name``
    (the fused weight call resamples under ``ppf.weights``)."""
    lowered = _sir_step_program(backend)
    assert set(SCOPE.findall(lowered.as_text(debug_info=True))) == scopes
    compiled = lowered.compile().as_text()
    assert set(SCOPE.findall(compiled)) == scopes


def test_tier_program_names_gather_scatter_and_the_step():
    """The tier program gathers and scatters the carries under
    ``ppf.tier.*`` around the (vmapped) SIR step's scopes."""
    srv = make_server(capacity=2)
    lowered = srv._tier_fn.lower(
        srv._carry, jnp.arange(2, dtype=jnp.int32),
        jnp.zeros((2,), jnp.float32), jnp.zeros((2,), bool))
    found = set(SCOPE.findall(lowered.as_text(debug_info=True)))
    assert found == {"ppf.tier.gather", "ppf.tier.scatter", "ppf.advance",
                     "ppf.likelihood", "ppf.weights", "ppf.resample",
                     "ppf.gather"}


@pytest.mark.parametrize("kind", ["rna", "rpa", "butterfly"])
def test_distributed_step_names_its_exchange(kind):
    """On an emulated 2-shard mesh the DRA's collectives run under
    ``ppf.exchange`` inside its ``ppf.resample``."""
    model, n = lg_model(), 64
    step = smc.make_distributed_sir_step(model, SIRConfig(n_particles=n),
                                         DRAConfig(kind=kind), emesh.AXIS)

    def per_shard(i):
        del i
        carry = filters._shard_carry(jax.random.key(0), model, emesh.AXIS,
                                     n // 2, n)
        return step(carry, jnp.float32(0.3))

    text = jax.jit(jax.vmap(per_shard, axis_name=emesh.AXIS)).lower(
        jnp.arange(2)).as_text(debug_info=True)
    assert set(SCOPE.findall(text)) == {"ppf.advance", "ppf.likelihood",
                                        "ppf.weights", "ppf.resample",
                                        "ppf.exchange"}
    assert re.search(r"ppf\.resample\)?/[^\"]*ppf\.exchange", text)


def _program_body(text: str) -> str:
    """Compiled HLO text without its metadata and source tables."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return text[text.index("\n%"):]


@pytest.mark.parametrize("backend", ["composed", "fused"])
def test_scopes_change_nothing_but_op_names(backend, monkeypatch):
    """With ``jax.named_scope`` made a no-op the step compiles to the same
    program, instruction for instruction, once metadata is set aside."""
    scoped = _program_body(_sir_step_program(backend).compile().as_text())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _sir_step_program(backend).compile().as_text()
    assert "ppf." not in plain
    assert _program_body(plain) == scoped


def _decode_step_program():
    """The SMC decode step of the MLA + MoE smoke model, one prompt of K
    rows, as ``smc_decode`` scans it, jitted afresh."""
    from repro.configs import get_config
    from repro.models.lm import decode_ssm
    from repro.models.lm import model as M
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    params = M.init_params(jax.random.key(0), cfg)
    dcfg = decode_ssm.SMCDecodeConfig(n_particles=4, steps=4)
    model = decode_ssm.LMDecodeSSM(params=params, cfg=cfg, decode=dcfg,
                                   prompt_len=6)
    carry = decode_ssm.decode_carry(model, jax.random.key(1),
                                    jnp.arange(6) % cfg.vocab_size)[0]
    carry = jax.tree_util.tree_map(lambda x: x[None], carry)
    step = filters.make_bank_step(model, dcfg.sir())
    return jax.jit(step).lower(carry, (jnp.ones((1,), jnp.float32),
                                       jnp.ones((1,), bool)))


def test_decode_step_names_the_model_parts(monkeypatch):
    """The decode step carries the latent attention, the expert layer and
    the head under ``ppf.lm.*`` inside ``ppf.advance``, and compiles to the
    same program, instruction for instruction, without the scopes."""
    compiled = _decode_step_program().compile().as_text()
    found = set(SCOPE.findall(compiled))
    assert {"ppf.advance", "ppf.lm.mla", "ppf.lm.moe", "ppf.lm.head",
            "ppf.gather"} <= found, found
    assert re.search(r"ppf\.advance\)?/[^\"]*ppf\.lm\.moe", compiled)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _decode_step_program().compile().as_text()
    assert "ppf." not in plain
    assert _renamed(_program_body(plain)) == _renamed(_program_body(compiled))


def _renamed(body: str) -> str:
    """HLO text with its names numbered in order of first appearance: the
    scopes may shift the suffixes XLA gives an instruction's name."""
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  body)
