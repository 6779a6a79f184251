"""Roofline machinery: HLO collective parsing, extrapolation, and the
sharding rules' divisibility (pure math, no devices needed)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch import roofline as RL
from repro.launch.sharding import param_spec
from repro.models.lm import model as M

HLO_SAMPLE = """
  %ag = bf16[8,512,128]{2,1,0} all-gather(%p0), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={1}
  %ar.1 = f32[1024]{0} all-reduce(%x), replica_groups=[32,16]<=[512]T(1,0), to_apply=%add
  %rs = f32[64]{0} reduce-scatter(%y), replica_groups={{0,1}}, dimensions={0}
  %cp = bf16[256,64]{1,0} collective-permute(%z), source_target_pairs={{0,1},{1,0}}
  %a2a-start = (bf16[16,32]{1,0}, bf16[16,32]{1,0}) all-to-all-start(%w), replica_groups={{0,1,2,3}}
"""


def test_collective_parser_kinds_and_ring_model():
    out = RL.collective_link_bytes(HLO_SAMPLE, world=512)
    counts = out.pop("_counts")
    assert counts == {"all-gather": 1, "all-reduce": 1, "reduce-scatter": 1,
                      "collective-permute": 1, "all-to-all": 1}
    # all-gather: 8·512·128·2 bytes × 3/4
    assert abs(out["all-gather"] - 8 * 512 * 128 * 2 * 0.75) < 1
    # all-reduce group size 16 (iota [32,16]): 2·(15/16)·4096
    assert abs(out["all-reduce"] - 2 * 1024 * 4 * 15 / 16) < 1
    # reduce-scatter: out 64 f32, g=2 → 256·1
    assert abs(out["reduce-scatter"] - 64 * 4 * 1) < 1
    # permute: full payload
    assert abs(out["collective-permute"] - 256 * 64 * 2) < 1


def test_extrapolation_exact_for_linear():
    # f(k) = a + b·k with a=7, b=3 → total at k=10
    f1, f2 = 7 + 3 * 1, 7 + 3 * 2
    assert RL.extrapolate(f1, f2, 10) == 7 + 3 * 10


def test_terms_pick_dominant():
    c = RL.CellAnalysis(flops=197e12, bytes_accessed=819e9 * 3,
                        coll_bytes=50e9, coll_by_kind={},
                        flops_raw_full=0, peak_memory=0, argument_bytes=0,
                        temp_bytes=0, compile_seconds=0)
    t = c.terms()
    assert t["dominant"] == "memory"
    assert abs(t["memory_s"] - 3.0) < 1e-6
    assert abs(t["step_lower_bound_s"] - 3.0) < 1e-6


@pytest.mark.parametrize("arch", list_archs())
def test_param_spec_divisibility_on_production_mesh(arch):
    """Every sharded param dim must divide the production-mesh axis extent
    (after the fit_spec fallback this is guaranteed; here we verify the
    RAW rules rarely need the fallback — i.e. the sharding plan is real)."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    sizes = {"pod": 2, "data": 16, "model": 16}
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    violations = []
    total = 0
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        spec = param_spec(name, leaf.shape)
        for dim, entry in zip(leaf.shape,
                              tuple(spec) + (None,) * len(leaf.shape)):
            if entry is None:
                continue
            total += 1
            axes = entry if isinstance(entry, tuple) else (entry,)
            extent = 1
            for a in axes:
                extent *= sizes[a]
            if dim % extent:
                violations.append((name, leaf.shape, spec))
    # mamba2's vocab 50280 is the single known fallback case
    assert len(violations) <= 2, violations[:5]


def test_model_flops_moe_counts_active_only():
    dense = get_config("stablelm-3b")
    moe = get_config("moonlight-16b-a3b")
    info = {"batch": 8, "seq": 128, "kind": "train"}
    f_dense = RL.model_flops(dense, info)
    a_moe = RL.active_params(moe)
    # moonlight: 16B total, ~3B active
    import jax as _jax
    params = _jax.eval_shape(lambda: M.init_params(_jax.random.key(0), moe))
    total = sum(x.size for x in _jax.tree_util.tree_leaves(params))
    assert a_moe < 0.45 * total
    assert f_dense > 0
