"""Tests for the HLO collective parser, roofline math, and sharding rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis.hlo import count_op, loop_data_movement, parse_collectives
from repro.launch import sharding as shd

HLO_SAMPLE = """
HloModule jit_f

ENTRY %main {
  %param = f32[16,256]{1,0} parameter(0)
  %param.1 = f32[32,256]{1,0} parameter(1)
  %all-gather = f32[256,128]{1,0} all-gather(%param), channel_id=1, replica_groups=[4,2]<=[8], dimensions={0}
  %all-reduce = f32[16,256]{1,0} all-reduce(%param), channel_id=2, replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %reduce-scatter = f32[4,256]{1,0} reduce-scatter(%param.1), channel_id=3, replica_groups=[1,8]<=[8], dimensions={0}
  %all-to-all = f32[32,256]{1,0} all-to-all(%param.1), channel_id=4, replica_groups=[2,4]<=[8]
  %collective-permute = f32[16,256]{1,0} collective-permute(%param), channel_id=5, source_target_pairs={{0,1}}
  ROOT %t = (f32[256,128]{1,0}) tuple(%all-gather)
}
"""


def test_parse_collectives_kinds_and_groups():
    stats = parse_collectives(HLO_SAMPLE, num_devices=8)
    kinds = {o.kind: o for o in stats.ops}
    assert set(kinds) == {"all-gather", "all-reduce", "reduce-scatter",
                          "all-to-all", "collective-permute"}
    assert kinds["all-gather"].group_size == 2         # [4,2]<=[8]
    assert kinds["all-reduce"].group_size == 4         # explicit {{0..3}}
    assert kinds["reduce-scatter"].group_size == 8


def test_wire_byte_formulas():
    stats = parse_collectives(HLO_SAMPLE, num_devices=8)
    by = {o.kind: o for o in stats.ops}
    b16 = 16 * 256 * 4
    b32 = 32 * 256 * 4
    bag = 256 * 128 * 4
    assert np.isclose(by["all-reduce"].wire_bytes, 2 * b16 * 3 / 4)
    assert np.isclose(by["all-gather"].wire_bytes, bag * 1 / 2)
    assert np.isclose(by["reduce-scatter"].wire_bytes, b32 * 7 / 8)
    assert np.isclose(by["all-to-all"].wire_bytes, b32 * 3 / 4)
    assert np.isclose(by["collective-permute"].wire_bytes, b16)


def test_async_start_counted_once():
    txt = """
  %ag-start = (f32[16,8]{1,0}, f32[64,8]{1,0}) all-gather-start(%p), replica_groups=[1,4]<=[4], dimensions={0}
  %ag-done = f32[64,8]{1,0} all-gather-done(%ag-start)
  %p = f32[16,8]{1,0} parameter(0)
"""
    stats = parse_collectives(txt, num_devices=4)
    assert len(stats.ops) == 1
    # start result tuple minus operand -> gathered bytes
    assert stats.ops[0].result_bytes == 64 * 8 * 4


def test_count_op():
    assert count_op(HLO_SAMPLE, "parameter") == 2
    assert count_op(HLO_SAMPLE, "all-gather") == 1


HLO_LOOP = """
HloModule jit_chunk

%fused_dus (param_0: f32[8,64], param_1: f32[8,16], param_2: s32[]) -> f32[8,64] {
  %param_0 = f32[8,64]{1,0} parameter(0)
  %param_1 = f32[8,16]{1,0} parameter(1)
  %bitcast = f32[8,16]{1,0} bitcast(%param_1)
  %param_2 = s32[] parameter(2)
  %constant = s32[] constant(0)
  ROOT %dynamic-update-slice = f32[8,64]{1,0} dynamic-update-slice(%param_0, %bitcast, %constant, %param_2)
}

%fused_mul (param_0.1: f32[8,64]) -> f32[8,64] {
  %param_0.1 = f32[8,64]{1,0} parameter(0)
  ROOT %multiply = f32[8,64]{1,0} multiply(%param_0.1, %param_0.1)
}

%fused_bcast (param_0.2: f32[64]) -> f32[8,64] {
  %param_0.2 = f32[64]{0} parameter(0)
  ROOT %broadcast = f32[8,64]{1,0} broadcast(%param_0.2), dimensions={1}
}

%body (p: (f32[8,64], f32[64])) -> (f32[8,64], f32[64]) {
  %p = (f32[8,64]{1,0}, f32[64]{0}) parameter(0)
  %x = f32[8,64]{1,0} get-tuple-element(%p), index=0
  %r = f32[64]{0} get-tuple-element(%p), index=1
  %copy.1 = f32[8,64]{0,1} copy(%x)
  %slice.2 = f32[8,16]{1,0} slice(%x), slice={[0:8], [0:16]}
  %i = s32[] constant(1)
  %fusion.3 = f32[8,64]{1,0} fusion(%x, %slice.2, %i), kind=kLoop, calls=%fused_dus
  %fusion.4 = f32[8,64]{1,0} fusion(%fusion.3), kind=kLoop, calls=%fused_mul
  %fusion.5 = f32[8,64]{1,0} fusion(%r), kind=kLoop, calls=%fused_bcast
  ROOT %t = (f32[8,64]{1,0}, f32[64]{0}) tuple(%fusion.4, %r)
}

%cond (p.1: (f32[8,64], f32[64])) -> pred[] {
  %p.1 = (f32[8,64]{1,0}, f32[64]{0}) parameter(0)
  ROOT %c = pred[] constant(true)
}

ENTRY %main (a: f32[8,64], b: f32[64]) -> f32[8,64] {
  %a = f32[8,64]{1,0} parameter(0)
  %b = f32[64]{0} parameter(1)
  %copy.9 = f32[8,64]{0,1} copy(%a)
  %init = (f32[8,64]{1,0}, f32[64]{0}) tuple(%a, %b)
  %w = (f32[8,64]{1,0}, f32[64]{0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,64]{1,0} get-tuple-element(%w), index=0
}
"""


def test_loop_data_movement_sees_fusions_that_only_move_data():
    """Inside the loop body: the bare copy and slice, and the fusion of a
    bitcast and a dynamic-update-slice; not the arithmetic fusion, not
    the broadcast fusion (it writes new values), and not the copy
    outside the loop."""
    found = {(name, op): nbytes
             for _, name, op, nbytes in loop_data_movement(HLO_LOOP)}
    assert found == {("copy.1", "copy"): 8 * 64 * 4,
                     ("slice.2", "slice"): 8 * 16 * 4,
                     ("fusion.3", "fusion"): 8 * 64 * 4}


# ---------------------------------------------------------------------------
# sharding rules (AbstractMesh: no devices needed)
# ---------------------------------------------------------------------------

def _mesh(multi=False):
    from repro.compat import abstract_mesh
    shape = (2, 16, 16) if multi else (16, 16)
    axes = ("pod", "data", "model") if multi else ("data", "model")
    return abstract_mesh(shape, axes)


def test_param_specs_basic():
    mesh = _mesh()
    axes = shd.default_axes_map(False)
    params = {
        "embed": jax.ShapeDtypeStruct((128256, 4096), jnp.bfloat16),
        "lm_head": jax.ShapeDtypeStruct((4096, 128256), jnp.bfloat16),
        "blocks": {
            "attn": {"w_q": jax.ShapeDtypeStruct((32, 4096, 4096),
                                                 jnp.bfloat16)},
            "norm_mix": {"scale": jax.ShapeDtypeStruct((32, 4096),
                                                       jnp.bfloat16)},
            "moe": {"w_gate": jax.ShapeDtypeStruct((32, 8, 4096, 1024),
                                                   jnp.bfloat16)},
        },
    }
    specs = shd.param_spec_tree(params, mesh, axes)
    assert specs["embed"] == P("model", "data")
    assert specs["lm_head"] == P("data", "model")
    assert specs["blocks"]["attn"]["w_q"] == P(None, "data", "model")
    assert specs["blocks"]["norm_mix"]["scale"] == P()
    assert specs["blocks"]["moe"]["w_gate"] == P(None, None, "data", "model")


def test_divisibility_guard_drops_axis():
    mesh = _mesh()
    axes = shd.default_axes_map(False)
    params = {"embed": jax.ShapeDtypeStruct((50280, 2560), jnp.float32)}
    specs = shd.param_spec_tree(params, mesh, axes)
    # 50280 % 16 != 0 -> vocab axis dropped; 2560 % 16 == 0 -> kept
    assert specs["embed"] == P(None, "data")


def test_learner_axis_sharding():
    mesh = _mesh(multi=True)
    axes = shd.default_axes_map(True)
    params = {"blocks": {"ffn": {
        "w_gate": jax.ShapeDtypeStruct((2, 32, 4096, 14336), jnp.bfloat16)}}}
    specs = shd.param_spec_tree(params, mesh, axes, learner_axis=True)
    assert specs["blocks"]["ffn"]["w_gate"] == P("pod", None, "data", "model")


def test_batch_specs():
    mesh = _mesh(multi=True)
    axes = shd.default_axes_map(True)
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    specs = shd.batch_spec_tree(batch, mesh, axes)
    assert specs["tokens"] == P(("pod", "data"))
    # batch=1 (long_500k): axis dropped
    b1 = {"tokens": jax.ShapeDtypeStruct((1,), jnp.int32)}
    assert shd.batch_spec_tree(b1, mesh, axes)["tokens"] == P()


def test_cache_specs():
    mesh = _mesh()
    axes = shd.default_axes_map(False)
    cache = {"k": jax.ShapeDtypeStruct((32, 128, 32768, 8, 128),
                                       jnp.bfloat16),
             "ssm": jax.ShapeDtypeStruct((64, 128, 80, 64, 128),
                                         jnp.float32)}
    specs = shd.cache_spec_tree(cache, mesh, axes)
    assert specs["k"] == P(None, "data", "model")          # B, S sharded
    assert specs["ssm"] == P(None, "data", "model")        # B, H sharded


def test_roofline_model_flops():
    from repro.analysis.roofline import model_flops_for
    from repro.config import INPUT_SHAPES, get_arch
    cfg = get_arch("llama3-8b")
    f = model_flops_for(cfg, INPUT_SHAPES["train_4k"], "train")
    tokens = 256 * 4096
    assert np.isclose(f, 6.0 * cfg.active_param_count() * tokens)
    # MoE uses active params only
    moe = get_arch("mixtral-8x22b")
    fm = model_flops_for(moe, INPUT_SHAPES["train_4k"], "train")
    assert fm < 6.0 * moe.param_count() * tokens


def test_chip_peaks_keyed_by_device_kind():
    """The roofline's peaks come from one table keyed by device_kind; a
    kind missing from it is an error, not a v5e default."""
    from repro.analysis.roofline import chip_peaks
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="TPU v4"):
        chip_peaks("TPU v4")
