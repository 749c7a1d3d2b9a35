"""Property tests for the perf-loop additions: row-wise int8 quantization
(sharding-preserving optimizer state) and the TPU-faithful HLO collective
accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.launch import hlo_analysis
from repro.parallel.compression import (dequantize_int8_rowwise,
                                        quantize_int8_rowwise)


# --------------------------------------------------------------------------- #
# row-wise int8
# --------------------------------------------------------------------------- #
@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(1, 6), min_size=1, max_size=3),
       st.integers(1, 257), st.integers(0, 2 ** 31 - 1))
@pytest.mark.slow
def test_rowwise_int8_shapes_and_error_bound(lead, last, seed):
    """q keeps x's shape; scale drops the last dim; |x - deq| <= scale/2
    per row (symmetric rounding bound)."""
    shape = tuple(lead) + (last,)
    x = np.asarray(jax.random.normal(jax.random.key(seed), shape,
                                     jnp.float32)) * 3.0
    q, s = quantize_int8_rowwise(jnp.asarray(x))
    assert q.shape == x.shape and q.dtype == jnp.int8
    assert s.shape == x.shape[:-1]
    deq = np.asarray(dequantize_int8_rowwise(q, s))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-7
    assert (np.abs(deq - x) <= bound + 1e-6).all()


def test_rowwise_int8_zero_and_extremes():
    z = jnp.zeros((4, 8))
    q, s = quantize_int8_rowwise(z)
    assert np.asarray(q).max() == 0
    np.testing.assert_allclose(np.asarray(dequantize_int8_rowwise(q, s)),
                               0.0)
    # max magnitude maps to +-127 exactly
    x = jnp.asarray([[1.0, -2.0, 0.5, 2.0]])
    q, s = quantize_int8_rowwise(x)
    assert int(np.abs(np.asarray(q)).max()) == 127


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(st.integers(2, 64), st.integers(0, 2 ** 31 - 1))
def test_rowwise_int8_scale_invariance(n, seed):
    """Quantization commutes with positive per-tensor scaling."""
    x = np.asarray(jax.random.normal(jax.random.key(seed), (3, n)))
    q1, _ = quantize_int8_rowwise(jnp.asarray(x))
    q2, _ = quantize_int8_rowwise(jnp.asarray(x * 7.25))
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


# --------------------------------------------------------------------------- #
# HLO collective accounting
# --------------------------------------------------------------------------- #
def _entry(body: str) -> str:
    return ("ENTRY %main (p0: f32[8]) -> f32[8] {\n" + body +
            "\n}\n")


def test_ring_model_factors():
    """all-gather (n-1)/n, all-reduce 2(n-1)/n, reduce-scatter result*(n-1),
    permute 1x — on synthetic single-op modules."""
    cases = [
        ("%ag = f32[64,4]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, "
         "dimensions={0}", "all-gather", 64 * 4 * 4 * 3 / 4),
        ("%ar = f32[64,4]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, "
         "to_apply=%add", "all-reduce", 64 * 4 * 4 * 2 * 3 / 4),
        ("%rs = f32[16,4]{1,0} reduce-scatter(%x), "
         "replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add",
         "reduce-scatter", 16 * 4 * 4 * 3),
        ("%cp = f32[64,4]{1,0} collective-permute(%x), "
         "source_target_pairs={{0,1},{1,0}}", "collective-permute",
         64 * 4 * 4),
    ]
    for line, op, want in cases:
        out = hlo_analysis.analyze(_entry("  " + line))
        assert abs(out["coll"][op] - want) < 1e-6, (op, out["coll"], want)


def test_promoted_and_convert_fed_counted_bf16():
    """CPU-widened collectives count at bf16 (half) width."""
    promoted = ("  %ar = f32[64]{0} all-reduce(%x), "
                "replica_groups={{0,1}}, to_apply=%add.clone_promoted")
    out = hlo_analysis.analyze(_entry(promoted))
    assert abs(out["coll"]["all-reduce"] - 64 * 4 * 2 * 0.5 / 2) < 1e-6
    conv = ("  %ag = f32[64]{0} all-gather(%wrapped_convert.3), "
            "replica_groups={{0,1}}, dimensions={0}")
    out = hlo_analysis.analyze(_entry(conv))
    assert abs(out["coll"]["all-gather"] - 64 * 4 * 0.5 * 0.5) < 1e-6
    # genuine f32 (non-convert operand) is NOT halved
    raw = ("  %ag2 = f32[64]{0} all-gather(%x), "
           "replica_groups={{0,1}}, dimensions={0}")
    out = hlo_analysis.analyze(_entry(raw))
    assert abs(out["coll"]["all-gather"] - 64 * 4 * 0.5) < 1e-6


def test_trip_count_weighting():
    """Collectives inside a while body multiply by the trip count."""
    hlo = """
%cond (c: (s32[], f32[8])) -> pred[] {
  %c = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %k = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %k), direction=LT
}
%body (b: (s32[], f32[8])) -> (s32[], f32[8]) {
  %b = (s32[], f32[8]) parameter(0)
  %v = f32[8]{0} get-tuple-element(%b), index=1
  %ar = f32[8]{0} all-reduce(%v), replica_groups={{0,1}}, to_apply=%add
  %i2 = s32[] get-tuple-element(%b), index=0
  ROOT %t = (s32[], f32[8]) tuple(%i2, %ar)
}
ENTRY %main (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  ROOT %w = (s32[], f32[8]) while(%p), condition=%cond, body=%body
}
"""
    out = hlo_analysis.analyze(hlo)
    assert out["trip_counts"] == [12]
    assert abs(out["coll"]["all-reduce"] - 12 * 8 * 4 * 2 * 0.5) < 1e-6


def test_opt_state_specs_rowwise_layout():
    """int8 moment specs mirror the parameter sharding (q exact, s
    truncated) — the fix that removed 2 TB/step of resharding."""
    import dataclasses
    from jax.sharding import PartitionSpec as P
    from repro.configs import ARCHS, tiny_config
    from repro.launch.mesh import ctx_for_mesh, make_mesh
    from repro.optim import adamw
    from repro.train import steps as steps_mod

    cfg = tiny_config(ARCHS["llama4-scout-17b-a16e"])
    opt_cfg = adamw.OptConfig(int8_moments=True)
    state = steps_mod.abstract_state(cfg, opt_cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = ctx_for_mesh(mesh)
    specs = steps_mod.state_specs(state, ctx)
    flat_p = jax.tree_util.tree_leaves_with_path(state["params"])
    flat_m = dict(jax.tree_util.tree_leaves_with_path(state["opt"]["m"]))
    flat_ms = dict(jax.tree_util.tree_leaves_with_path(specs["opt"]["m"],
                   is_leaf=lambda x: isinstance(x, P)))
    checked = 0
    for path, leaf in flat_p:
        qpath = tuple(path) + (jax.tree_util.DictKey("q"),)
        spath = tuple(path) + (jax.tree_util.DictKey("s"),)
        if qpath in flat_m:
            assert flat_m[qpath].shape == leaf.shape          # q mirrors p
            assert flat_m[spath].shape == leaf.shape[:-1]     # s drops last
            assert len(flat_ms[qpath]) <= leaf.ndim
            checked += 1
    assert checked > 5
