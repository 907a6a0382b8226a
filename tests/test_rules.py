"""Golden layout tests: the unified rule table (parallel/rules.py) must
reproduce every legacy layout bitwise — the hand-written gspmd
PartitionRules literals, serve/sharded.py's deleted span helpers, and the
ring chunk contract ZeRO/reshard shard by.  These pin the refactor: a rule
or layout-table edit that drifts any consumer's layout fails here."""

import numpy as np
import pytest

from tpu_dist.collectives.ring import _bounds as ring_bounds, ring_chunk_span
from tpu_dist.models import TransformerLM
from tpu_dist.parallel import rules as R
from tpu_dist.parallel.rules import (DEFAULT_RULES, SERVING_RULES,
                                     ShardLayoutError, chunk_bounds,
                                     chunk_span, model_axes, shard_leaf,
                                     spans_for, spec_for, spec_for_key)


def _lm(vocab=64, dim=32, depth=2, heads=4, seq=16, **kw):
    return TransformerLM(vocab_size=vocab, dim=dim, depth=depth,
                         num_heads=heads, max_seq_len=seq, **kw)


def _np_params(params):
    return {p: {n: np.asarray(a) for n, a in d.items()}
            for p, d in params.items()}


# ---------------------------------------------------------------------------
# pjit specs: generated pairs == the legacy hand-written literals
# ---------------------------------------------------------------------------

def _legacy_tp_rules():
    """The TRANSFORMER_TP_RULES literals as written before the rule table
    (gspmd.py at the PR-17 seed) — the golden reference."""
    from jax.sharding import PartitionSpec as P
    from tpu_dist.parallel.gspmd import PartitionRules
    return PartitionRules([
        (r"qkv_weight", P(None, "model")),
        (r"qkv_bias", P("model")),
        (r"out_weight", P("model", None)),
        (r"mlp\.0'\]\['weight", P(None, "model")),
        (r"mlp\.0'\]\['bias", P("model")),
        (r"mlp\.2'\]\['weight", P("model", None)),
        (r"\['head'\].*weight", P(None, "model")),
        (r"\['head'\].*bias", P("model")),
        (r"\['tok'\].*weight", P("model", None)),
    ])


def _legacy_moe_rules():
    from jax.sharding import PartitionSpec as P
    from tpu_dist.parallel.gspmd import PartitionRules
    return PartitionRules([(r"mlp'\]\['[wb][12]'\]", P("expert"))])


def _norm(spec):
    """Strip trailing Nones: P('model') and P('model', None) place leaves
    identically; only the normalized tuple is the layout contract."""
    t = tuple(spec)
    while t and t[-1] is None:
        t = t[:-1]
    return t


def _spec_trees_equal(a, b):
    import jax
    fa = jax.tree_util.tree_leaves_with_path(a)
    fb = jax.tree_util.tree_leaves_with_path(b)
    assert len(fa) == len(fb)
    for (pa, sa), (pb, sb) in zip(fa, fb):
        assert pa == pb
        assert _norm(sa) == _norm(sb), (jax.tree_util.keystr(pa), sa, sb)


def test_tp_specs_match_legacy_literals():
    import jax
    from tpu_dist.parallel.gspmd import TRANSFORMER_TP_RULES
    model = _lm()
    params = model.init(jax.random.PRNGKey(0))
    _spec_trees_equal(TRANSFORMER_TP_RULES.tree_specs(params),
                      _legacy_tp_rules().tree_specs(params))


def test_moe_specs_match_legacy_literals():
    import jax
    from tpu_dist.parallel.gspmd import MOE_EP_RULES
    model = _lm(dim=32, heads=4, num_experts=4)
    params = model.init(jax.random.PRNGKey(0))
    _spec_trees_equal(MOE_EP_RULES.tree_specs(params),
                      _legacy_moe_rules().tree_specs(params))


def test_spec_for_literals():
    from jax.sharding import PartitionSpec as P
    cases = [
        (("block0.attn", "qkv_weight"), (None, "model")),
        (("block0.attn", "qkv_bias"), ("model",)),
        (("block0.attn", "out_weight"), ("model",)),
        (("block0.attn", "out_bias"), ()),      # partial-sum bias: replicated
        (("block1.mlp.0", "weight"), (None, "model")),
        (("block1.mlp.0", "bias"), ("model",)),
        (("block1.mlp.2", "weight"), ("model",)),
        (("block1.mlp.2", "bias"), ()),
        (("head", "weight"), (None, "model")),
        (("head", "bias"), ("model",)),
        (("tok", "weight"), ("model",)),
        (("pos", "weight"), ()),
        (("block0.ln1", "weight"), ()),          # unmatched -> replicated
    ]
    for (path, name), want in cases:
        assert _norm(spec_for(path, name, DEFAULT_RULES)) == want, (path, name)
    assert spec_for_key("['block0.attn']['qkv_weight']") == P(None, "model")
    assert _norm(spec_for_key("not-a-keystr")) == ()


def test_row_parallel_biases_replicate_in_compiled_specs():
    """The compiled half of ``LeafLayout.partial_axis``: where the weight
    is cut along its rows (each device's matmul gives a partial sum), the
    bias that follows is whole on every device, so XLA adds it once, after
    the psum.  ``spans_for`` gives the same leaves to shard 0 alone."""
    import jax
    from jax.sharding import PartitionSpec as P
    from tpu_dist.parallel.gspmd import TRANSFORMER_TP_RULES
    model = _lm()
    params = model.init(jax.random.PRNGKey(0))
    specs = TRANSFORMER_TP_RULES.tree_specs(params)
    axes = model_axes(model)
    seen = set()
    for path, leaf in params.items():
        for name, arr in leaf.items():
            lay = R.layout_for(path, name)
            if lay is None or lay.partial_axis is None:
                continue
            seen.add((path, name))
            weight = name.replace("bias", "weight")
            assert DEFAULT_RULES[lay.partial_axis] == "model"
            assert _norm(specs[path][weight]) == ("model",), (path, weight)
            assert specs[path][name] == P(), (path, name)
            assert spec_for(path, name) == P(), (path, name)
            plans = [spans_for(path, name, arr.shape, axes, rank, 2)
                     for rank in range(2)]
            assert plans == [([(0, arr.size)], arr.shape), None]
    assert seen == {leaf for i in range(2) for leaf in
                    ((f"block{i}.attn", "out_bias"),
                     (f"block{i}.mlp.2", "bias"))}


def test_conflicting_dim_factors_raise():
    bad = dict(DEFAULT_RULES, qkv3="model", heads="model")
    # qkv3 and heads factor the SAME tensor dim of qkv_weight: one dim
    # cannot ride two (even identical) rule bindings through two factors
    with pytest.raises(ShardLayoutError):
        spans_for("block0.attn", "qkv_weight", (32, 96),
                  {"embed": 32, "qkv3": 3, "heads": 4, "head_dim": 8},
                  0, 2, rules=bad)


# ---------------------------------------------------------------------------
# serving spans: spans_for under SERVING_RULES == the deleted legacy helpers
# ---------------------------------------------------------------------------

def _legacy_leaf_tag(path, name):
    """serve/sharded.py's _leaf_tag as written before the rule table."""
    import re
    if re.match(r"^block(\d+)\.attn$", path):
        return {"qkv_weight": "qkv_w", "qkv_bias": "qkv_b",
                "out_weight": "head_rows", "out_bias": "bias0"}[name]
    if re.match(r"^block(\d+)\.mlp\.0$", path):
        return {"weight": "cols", "bias": "vec"}[name]
    if re.match(r"^block(\d+)\.mlp\.2$", path):
        return {"weight": "rows", "bias": "bias0"}[name]
    return "full"


def _legacy_leaf_spans(tag, shape, dims, rank, world):
    """serve/sharded.py's _leaf_spans, verbatim legacy span math."""
    H, hd = dims["num_heads"], dims["head_dim"]
    nl = H // world
    hidden = dims["hidden"]
    hl = hidden // world
    h0 = rank * nl
    c0 = rank * hl
    if tag == "full":
        return [(0, int(np.prod(shape, dtype=np.int64)))], shape
    if tag == "bias0":
        if rank != 0:
            return None
        return [(0, int(np.prod(shape, dtype=np.int64)))], shape
    if tag == "qkv_w":
        dim, three_dim = shape
        spans = []
        for i in range(dim):
            for c in range(3):
                base = i * three_dim + (c * H + h0) * hd
                spans.append((base, base + nl * hd))
        return spans, (dim, 3 * nl * hd)
    if tag == "qkv_b":
        spans = []
        for c in range(3):
            base = (c * H + h0) * hd
            spans.append((base, base + nl * hd))
        return spans, (3 * nl * hd,)
    if tag == "head_rows":
        rows, cols = shape
        return [(h0 * hd * cols, (h0 + nl) * hd * cols)], (nl * hd, cols)
    if tag == "rows":
        rows, cols = shape
        return [(c0 * cols, (c0 + hl) * cols)], (hl, cols)
    if tag == "cols":
        rows, cols = shape
        return ([(i * cols + c0, i * cols + c0 + hl) for i in range(rows)],
                (rows, hl))
    if tag == "vec":
        return [(c0, c0 + hl)], (hl,)
    raise AssertionError(tag)


def _merge_adjacent(spans):
    """Legacy qkv spans are per-(row, c) blocks even when world == 1 and
    adjacent blocks touch; the generalized formula emits the minimal
    per-outer-product span list.  Merge before comparing — the flat byte
    ranges, not the span partitioning, are the layout contract."""
    out = []
    for lo, hi in spans:
        if out and out[-1][1] == lo:
            out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return [tuple(s) for s in out]


@pytest.mark.parametrize("world", [1, 2, 4])
def test_serving_spans_match_legacy(world):
    import jax
    model = _lm()
    params = _np_params(model.init(jax.random.PRNGKey(0)))
    axes = model_axes(model)
    dims = {"num_heads": 4, "head_dim": 8, "hidden": 128}
    for rank in range(world):
        for path, leaf in params.items():
            for name, arr in leaf.items():
                legacy = _legacy_leaf_spans(
                    _legacy_leaf_tag(path, name), arr.shape, dims,
                    rank, world)
                plan = spans_for(path, name, arr.shape, axes, rank, world,
                                 rules=SERVING_RULES, mesh_axis="shard")
                key = (world, rank, path, name)
                if legacy is None:
                    assert plan is None, key
                    continue
                assert plan is not None, key
                assert _merge_adjacent(plan[0]) == \
                    _merge_adjacent(legacy[0]), key
                assert tuple(plan[1]) == tuple(legacy[1]), key
                # and the materialized shard is byte-identical
                want = np.concatenate(
                    [arr.reshape(-1)[lo:hi] for lo, hi in legacy[0]]
                ).reshape(legacy[1])
                np.testing.assert_array_equal(shard_leaf(arr, plan), want)


def test_spans_world1_are_identity():
    model = _lm()
    axes = model_axes(model)
    import jax
    params = _np_params(model.init(jax.random.PRNGKey(1)))
    for path, leaf in params.items():
        for name, arr in leaf.items():
            plan = spans_for(path, name, arr.shape, axes, 0, 1,
                             rules=DEFAULT_RULES, mesh_axis="model")
            np.testing.assert_array_equal(shard_leaf(arr, plan), arr)


def test_spans_indivisible_raises():
    model = _lm()
    with pytest.raises(ShardLayoutError):
        spans_for("block0.attn", "qkv_weight", (32, 96), model_axes(model),
                  0, 3, rules=DEFAULT_RULES, mesh_axis="model")


# ---------------------------------------------------------------------------
# flat chunk contract: ZeRO / reshard bounds ride ring._bounds unchanged
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,world", [(0, 4), (1, 4), (7, 3), (16, 4),
                                     (1000, 7), (4096, 8)])
def test_chunk_bounds_match_ring(n, world):
    assert chunk_bounds(n, world) == ring_bounds(n, world)
    for r in range(world):
        assert chunk_span(n, world, r) == ring_chunk_span(n, world, r)
    # contiguous full cover
    b = chunk_bounds(n, world)
    assert b[0][0] == 0 and b[-1][1] == n
    assert all(b[i][1] == b[i + 1][0] for i in range(world - 1))


def test_reshard_bounds_delegate_to_rules():
    from tpu_dist.resilience.reshard import _bounds as reshard_bounds
    for n, w in [(13, 4), (128, 3)]:
        assert reshard_bounds(n, w) == chunk_bounds(n, w)


# ---------------------------------------------------------------------------
# fsdp composition: rule table as the base placement for 2-D sharding
# ---------------------------------------------------------------------------

class _FakeMesh:
    shape = {"data": 2, "model": 2}


def test_fsdp_specs_compose_with_rule_table():
    import jax
    from tpu_dist.parallel.fsdp import fsdp_specs
    model = _lm()
    params = _np_params(model.init(jax.random.PRNGKey(0)))
    specs = fsdp_specs(params, _FakeMesh(), axis="data", min_size=1,
                       rules=DEFAULT_RULES)
    # column-parallel qkv keeps 'model' on dim 1 and gains 'data' on dim 0
    qkv = specs["block0.attn"]["qkv_weight"]
    assert tuple(qkv) == ("data", "model")
    # row-parallel down-projection: 'model' on dim 0, 'data' on dim 1
    down = specs["block0.mlp.2"]["weight"]
    assert tuple(down) == ("model", "data")
    # replicated-by-rules LayerNorm scale just gets the fsdp axis
    ln = specs["block0.ln1"]["weight"]
    assert "data" in tuple(ln)


def test_fsdp_specs_accept_partition_rules_object():
    import jax
    from tpu_dist.parallel.fsdp import fsdp_specs
    from tpu_dist.parallel.gspmd import TRANSFORMER_TP_RULES
    model = _lm()
    params = _np_params(model.init(jax.random.PRNGKey(0)))
    via_table = fsdp_specs(params, _FakeMesh(), axis="data", min_size=1,
                           rules=DEFAULT_RULES)
    via_rules = fsdp_specs(params, _FakeMesh(), axis="data", min_size=1,
                           rules=TRANSFORMER_TP_RULES)
    _spec_trees_equal(via_table, via_rules)


# ---------------------------------------------------------------------------
# rule-table surface
# ---------------------------------------------------------------------------

def test_mapped_axes():
    assert set(R.mapped_axes(DEFAULT_RULES, "model")) == \
        {"heads", "mlp", "vocab"}
    assert R.mapped_axes(DEFAULT_RULES, "data") == ("batch",)
    assert set(R.mapped_axes(SERVING_RULES, "shard")) == {"heads", "mlp"}


def test_model_axes_reads_model():
    model = _lm(vocab=64, dim=32, heads=4, seq=16)
    axes = model_axes(model)
    assert axes["embed"] == 32 and axes["heads"] == 4
    assert axes["head_dim"] == 8 and axes["mlp"] == 128
    assert axes["vocab"] == 64 and axes["seq"] == 16 and axes["qkv3"] == 3
