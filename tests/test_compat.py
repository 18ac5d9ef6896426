"""Direct unit tests for the repro.compat shims.

Each shim owns one jax API whose spelling drifted across releases; these
tests pin the shim's *behavior* (return shapes and types) rather than the
jax version, so the next drift must preserve the contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat


# --------------------------------------------------------------------------- #
# tree_leaves_with_path
# --------------------------------------------------------------------------- #
def test_tree_leaves_with_path_pairs_and_order():
    tree = {"b": jnp.zeros(2), "a": {"x": jnp.ones(3)}}
    pairs = compat.tree_leaves_with_path(tree)
    assert len(pairs) == 2
    # (key_path, leaf) pairs in canonical (sorted-key) flatten order.
    paths = [jax.tree_util.keystr(p) for p, _ in pairs]
    assert paths == ["['a']['x']", "['b']"]
    assert pairs[0][1].shape == (3,)
    assert pairs[1][1].shape == (2,)


def test_tree_leaves_with_path_matches_tree_util_reference():
    tree = {"w": jnp.arange(4.0), "nested": [jnp.zeros(1), jnp.ones(2)]}
    got = compat.tree_leaves_with_path(tree)
    ref = jax.tree_util.tree_leaves_with_path(tree)
    assert [jax.tree_util.keystr(p) for p, _ in got] \
        == [jax.tree_util.keystr(p) for p, _ in ref]
    for (_, a), (_, b) in zip(got, ref):
        np.testing.assert_array_equal(a, b)


def test_tree_leaves_with_path_respects_is_leaf():
    marker = object()

    class Spec:
        pass

    tree = {"a": {"s": Spec()}, "b": Spec()}
    pairs = compat.tree_leaves_with_path(
        tree, is_leaf=lambda x: isinstance(x, Spec))
    assert len(pairs) == 2
    assert all(isinstance(leaf, Spec) for _, leaf in pairs)
    del marker


# --------------------------------------------------------------------------- #
# shard_map
# --------------------------------------------------------------------------- #
def test_shard_map_single_device_identity():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    f = compat.shard_map(lambda x: x * 2.0, mesh=mesh,
                         in_specs=P("d"), out_specs=P("d"))
    x = jnp.arange(4.0)
    np.testing.assert_array_equal(f(x), x * 2.0)


def test_shard_map_is_jittable():
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    f = jax.jit(compat.shard_map(lambda x: x.sum(keepdims=True), mesh=mesh,
                                 in_specs=P("d"), out_specs=P("d")))
    assert float(f(jnp.ones(8))[0]) == 8.0


# --------------------------------------------------------------------------- #
# cost_analysis_dict
# --------------------------------------------------------------------------- #
class _FakeCompiled:
    def __init__(self, ret):
        self._ret = ret

    def cost_analysis(self):
        return self._ret


@pytest.mark.parametrize("ret,expect", [
    ({"flops": 8.0}, {"flops": 8.0}),            # dict-returning jax
    ({}, {}),                                    # empty analysis
    (None, {}),                                  # missing analysis
])
def test_cost_analysis_dict_normalizes_both_shapes(ret, expect):
    assert compat.cost_analysis_dict(_FakeCompiled(ret)) == expect


def test_cost_analysis_dict_on_real_compiled():
    compiled = jax.jit(lambda x: (x * x).sum()).lower(jnp.ones(16)).compile()
    ca = compat.cost_analysis_dict(compiled)
    assert isinstance(ca, dict)
    # CPU/TPU backends both report flops for a mul+reduce.
    if ca:
        assert all(isinstance(k, str) for k in ca)
