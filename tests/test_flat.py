"""The flat-parameter workspace (repro.core.flat) and everything wired to
it: pack/unpack round-trips, the flat server update vs the leafwise oracle,
the flat MGD optimizer path, the fused server kernels, and the dist layer's
flat gradient workspace.  (Hypothesis property tests for the pack/unpack
bit-exactness contract live in tests/test_property.py.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flat
from repro.kernels import ops, ref


def _mixed_tree(rng):
    return {
        "fc0": {"w": jnp.asarray(rng.normal(size=(17, 8)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(8,)), jnp.bfloat16)},
        "head": {"w": jnp.asarray(rng.normal(size=(8, 3)), jnp.float16),
                 "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32)},
    }


def test_spec_layout_static():
    rng = np.random.default_rng(0)
    tree = _mixed_tree(rng)
    spec = flat.make_flat_spec(tree)
    assert spec.size == 17 * 8 + 8 + 8 * 3 + 3
    assert spec.padded_size % flat.DEFAULT_PAD_TO == 0
    assert spec.padded_size >= spec.size
    assert spec.offsets[0] == 0
    # offsets are exact prefix sums of leaf sizes
    sizes = [np.prod(s, dtype=int) for s in spec.shapes]
    assert list(spec.offsets) == list(np.cumsum([0] + sizes[:-1]))


def test_pack_unpack_mixed_dtypes_bit_exact():
    rng = np.random.default_rng(1)
    tree = _mixed_tree(rng)
    spec = flat.make_flat_spec(tree)
    vec = flat.pack(spec, tree)
    assert vec.dtype == jnp.float32 and vec.shape == (spec.padded_size,)
    # padded tail is exactly zero
    np.testing.assert_array_equal(np.asarray(vec[spec.size:]), 0.0)
    out = jax.tree.map(lambda a, b: (a.dtype == b.dtype,
                                     bool(jnp.all(a == b))),
                       tree, flat.unpack(spec, vec))
    assert all(t == (True, True) for t in jax.tree.leaves(
        out, is_leaf=lambda x: isinstance(x, tuple)))


def test_pack_stacked_rows_equal_per_item_pack():
    rng = np.random.default_rng(2)
    tree = _mixed_tree(rng)
    spec = flat.make_flat_spec(tree)
    K = 3
    stacked = jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) for i in range(K)]), tree)
    mat = flat.pack_stacked(spec, stacked)
    assert mat.shape == (K, spec.padded_size)
    for i in range(K):
        row_i = flat.pack(spec, jax.tree.map(lambda x: x[i], stacked))
        np.testing.assert_array_equal(np.asarray(mat[i]), np.asarray(row_i))
    back = flat.unpack_stacked(spec, mat)
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        assert bool(jnp.all(a == b))


def test_float64_leaf_rejected():
    with jax.enable_x64(True):
        tree = {"w": jnp.asarray(np.ones(4), jnp.float64)}
        with pytest.raises(TypeError, match="round-trip"):
            flat.make_flat_spec(tree)


def test_server_update_flat_matches_tree_oracle():
    from repro.fl.server import (fedavg, server_update_flat,
                                 update_global_direction)
    rng = np.random.default_rng(3)
    params = {"w": jnp.asarray(rng.normal(size=(12, 5)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
    spec = flat.make_flat_spec(params)
    K = 4
    w_i = jax.tree.map(
        lambda p: p[None] + jnp.asarray(
            rng.normal(size=(K,) + p.shape) * 0.1, jnp.float32), params)
    direction = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32), params)

    p_tree = fedavg(w_i)
    d_tree = update_global_direction(direction, params, p_tree, 0.005, 0.1)

    p_vec, d_vec = server_update_flat(
        flat.pack_stacked(spec, w_i), flat.pack(spec, params),
        flat.pack(spec, direction), lr=0.005, gamma=0.1)
    np.testing.assert_array_equal(
        np.asarray(flat.pack(spec, p_tree)), np.asarray(p_vec))
    np.testing.assert_allclose(
        np.asarray(flat.pack(spec, d_tree)), np.asarray(d_vec),
        rtol=1e-6, atol=1e-5)
    # padded tail stays zero through the update (norms/dots unaffected)
    np.testing.assert_array_equal(np.asarray(p_vec[spec.size:]), 0.0)
    np.testing.assert_array_equal(np.asarray(d_vec[spec.size:]), 0.0)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_mgd_update_flat_matches_tree(use_kernel):
    from repro.optim import mgd_init, mgd_update
    rng = np.random.default_rng(4)
    params = {"w": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}
    grads = jax.tree.map(lambda x: x * 0.1, params)
    spec = flat.make_flat_spec(params)
    pv, gv = flat.pack(spec, params), flat.pack(spec, grads)

    p1, s1 = mgd_update(params, grads, mgd_init(params), lr=0.05, gamma=0.9,
                        weight_decay=1e-4)
    p2v, s2 = mgd_update(pv, gv, mgd_init(pv), lr=0.05, gamma=0.9,
                         weight_decay=1e-4, param_layout="flat",
                         use_kernel=use_kernel)
    np.testing.assert_allclose(np.asarray(flat.pack(spec, p1)),
                               np.asarray(p2v), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(flat.pack(spec, s1.momentum)),
                               np.asarray(s2.momentum), rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="param_layout"):
        mgd_update(pv, gv, mgd_init(pv), lr=0.05, param_layout="nope")


def test_gp_projection_tree_uses_flat_workspace():
    """The pytree kernel adapter must agree with gp_scores_stacked."""
    from repro.core import gp
    rng = np.random.default_rng(5)
    direction = {"w": jnp.asarray(rng.normal(size=(9, 4)), jnp.float32),
                 "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    K = 3
    stacked = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=(K,) + p.shape), jnp.float32),
        direction)
    got = ops.gp_projection_tree(stacked, direction)
    want = gp.gp_scores_stacked(stacked, direction)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-4)


def test_dist_gpfl_flat_workspace_matches_tree():
    """grads-impl GPFL step: param_layout='flat' reproduces the tree
    workspace's scores, selection and parameter update."""
    from repro.configs import ARCHS
    from repro.dist import init_train_state, make_gpfl_train_step
    from repro.models import build, concrete_inputs
    cfg = ARCHS["qwen2.5-3b"].reduced()
    api = build(cfg)
    params = api.init(jax.random.key(0))
    batch = concrete_inputs(cfg, 8, 32)
    state = init_train_state(params, 4)
    kw = dict(n_groups=4, k_select=2, total_rounds=100, lr=1e-2,
              remat="none")
    s_t, m_t = jax.jit(make_gpfl_train_step(api, impl="grads", **kw))(
        state, batch)
    s_f, m_f = jax.jit(make_gpfl_train_step(
        api, impl="grads", param_layout="flat", **kw))(state, batch)
    np.testing.assert_allclose(np.asarray(m_t["gp_scores"]),
                               np.asarray(m_f["gp_scores"]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(m_t["selected_mask"]),
                                  np.asarray(m_f["selected_mask"]))
    for a, b in zip(jax.tree.leaves(s_t.params), jax.tree.leaves(s_f.params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-5, atol=1e-6)


def test_run_experiment_rejects_flat_python_backend():
    from repro.configs.paper import femnist_experiment
    from repro.fl import run_experiment
    exp = dataclasses.replace(
        femnist_experiment("2spc", "gpfl"), rounds=2, n_clients=4,
        clients_per_round=2, samples_per_client_mean=10,
        samples_per_client_std=2, local_iters=1, eval_size=16)
    with pytest.raises(ValueError, match="param_layout"):
        run_experiment(exp, backend="python", param_layout="flat")
    with pytest.raises(ValueError, match="param_layout"):
        run_experiment(exp, backend="scan", param_layout="nope")
