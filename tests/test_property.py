"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need the 'hypothesis' dev extra "
           "(pip install -e '.[dev]')")
from hypothesis import given, settings, strategies as st

from repro.core import flat, gp, gpcb
from repro.data.partition import partition
from repro.kernels import ops, ref

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")

_FLAT_DTYPES = st.sampled_from([jnp.float32, jnp.bfloat16, jnp.float16])
_FLAT_SHAPES = st.lists(
    st.lists(st.integers(1, 7), min_size=0, max_size=3).map(tuple),
    min_size=1, max_size=5)


@given(_FLAT_SHAPES, st.lists(st.integers(0, 2), min_size=1, max_size=5),
       st.integers(0, 10 ** 6))
def test_flat_pack_unpack_bit_exact(shapes, dtype_picks, seed):
    """unpack(pack(tree)) == tree BIT-exactly across mixed dtypes/shapes
    (incl. 0-d leaves), and the padded tail is exactly zero."""
    rng = np.random.default_rng(seed)
    dts = [jnp.float32, jnp.bfloat16, jnp.float16]
    tree = {
        f"leaf{i}": jnp.asarray(rng.normal(size=shp) * 10.0 ** rng.integers(
            -3, 4), dts[dtype_picks[i % len(dtype_picks)]])
        for i, shp in enumerate(shapes)
    }
    spec = flat.make_flat_spec(tree)
    vec = flat.pack(spec, tree)
    assert vec.shape == (spec.padded_size,)
    assert spec.padded_size % flat.DEFAULT_PAD_TO == 0
    np.testing.assert_array_equal(np.asarray(vec[spec.size:]), 0.0)
    back = flat.unpack(spec, vec)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        # bitwise comparison: compare the raw bytes (works for 0-d too)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@given(st.integers(1, 5), _FLAT_SHAPES, st.integers(0, 10 ** 6))
def test_flat_gp_matrix_matches_tree_scores(k, shapes, seed):
    """gp_scores_matrix on the packed (K, Dp) workspace == gp_scores_tree
    on the pytrees (float32 tolerance) — the padded tail must not leak
    into dots or the direction norm."""
    rng = np.random.default_rng(seed)
    direction = {f"l{i}": jnp.asarray(rng.normal(size=shp) + 0.05,
                                      jnp.float32)
                 for i, shp in enumerate(shapes)}
    grads = [jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32),
        direction) for _ in range(k)]
    spec = flat.make_flat_spec(direction)
    gm = jnp.stack([flat.pack(spec, g) for g in grads])
    want = gp.gp_scores_tree(grads, direction)
    got = gp.gp_scores_matrix(gm, flat.pack(spec, direction))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@given(st.integers(2, 40), st.integers(1, 5), st.integers(0, 2 ** 31 - 1),
       st.sampled_from(["iid", "1spc", "2spc", "dir"]))
def test_partition_is_a_partition(n_clients, spc_unused, seed, scheme):
    """Every sample assigned exactly once; client count respected."""
    rng = np.random.default_rng(seed)
    n = n_clients * 40
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    parts = partition(scheme, labels, n_clients, seed=seed)
    assert len(parts) == n_clients
    allidx = np.concatenate(parts)
    assert len(allidx) <= n
    assert len(np.unique(allidx)) == len(allidx)  # disjoint
    if scheme in ("iid", "1spc", "2spc"):
        # balanced schemes drop at most n_clients*spc remainder samples
        assert len(allidx) >= n - 2 * n_clients


@given(st.integers(1, 8), st.integers(0, 2 ** 31 - 1))
def test_spc_label_concentration(spc_clients, seed):
    """1SPC clients hold exactly one label (the paper's extreme skew)."""
    rng = np.random.default_rng(seed)
    n_clients = max(2, spc_clients)
    labels = np.sort(rng.integers(0, n_clients, size=n_clients * 64)
                     ).astype(np.int32)
    parts = partition("1spc", labels, n_clients, seed=seed)
    for ix in parts:
        # one shard = one contiguous slice of the label-sorted order ⇒ the
        # labels a client sees form a contiguous integer range
        u = np.unique(labels[ix])
        assert u.max() - u.min() == len(u) - 1


@given(st.integers(1, 6), st.integers(5, 60), st.integers(0, 10 ** 6))
def test_gp_scale_invariance_of_direction(k, d, seed):
    """GP(g, c·m) == GP(g, m) for c>0 — projection uses only m's direction
    up to |m| normalisation (Eq. 3)."""
    rng = np.random.default_rng(seed)
    G = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(d,)) + 0.1, jnp.float32)
    s1 = gp.gp_scores_matrix(G, m)
    s2 = gp.gp_scores_matrix(G, 3.7 * m)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=2e-4,
                               atol=1e-4)


@given(st.integers(1, 4), st.integers(10, 500), st.integers(0, 10 ** 6))
def test_gp_kernel_equals_oracle(k, d, seed):
    rng = np.random.default_rng(seed)
    G = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(d,)) + 0.05, jnp.float32)
    got = ops.gp_projection(G, m, block_d=128)
    want = ref.gp_projection_ref(G, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=1e-4)


@given(st.integers(2, 30), st.integers(1, 29), st.integers(0, 10 ** 6))
def test_gpcb_selects_k_and_prefers_unseen(n, k, seed):
    k = min(k, n)
    rng = np.random.default_rng(seed)
    st_ = gpcb.init_state(n)
    seen = rng.random(n) < 0.5
    seen[: 1] = True  # at least one seen
    count = jnp.asarray(np.where(seen, rng.integers(1, 10, n), 0), jnp.float32)
    st_ = st_._replace(round=jnp.float32(20), count=count,
                       reward_sum=jnp.asarray(rng.random(n), jnp.float32)
                       * count)
    u = gpcb.gpcb_values(st_, 100)
    vals, idx = gpcb.select_topk(u, k)
    assert len(set(np.asarray(idx).tolist())) == k
    n_unseen = int((~seen).sum())
    # unseen arms (infinite UCB) must be selected before any seen arm
    expect_unseen = min(k, n_unseen)
    assert int((~seen[np.asarray(idx)]).sum()) == expect_unseen


@given(st.lists(st.floats(-5, 5), min_size=2, max_size=20),
       st.floats(0, 1), st.floats(0, 1))
def test_calibrated_rewards_bounded(mus, acc, prev_acc):
    """Assumption 2: rewards stay in [0, 1] after Eq. 8 calibration."""
    mu = jnp.asarray(np.abs(mus) / (np.abs(mus).max() + 1e-9), jnp.float32)
    out = np.asarray(gpcb.calibrate_reward(mu, acc, prev_acc, 2.0, 1.0))
    assert np.all(out >= 0.0) and np.all(out <= 1.0)


@given(st.integers(2, 8), st.integers(0, 10 ** 6))
def test_fedavg_identity(n, seed):
    """FedAvg of identical params is the identity."""
    from repro.fl.server import fedavg
    rng = np.random.default_rng(seed)
    p = {"w": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)}
    cohort = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                          p)
    out = fedavg(cohort)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(p["w"]),
                               rtol=1e-6)


@given(st.integers(0, 10 ** 6))
def test_momentum_kernel_property(seed):
    """Fused kernel: with γ=0, wd=0 the update is plain SGD."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(10, 3000))
    p = jnp.asarray(rng.normal(size=n), jnp.float32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    m = jnp.asarray(rng.normal(size=n), jnp.float32)
    pn, mn = ops.fused_momentum(p, g, m, lr=0.1, gamma=0.0, weight_decay=0.0)
    np.testing.assert_allclose(np.asarray(pn), np.asarray(p - 0.1 * g),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mn), np.asarray(g), rtol=1e-6)


@given(st.integers(3, 8), st.integers(1, 4), st.integers(0, 10 ** 6),
       st.sampled_from(["mean", "trimmed_mean", "median", "norm_clip"]),
       st.booleans())
def test_robust_aggregation_screens_nonfinite_rows(k, n_bad, seed, agg,
                                                   packed):
    """Corrupt any subset of cohort rows with NaN/Inf: after the
    non-finite screen, every robust aggregator yields FINITE params that
    match the same aggregator run on the clean rows alone — for the
    stacked-pytree layout and the packed (K, Dp) matrix alike."""
    from repro.fl.robust import RobustConfig, finite_rows, robust_aggregate
    n_bad = min(n_bad, k - 1)  # keep at least one clean row
    rng = np.random.default_rng(seed)
    if packed:
        shapes = {"m": (37,)}
    else:
        shapes = {"a": (3, 2), "b": (5,)}
    cohort = {n: jnp.asarray(rng.normal(size=(k,) + s), jnp.float32)
              for n, s in shapes.items()}
    w_prev = {n: jnp.asarray(rng.normal(size=s), jnp.float32)
              for n, s in shapes.items()}
    bad = rng.choice(k, size=n_bad, replace=False)
    poison = [np.nan, np.inf, -np.inf]
    for j, row in enumerate(bad):
        name = list(shapes)[j % len(shapes)]
        flat_idx = (row,) + tuple(0 for _ in shapes[name])
        cohort[name] = cohort[name].at[flat_idx].set(poison[j % 3])

    valid = finite_rows(cohort)
    np.testing.assert_array_equal(np.asarray(valid),
                                  ~np.isin(np.arange(k), bad))
    cfg = RobustConfig(agg)
    out = robust_aggregate(cfg, cohort, w_prev, valid)
    clean = {n: v[jnp.asarray(valid)] for n, v in cohort.items()}
    ref = robust_aggregate(cfg, clean, w_prev,
                           jnp.ones((k - n_bad,), bool))
    for n in shapes:
        assert np.isfinite(np.asarray(out[n])).all()
        np.testing.assert_allclose(np.asarray(out[n]), np.asarray(ref[n]),
                                   rtol=1e-5, atol=1e-6)
