"""The round's local minibatch rows, built once before the local scan.

``repro.fl.client`` draws every local step's indices up front and builds a
client's rows for the whole round in one go: by a one-hot contraction on a
TPU (``contract_rows``, labels by ``select_labels``), by one row gather
elsewhere.  Both must give the
rows a per-step ``x[idx]`` gives, bit for bit, and the cohort trainer must
give what it gave when it gathered inside each local step (the oracle
below keeps that version).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper import CIFAR10_CNN, FEMNIST_MLP, femnist_experiment
from repro.fl import client
from repro.fl import engine
from repro.models import small


def _bits(a):
    return np.asarray(a).view(np.uint32)


def _table(kind: str, cap: int, feat, seed: int = 0):
    rng = np.random.default_rng(seed)
    shape = (cap,) + tuple(feat)
    if kind == "zeros":
        return np.zeros(shape, np.float32)
    mag = 10.0 ** rng.uniform(-6, 6, shape)
    x = rng.choice([-1.0, 1.0], shape) * mag
    if kind == "mixed":  # exact zeros and powers of two among the values
        x[rng.random(shape) < 0.2] = 0.0
        x[rng.random(shape) < 0.1] = 2.0 ** rng.integers(-20, 20)
    return x.astype(np.float32)


@pytest.mark.parametrize("feat", [(784,), (32, 32, 3)], ids=["mlp", "cnn"])
@pytest.mark.parametrize("kind", ["wide", "mixed", "zeros"])
@pytest.mark.parametrize("size", [226, 100, 1, 0],
                         ids=["full", "padded", "one", "empty"])
def test_contract_rows_equal_gather(feat, kind, size):
    cap, iters, batch = 226, 5, 16
    x = _table(kind, cap, feat)
    rng = np.random.default_rng(1)
    idx = rng.integers(0, max(size, 1), (iters, batch)).astype(np.int32)
    got = jax.jit(client.contract_rows)(jnp.asarray(x), jnp.asarray(idx))
    assert got.shape == (iters, batch) + feat
    np.testing.assert_array_equal(_bits(got), _bits(x[idx]))


@pytest.mark.parametrize("size", [226, 100, 1, 0],
                         ids=["full", "padded", "one", "empty"])
def test_select_labels_equal_take(size):
    rng = np.random.default_rng(2)
    y = rng.integers(-2**31, 2**31 - 1, 226, dtype=np.int64).astype(np.int32)
    idx = rng.integers(0, max(size, 1), (20, 64)).astype(np.int32)
    got = jax.jit(client.select_labels)(jnp.asarray(y), jnp.asarray(idx))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), y[idx])


@pytest.mark.parametrize("kind", ["wide", "mixed"])
def test_split_bf16_rebuilds_float32(kind):
    x = _table(kind, 64, (97,), seed=3)
    hi, mid, lo = jax.jit(client.split_bf16)(jnp.asarray(x))
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    f32 = jnp.float32
    back = (hi.astype(f32) + mid.astype(f32)) + lo.astype(f32)
    np.testing.assert_array_equal(_bits(back), _bits(x))


@pytest.mark.parametrize("size", [226, 17, 1, 0])
def test_hoisted_indices_equal_per_step_draws(size):
    iters, batch = 20, 64
    rngs = jax.random.split(jax.random.key(7), iters)
    hi = jnp.maximum(size, 1)
    hoisted = jax.vmap(lambda r: jax.random.randint(r, (batch,), 0, hi))(rngs)
    per_step = jnp.stack([jax.random.randint(r, (batch,), 0, hi)
                          for r in rngs])
    np.testing.assert_array_equal(np.asarray(hoisted), np.asarray(per_step))


@pytest.mark.parametrize("backend,cap,path", [
    ("tpu", 226, "contract"),
    ("tpu", client.CONTRACT_MAX_ROWS, "contract"),
    ("tpu", client.CONTRACT_MAX_ROWS + 1, "take"),
    ("cpu", 226, "take"),
    ("gpu", 226, "take"),
])
def test_minibatch_path_by_cap_and_backend(backend, cap, path):
    assert client.minibatch_path(cap, backend) == path


def test_minibatch_path_defaults_to_jax_backend():
    assert client.minibatch_path(226) == client.minibatch_path(
        226, jax.default_backend())


def test_engine_records_path_outside_fingerprint(monkeypatch):
    exp = dataclasses.replace(femnist_experiment(selector="random", rounds=2),
                              n_clients=6, eval_size=16)
    eng = engine.ScanEngine(exp)
    assert eng.minibatch_path == client.minibatch_path(eng.store.capacity)
    monkeypatch.setattr(engine, "minibatch_path",
                        lambda cap, backend=None: "contract")
    other = engine.ScanEngine(exp, data=(eng.store, eng.eval_x, eng.eval_y))
    assert other.minibatch_path == "contract" != eng.minibatch_path
    assert other.fingerprint() == eng.fingerprint()


def _oracle_trainer(exp):
    """The cohort trainer as it was: each local step draws its indices and
    gathers ``x[idx]`` inside the scan."""
    cfg = exp.model

    def one_client(params0, x, y, size, rng):
        def step(carry, rng_i):
            params, d = carry
            idx = jax.random.randint(rng_i, (exp.local_batch_size,), 0,
                                     jnp.maximum(size, 1))
            batch = {"x": x[idx], "y": y[idx]}
            loss, grads = jax.value_and_grad(small.loss_fn)(params, batch, cfg)

            def upd(p, g, m):
                gf = g + exp.weight_decay * p
                m_new = exp.momentum * m + gf
                return p - exp.lr * m_new, m_new

            out = jax.tree.map(upd, params, grads, d)
            params = jax.tree.map(lambda o: o[0], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
            d = jax.tree.map(lambda o: o[1], out,
                             is_leaf=lambda t: isinstance(t, tuple))
            return (params, d), loss

        d0 = jax.tree.map(jnp.zeros_like, params0)
        rngs = jax.random.split(rng, exp.local_iters)
        (params, d), losses = jax.lax.scan(step, (params0, d0), rngs)
        return params, d, jnp.mean(losses)

    return jax.jit(jax.vmap(one_client, in_axes=(None, 0, 0, 0, 0)))


def _cohort(exp, K: int, cap: int, seed: int):
    rng = np.random.default_rng(seed)
    feat = exp.model.input_shape
    x = rng.standard_normal((K, cap) + feat).astype(np.float32)
    y = rng.integers(0, exp.model.num_classes, (K, cap)).astype(np.int32)
    sizes = np.linspace(cap, 0, K).astype(np.int32)  # full down to empty
    params = small.init(jax.random.key(seed), exp.model)
    keys = jax.random.split(jax.random.key(seed + 1), K)
    return params, jnp.asarray(x), jnp.asarray(y), jnp.asarray(sizes), keys


_MODELS = {"mlp": FEMNIST_MLP, "cnn": CIFAR10_CNN}


def _trainer_case(model: str, mode: str):
    """(experiment, arguments, how to wrap a cohort trainer) of one case."""
    exp = dataclasses.replace(femnist_experiment(), model=_MODELS[model],
                              local_iters=3, local_batch_size=8)
    K, cap = 3, 24
    if mode == "jit":
        return exp, _cohort(exp, K, cap, seed=0), lambda f: f
    # a seed axis over everything, as the batched seed engine has it
    seeds = [_cohort(exp, K, cap, seed=s) for s in (0, 5)]
    args = jax.tree.map(lambda *a: jnp.stack(a), *seeds)
    return exp, args, lambda f: jax.jit(jax.vmap(f))


@functools.lru_cache(maxsize=None)
def _oracle_result(model: str, mode: str):
    exp, args, wrap = _trainer_case(model, mode)
    return wrap(_oracle_trainer(exp))(*args)


@pytest.mark.parametrize("model,mode", [
    ("mlp", "jit"), ("mlp", "vmap_seeds"), ("cnn", "jit"),
], ids=["mlp-jit", "mlp-vmap_seeds", "cnn-jit"])
@pytest.mark.parametrize("path", ["contract", "take"])
def test_cohort_trainer_matches_per_step_gather(monkeypatch, model, mode,
                                                path):
    monkeypatch.setattr(client, "minibatch_path",
                        lambda cap, backend=None: path)
    exp, args, wrap = _trainer_case(model, mode)
    got = wrap(client.make_cohort_trainer(exp))(*args)
    want = _oracle_result(model, mode)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(_bits(g), _bits(w))
