"""Launch/analysis utilities: HLO collective parsing, sharding rules,
chunked CE, LR schedules, data streams."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES
from repro.dist.sharding import arch_rules, rules_for
from repro.launch.dryrun import parse_collectives

HLO = """
ENTRY %main (p0: f32[16,128]) -> f32[16,128] {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ag = f32[256,128]{1,0} all-gather(%p0), replica_groups={{0,1},{2,3}}, dimensions={0}
  %ar = bf16[16,128]{1,0} all-reduce(%conv), replica_groups={{0,1,2,3}}, to_apply=%sum
  %rs = f32[4,128]{1,0} reduce-scatter(%ag2), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = f32[16,128]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
  %a2a = s32[8,16]{1,0} all-to-all(%x), replica_groups={{0,1}}
}
"""


class TestParseCollectives:
    def test_kinds_and_counts(self):
        out = parse_collectives(HLO)
        assert out["all-gather"]["count"] == 1
        assert out["all-reduce"]["count"] == 1
        assert out["reduce-scatter"]["count"] == 1
        assert out["collective-permute"]["count"] == 1
        assert out["all-to-all"]["count"] == 1

    def test_bytes(self):
        out = parse_collectives(HLO)
        assert out["all-gather"]["bytes"] == 256 * 128 * 4
        assert out["all-reduce"]["bytes"] == 16 * 128 * 2
        # reduce-scatter: result bytes × group size (4)
        assert out["reduce-scatter"]["bytes"] == 4 * 128 * 4 * 4
        assert out["total_bytes"] == sum(
            v["bytes"] for k, v in out.items() if isinstance(v, dict))

    def test_empty(self):
        assert parse_collectives("ENTRY %m { %r = f32[2]{0} add(%a,%b) }"
                                 )["total_bytes"] == 0


class TestRules:
    def test_heads_shard_when_divisible(self):
        r = arch_rules(ARCHS["qwen2.5-3b"], model_size=16)
        assert r["heads"] == "model"
        assert r["kv_heads"] is None        # kv=2 < 16
        assert r["head_dim"] is None

    def test_head_dim_fallback(self):
        r = arch_rules(ARCHS["phi3-medium-14b"], model_size=16)  # 40 heads
        assert r["heads"] is None
        assert r["head_dim"] == "model"     # hd=128 % 16 == 0

    def test_vocab_replicated_when_indivisible(self):
        assert arch_rules(ARCHS["whisper-small"])["vocab"] is None  # 51865
        assert arch_rules(ARCHS["qwen2.5-3b"])["vocab"] == "model"

    def test_moe_layouts(self):
        q = arch_rules(ARCHS["qwen3-moe-235b-a22b"])
        assert q["experts"] == "model" and q["expert_ff"] == "data"
        g = arch_rules(ARCHS["grok-1-314b"])
        assert g["experts"] is None
        assert g["expert_ff"] == ("data", "model")
        assert g["expert_ff_act"] == "model"  # no 16× replicated FLOPs

    def test_long500k_shards_cache_seq(self):
        r = rules_for(ARCHS["gemma3-4b"], SHAPES["long_500k"])
        assert r["cache_seq"] == "data"
        assert r["batch"] is None           # global_batch=1
        r2 = rules_for(ARCHS["gemma3-4b"], SHAPES["decode_32k"])
        assert r2["cache_seq"] is None
        assert r2["cache_batch"] == "data"

    def test_seq_parallel_only_when_divisible(self):
        r = rules_for(ARCHS["qwen2.5-3b"], SHAPES["train_4k"])
        assert r["act_seq"] == "model"
        r2 = rules_for(ARCHS["qwen2.5-3b"], SHAPES["decode_32k"])
        assert r2.get("act_seq") is None

    def test_moe_chunking_budget(self):
        r = rules_for(ARCHS["qwen3-moe-235b-a22b"], SHAPES["train_4k"])
        tg = 256 * 4096 // r["_moe_groups"]
        tc = tg // r["_moe_chunks"]
        assert tc * 8 * 4096 * 2 <= 256 * 2 ** 20  # ≤ 256MB dispatch buffer


class TestChunkedCE:
    @pytest.mark.parametrize("n_chunks", [1, 2, 4, 7])
    def test_matches_dense_ce(self, n_chunks):
        from repro.models.stack import chunked_ce
        rng = np.random.default_rng(n_chunks)
        B, S, D, V = 2, 28, 16, 50
        x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(D, V)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
        got = chunked_ce(x, w, labels, n_chunks=n_chunks)
        logits = (x @ w).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(lse - gold),
                                   rtol=2e-5, atol=2e-5)

    def test_grad_matches(self):
        from repro.models.stack import chunked_ce
        rng = np.random.default_rng(0)
        B, S, D, V = 2, 8, 8, 20
        x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(D, V)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
        g1 = jax.grad(lambda w_: jnp.mean(chunked_ce(x, w_, labels,
                                                     n_chunks=4)))(w)
        def dense(w_):
            logits = (x @ w_).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, -1)
            gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
            return jnp.mean(lse - gold)
        g2 = jax.grad(dense)(w)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                                   atol=1e-6)


class TestSchedules:
    def test_cosine_endpoints(self):
        from repro.optim import cosine_decay
        f = cosine_decay(1.0, 100, final_frac=0.1)
        assert abs(float(f(jnp.int32(0))) - 1.0) < 1e-6
        assert abs(float(f(jnp.int32(100))) - 0.1) < 1e-6

    def test_warmup(self):
        from repro.optim import linear_warmup_cosine
        f = linear_warmup_cosine(2.0, 10, 110)
        assert abs(float(f(jnp.int32(5))) - 1.0) < 1e-6
        assert abs(float(f(jnp.int32(10))) - 2.0) < 1e-6


def test_domain_stream_heterogeneous():
    """Per-group token streams must be distinguishable (Non-IID premise)."""
    from repro.data.synthetic import lm_token_stream
    toks = lm_token_stream(4, 4000, 1024, n_domains=4, seed=0)
    means = toks.mean(axis=1)
    assert np.std(means) > 30  # domains occupy different vocab slices


@pytest.mark.parametrize("chips,holds,workers,platforms,refused", [
    (0, True, 8, "", False),        # no TPU attached: anything goes
    (4, False, 1, "", False),       # one child, parent off the backend
    (4, False, 2, "", True),        # two children contend for the chips
    (4, True, 1, "", True),         # the parent holds the chips
    (4, True, 2, "cpu", False),     # children kept off the TPU
])
def test_check_can_spawn(monkeypatch, chips, holds, workers, platforms,
                         refused):
    """JAX children are refused on a TPU host unless each can reach it."""
    from jax._src import hardware_utils, xla_bridge
    from repro.launch.devices import check_can_spawn
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (chips, None))
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: holds)
    if refused:
        with pytest.raises(RuntimeError, match="TPU chip"):
            check_can_spawn(workers, "test")
    else:
        check_can_spawn(workers, "test")


def test_device_peaks_keyed_by_kind():
    from repro.launch.mesh import device_peaks
    assert device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("TPU v99")
