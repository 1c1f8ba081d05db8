"""Small-mesh (2×2, subprocess-forced 8 devices) lowering tests: the same
code path as the production dry-run, kept cheap for CI.  The full 16×16 and
2×16×16 meshes are exercised by ``python -m repro.launch.dryrun --all``
(results recorded in EXPERIMENTS.md §Dry-run)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json, sys
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import ARCHS, SHAPES
    from repro.dist import rules_for, init_train_state, \\
        make_gpfl_train_step, make_serve_step
    from repro.launch.mesh import make_cpu_test_mesh
    from repro.models import build, input_specs
    from repro.models.common import logical_spec

    arch, kind = sys.argv[1], sys.argv[2]
    cfg = ARCHS[arch].reduced()
    mesh = make_cpu_test_mesh(2, 2)
    shape = dataclasses.replace(
        SHAPES["train_4k" if kind == "train" else "decode_32k"],
        seq_len=64, global_batch=8)
    rules = rules_for(cfg, shape, model_size=2, data_size=2)
    api = build(cfg)
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    params_abs = api.abstract_params(jnp.bfloat16)
    pspecs = api.param_specs(rules)
    with jax.set_mesh(mesh):
        if kind == "train":
            step = make_gpfl_train_step(api, n_groups=2, k_select=1,
                                        total_rounds=10, lr=1e-2,
                                        rules=rules, remat="full",
                                        grad_specs=pspecs)
            state = jax.eval_shape(lambda p: init_train_state(p, 2),
                                   params_abs)
            sspec = type(state)(params=pspecs, momentum=pspecs,
                                bandit=jax.tree.map(lambda _: P(),
                                                    state.bandit),
                                step=P(), prev_loss=P())
            batch = input_specs(cfg, shape)
            bspec = {k: logical_spec(("batch", "seq") if v.ndim == 2 else
                                     ("batch", None, "embed"), rules)
                     for k, v in batch.items()}
            c = jax.jit(step, in_shardings=(named(sspec), named(bspec))
                        ).lower(state, batch).compile()
        else:
            step = make_serve_step(api, rules=rules)
            cache = api.init_cache(8, 64, abstract=True)
            cspecs = api.cache_specs(rules)
            dec = input_specs(cfg, shape)
            c = jax.jit(step, in_shardings=(
                named(pspecs), named(cspecs),
                NamedSharding(mesh, logical_spec(("cache_batch", None),
                                                 rules)),
                NamedSharding(mesh, P()))).lower(
                params_abs, cache, dec["tokens"], dec["pos"]).compile()
    print(json.dumps({"ok": True,
                      "flops": c.cost_analysis().get("flops", -1)}))
""")


def _run(arch, kind):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT, arch, kind],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"]


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "mamba2-370m",
                                  "recurrentgemma-9b",
                                  "qwen3-moe-235b-a22b", "whisper-small"])
def test_train_step_lowers_on_2x2(arch):
    _run(arch, "train")


@pytest.mark.parametrize("arch", ["gemma3-4b", "llama-3.2-vision-90b"])
def test_serve_step_lowers_on_2x2(arch):
    _run(arch, "serve")
