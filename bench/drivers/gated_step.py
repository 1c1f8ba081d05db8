"""The GPFL-gated trainer's step (or, with ``"gate": false``, the plain
step of the launcher's ``--no-gate``), jitted with the state donated and
fed by ``repro.launch.train.data_stream``, as ``python -m
repro.launch.train`` runs it.  The step is built from the same public
factories, since ``train.main`` stops after a fixed number of steps.

Set-up makes the weights on the device from the seed, compiles the step,
and drives it through the first ``check_steps`` steps with the window's
own call and feed, recording what the check compares; the window then
continues from that state.  There is no host sync per step: the host
waits only on the loss of the step ``max_in_flight`` back, which bounds
how far it runs ahead of the device.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import jax

from bench.compare import (moved, norm_gap, rel_gap, slice_names,
                           slice_norms, vec_gap)
from bench.counts.mamba2 import train_flops_per_token
from bench.gen.mamba2 import layout, make_weights

#: program config fields that must equal the configuration file's
ARCH_FIELDS = {"n_layers": "n_layer", "d_model": "d_model",
               "vocab_size": "vocab_size", "ssm_state": "state_size",
               "ssm_head_dim": "head_dim", "ssm_expand": "expand",
               "tie_embeddings": "tie_embeddings"}


def arch_config(config: dict):
    """The program's ``ArchConfig`` for ``config['arch']``, with every
    width the file states; a width the program would run differently
    from the file is an error."""
    from repro.configs import get_arch
    arch = get_arch(config["arch"])
    arch = dataclasses.replace(arch, ssm_chunk=config["chunk_size"], **{
        f: config[k] for f, k in ARCH_FIELDS.items()})
    return arch


class Driver:
    unit = "tokens"

    def __init__(self, config: dict, workload: dict, seed: int):
        self.config, self.workload, self.seed = config, workload, seed
        self.train = workload["train"]
        self.gated = bool(workload["gate"])
        self.failed = 0
        self.check_overhead_s = 0.0
        self.pending, self.losses = [], []
        self.ref_rec = None

    def setup(self) -> None:
        import jax.numpy as jnp
        from repro.dist import (init_train_state, make_gpfl_train_step,
                                make_plain_train_step)
        from repro.launch import train as launch
        from repro.models import build

        t, cfg = self.train, self.config
        arch = arch_config(cfg)
        api = build(arch)
        want = jax.tree.map(lambda s: s.shape,
                            jax.eval_shape(api.init, jax.random.key(0)))
        if want != layout(cfg):
            raise ValueError("the program's parameter layout differs from "
                             "the benchmark's for this configuration")
        self.weights = jax.jit(make_weights(cfg))
        self.key = jax.random.key(self.seed)
        state = init_train_state(self.weights(self.key), t["n_groups"])
        if self.gated:
            fn = make_gpfl_train_step(
                api, impl="jvp", n_groups=t["n_groups"],
                k_select=t["k_select"], total_rounds=t["total_rounds"],
                lr=t["lr"], gamma=t["gamma"], rho=t["rho"],
                remat=t["remat"])
        else:
            fn = make_plain_train_step(api, lr=t["lr"], gamma=t["gamma"],
                                       remat=t["remat"])
        self.stream = launch.data_stream(arch, t["n_groups"], t["batch"],
                                         t["seq"], self.seed)
        batch = next(self.stream)
        self.compiled = jax.jit(fn, donate_argnums=0).lower(
            state, batch).compile()
        norms = jax.jit(slice_norms)
        delta = jax.jit(lambda p, q: slice_norms(
            jax.tree.map(jnp.subtract, p, q)))

        rec = {"tokens": [], "labels": [], "loss": [], "scores": [],
               "selected": []}
        for i in range(self.workload["check_steps"]):
            if i:
                batch = next(self.stream)
            rec["tokens"].append(np.asarray(batch["tokens"]))
            rec["labels"].append(np.asarray(batch["labels"]))
            state, met = self.compiled(state, batch)
            rec["loss"].append(met["loss"])
            if self.gated:
                rec["scores"].append(met["gp_scores"])
                rec["selected"].append(met["selected_mask"])
            if i == 0:
                jax.block_until_ready(state)
                t0 = time.perf_counter()
                rec["grad_norms"] = np.asarray(norms(state.momentum))
                self.check_overhead_s += time.perf_counter() - t0
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        p0 = self.weights(self.key)
        rec["change_norms"] = np.asarray(delta(state.params, p0))
        del p0
        rec = {k: [np.asarray(v) for v in vs] if isinstance(vs, list) else vs
               for k, vs in rec.items()}
        self.rec = rec
        self.check_overhead_s += time.perf_counter() - t0
        self.state = state
        self.tokens_per_step = t["batch"] * t["seq"]

    def step(self, spans) -> int:
        with spans.span("next_batch"):
            batch = next(self.stream)
        with spans.span("dispatch"):
            self.state, met = self.compiled(self.state, batch)
        self.pending.append(met["loss"])
        if len(self.pending) > self.workload["max_in_flight"]:
            with spans.span("wait"):
                self.losses.append(np.asarray(self.pending.pop(0)))
        return self.tokens_per_step

    def drain(self, spans) -> None:
        with spans.span("wait"):
            jax.block_until_ready(self.state)
            self.losses += [np.asarray(x) for x in self.pending]
        self.pending = []
        self.failed = int(sum(not np.isfinite(x) for x in self.losses))

    def counts(self, units: int, steps: int) -> dict:
        return {"tokens": units, "steps": steps,
                "flops_per_token": train_flops_per_token(self.config)}

    def release(self) -> None:
        self.state = self.compiled = self.stream = None

    def check(self) -> dict:
        lim = self.workload["limits"]
        got = self.readings()
        return {k: {"value": got[k], "limit": lim[k]} for k in lim}

    def readings(self, **ref_kw) -> dict:
        """The compared numbers of this run against the reference (or,
        with ``ref_kw``, of the reference in another form put in the
        program's place: the control, a planted fault)."""
        def weights():
            return self.weights(self.key)
        if self.ref_rec is None:
            self.ref_rec = reference_record(self.config, self.train,
                                            self.gated, weights, self.rec)
        prog = self.rec if not ref_kw else reference_record(
            self.config, self.train, self.gated, weights, self.rec, **ref_kw)
        return gaps(prog, self.ref_rec, self.gated)

    def control_readings(self) -> dict:
        """The reference at bfloat16, the precision below the
        configuration's float32, in the program's place."""
        import jax.numpy as jnp
        return self.readings(dtype=jnp.bfloat16, precision="default")

    def fault_readings(self) -> dict:
        """Faults planted in the reference put in the program's place: half
        of each group's rows left out (the mean over the rest), and the
        selection altered where it is made.  A state left unchanged reads
        1 on ``change`` by construction."""
        faults = ("half_batch", "answer") if self.gated else ("half_batch",)
        return {f: self.readings(fault=f) for f in faults}


def reference_record(config: dict, train: dict, gated: bool, weights,
                     rec: dict, **ref_kw) -> dict:
    """Follow ``rec``'s batches with the reference, starting from
    ``weights()`` → the same record the driver keeps of the program: per
    step the loss, scores and selection mask, the first step's momentum
    norms and the change norms.  The initial weights are made again for
    the change, not kept beside the reference's state."""
    import jax.numpy as jnp
    from bench.reference.mamba2 import Reference
    ref = Reference(config, train, weights(), **ref_kw)
    norms = jax.jit(slice_norms)
    out = {"loss": [], "scores": [], "selected": []}
    G = train["n_groups"]
    for i, (tok, lab) in enumerate(zip(rec["tokens"], rec["labels"])):
        r = ref.step(tok, lab, gated)
        out["loss"].append(r["loss"])
        out["scores"].append(r["scores"])
        mask = np.zeros(G, np.float32)
        mask[r["selected"]] = 1.0
        out["selected"].append(mask)
        if i == 0:
            out["grad_norms"] = np.asarray(norms(ref.momentum))
    out["names"] = slice_names(ref.params)
    out["change_norms"] = np.asarray(jax.jit(lambda p, q: slice_norms(
        jax.tree.map(lambda a, b: a.astype(jnp.float32)
                     - b.astype(jnp.float32), p, q)))(ref.params, weights()))
    return out


def gaps(prog: dict, ref: dict, gated: bool) -> dict:
    """The compared numbers: relative loss gap over the steps, worst-leaf
    gaps of the first gradient's norm and of the parameters' change (over
    the leaves the reference moves), and for the gated step the widest
    GP-score gap after step 0 (whose scores are exactly 0: zero
    momentum)."""
    keep = moved(ref["grad_norms"])
    g_gap, g_i = norm_gap(prog["grad_norms"], ref["grad_norms"])
    c_gap, c_i = norm_gap(prog["change_norms"], ref["change_norms"], keep)
    out = {"loss": rel_gap(prog["loss"], ref["loss"]), "grad": g_gap,
           "change": c_gap, "grad_leaf": ref["names"][g_i],
           "change_leaf": ref["names"][c_i],
           "excluded_leaves": int((~keep).sum())}
    if gated:
        out["scores"] = max(vec_gap(p, r) for p, r in
                            zip(prog["scores"][1:], ref["scores"][1:]))
        out["same_selection"] = all(
            np.array_equal(np.asarray(p) > 0, np.asarray(r) > 0)
            for p, r in zip(prog["selected"], ref["selected"]))
    return out
