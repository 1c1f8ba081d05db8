"""The FL round engine: ``repro.fl.engine.ScanEngine.run()`` dispatched again
and again on one warm engine, as ``repro.api.Session`` dispatches a
single-seed scan cell (``ExecutionSpec`` defaults: scan backend, tree
layout, ``gp_impl="auto"``, so the Pallas GP kernel on a TPU).

Every dispatch runs the configuration's whole run of ``rounds`` rounds
from the same initial state, so every dispatch must return the same
history; the last one is compared with ``bench.reference.fl`` following
its cohorts: its per-round choices and eval losses, and every client's
latest GP score after the last round."""
from __future__ import annotations

import numpy as np

from bench.counts.gp_kernel import padded
from bench.counts.small_models import flops_per_round, param_count
from bench.gen.femnist import fl_dataset


def experiment(config: dict, workload: dict, seed: int):
    """The program's experiment config, built from the benchmark files."""
    from repro.configs.paper import FLExperimentConfig, SmallModelConfig
    m, fl = config["model"], config["fl"]
    model = SmallModelConfig(name=config["name"], kind=m["kind"],
                             input_shape=tuple(m["input_shape"]),
                             num_classes=m["num_classes"],
                             hidden=tuple(m["hidden"]))
    return FLExperimentConfig(
        name=f"{config['name']}-{fl['partition']}-{workload['selector']}",
        model=model, n_clients=fl["n_clients"],
        clients_per_round=fl["clients_per_round"],
        partition=fl["partition"], rounds=fl["rounds"],
        local_batch_size=fl["local_batch_size"],
        local_iters=fl["local_iters"], lr=fl["lr"],
        weight_decay=fl["weight_decay"], momentum=fl["momentum"],
        rho=fl["rho"], selector=workload["selector"], seed=seed,
        samples_per_client_mean=fl["samples_per_client"],
        eval_size=fl["eval_size"])


class Driver:
    unit = "rounds"

    def __init__(self, config: dict, workload: dict, seed: int):
        self.config, self.workload, self.seed = config, workload, seed
        self.fl = config["fl"]
        self.failed = 0
        self.check_overhead_s = 0.0
        self.first = self.last = None

    def setup(self) -> None:
        import jax.numpy as jnp
        from repro.data import ClientStore
        from repro.data.synthetic import Dataset
        from repro.fl.engine import ScanEngine

        exp = experiment(self.config, self.workload, self.seed)
        (self.x_tab, self.y_tab, self.eval_x, self.eval_y, tx, ty,
         parts) = fl_dataset(self.fl, self.seed)
        store = ClientStore(Dataset(tx, ty, self.config["model"]
                                    ["num_classes"]), parts)
        self.engine = ScanEngine(exp, data=(store, jnp.asarray(self.eval_x),
                                            jnp.asarray(self.eval_y)))
        self.first = self._record(self.engine.run())  # compiles, warms up

    @staticmethod
    def _record(res):
        return {"ids": np.asarray(res.selections),
                "loss": np.asarray(res.loss), "acc": np.asarray(res.accuracy)}

    def step(self, spans) -> int:
        with spans.span("dispatch"):
            res = self.engine.run()
        out = self._record(res)
        same = all(np.array_equal(out[k], self.first[k]) for k in out)
        finite = bool(np.isfinite(out["loss"]).all())
        self.failed += int(not (same and finite))
        self.last = out
        return self.fl["rounds"]

    def drain(self, spans) -> None:
        """``run()`` returns once its dispatch has finished."""

    def counts(self, units: int, steps: int) -> dict:
        fl, m = self.fl, self.config["model"]
        k = fl["clients_per_round"]
        d = padded(param_count(m))
        return {"rounds": units,
                "flops_per_round": flops_per_round(
                    m, k, fl["local_iters"], fl["local_batch_size"],
                    fl["eval_size"]),
                "gp_calls": units if self.workload["selector"] == "gpfl"
                else 0,
                "gp_k": k, "gp_d": d}

    def release(self) -> None:
        # the last dispatch's GP scores, for the run-end comparison
        self.last_gp = np.asarray(self.engine.final_carry.latest_gp)
        self.engine = None

    def check(self) -> dict:
        lim = self.workload["limits"]
        got = self.readings()
        return {k: {"value": got[k], "limit": lim[k]} for k in lim}

    def _reference(self, **kw) -> dict:
        from bench.reference.fl import reference_rounds
        return reference_rounds(self.config, self.workload["selector"],
                                self.seed, self.x_tab, self.y_tab,
                                self.eval_x, self.eval_y,
                                self.fl["rounds"], **kw)

    def readings(self, **ref_kw) -> dict:
        """The compared numbers of the last dispatch against the reference
        following its cohorts (or, with ``ref_kw``, of the reference in
        another form put in the program's place: the control, a planted
        fault)."""
        from bench.reference.fl import compare
        if ref_kw:
            prog = self._reference(**ref_kw)
        else:  # set-up's dispatch where no window ran
            prog = dict(self.last if self.last is not None else self.first)
            if self.workload["selector"] == "gpfl":
                prog["latest"] = self.last_gp
        ref = self._reference(follow=prog["ids"])
        return compare(prog, ref, self.fl["clients_per_round"],
                       self.workload.get("selection_rounds"))

    def control_readings(self) -> dict:
        """The reference at bfloat16, the precision below the
        configuration's float32, in the program's place."""
        import jax.numpy as jnp
        return self.readings(dtype=jnp.bfloat16, precision="default")

    def fault_readings(self) -> dict:
        """Faults planted in the reference put in the program's place: a
        state left unchanged, half of the round's cohort and half of each
        client's local batch left out, a cohort altered where it is
        chosen, and (GPFL) the GP scores read over half the parameters."""
        faults = ["unchanged", "half_cohort", "half_local_batch", "answer"]
        if self.workload["selector"] == "gpfl":
            faults.append("gp_half")
        return {f: self.readings(fault=f) for f in faults}
