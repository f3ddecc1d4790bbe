"""Tier driver: the simulator's sweep engine, ``repro.core.sweep.run_sweep``.

The window calls ``run_sweep`` over the traffic's grid again and again, as a
user's script does: host arrays in, a new set of simulation seeds per call,
every call waited for. Set-up makes the data from the seed and makes one
call on seeds of its own, which traces and compiles every group.

``correct``: once the window has closed, a sample of its calls drawn from
the seed is replayed by the configuration's plain reference, every cell of
each sampled call over its first rounds (the traffic's ``check.rounds``, up
to the first selection within rounding of a tie), and the histories are
compared round by round, with the mean signed gap of the loss besides (see
``Driver.check``).
"""
from __future__ import annotations

import time
from dataclasses import replace

import jax
import numpy as np

import traffic as gen
from harness import BENCH, load_module

# the history leaves compared, and how: (leaf, kind)
COMPARED = (("num_scheduled", "abs"), ("energy", "rel"), ("loss", "rel"),
            ("lam", "rel_l2"), ("avg_acc", "abs"))
# the exact-K rules whose selection score holds log λ
LAMBDA_RULES = ("afl", "ca_afl")


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.results = []

    # -- set-up -----------------------------------------------------------
    def fl_config(self, point: dict):
        from repro.configs.base import FLConfig, GCAParams

        c = self.cfg
        base = FLConfig(
            num_clients=c["num_clients"],
            clients_per_round=c["clients_per_round"], rounds=c["rounds"],
            batch_size=c["batch_size"], lr0=c["lr0"], lr_decay=c["lr_decay"],
            ascent_lr=c["ascent_lr"], local_steps=1,
            num_subcarriers=c["num_subcarriers"], flat_fading=True,
            channel_floor=c["channel_floor"], psi=c["psi"], tau=c["tau"],
            noise_std=0.0, gca=GCAParams(**c["gca"]),
            transport=self.traffic["transport"],
            eval_every=self.traffic["eval_every"])
        kw = {k: v for k, v in point.items() if k != "label"}
        return replace(base, **kw)

    def setup(self):
        from repro.core import sweep
        from repro.models.logreg import logistic_regression

        self.sweep = sweep
        c = self.cfg
        self.data = gen.client_data(c["data"], c["num_clients"], self.seed)
        self.model = logistic_regression(c["data"]["dim"],
                                         c["data"]["num_classes"])
        self.specs = [(p["label"], self.fl_config(p))
                      for p in self.traffic["points"]]
        self.seeds_per_call = self.traffic["seeds_per_call"]
        self.work_per_call = (len(self.specs) * self.seeds_per_call
                              * c["rounds"])
        self._call(seeds=self.call_seeds(-1))

    def call_seeds(self, i: int) -> tuple:
        return tuple(int(s) for s in gen.int31(self.seed, 100, i + 1,
                                               size=self.seeds_per_call))

    def _call(self, seeds):
        with jax.profiler.TraceAnnotation("run_sweep"):
            res = self.sweep.run_sweep(self.model, self.data, self.specs,
                                       seeds=seeds)
            jax.block_until_ready(res.histories)
        return res

    # -- the window -------------------------------------------------------
    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        while True:
            seeds = self.call_seeds(len(self.results))
            self.results.append((seeds, self._call(seeds)))
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        n = len(self.results)
        return {"metrics": {"sim_rounds_per_s":
                            n * self.work_per_call / elapsed},
                "attempted": n, "failed": 0, "seconds": elapsed,
                "work": self.work(n)}

    def work(self, calls: int) -> dict:
        """What ``calls`` calls computed, for the per-layer readers: model
        FLOPs and the AirComp kernels' launches, from ``costs/``."""
        sim = load_module(BENCH / "costs" / "sim_round.py")
        air = load_module(BENCH / "costs" / "aircomp.py")
        flops, kernel_calls = 0.0, []
        groups = {}
        for _, fl in self.specs:
            groups.setdefault(fl.method, []).append(fl)
            flops += (sim.flops_per_seed_round(fl.method, self.cfg)
                      * self.seeds_per_call * fl.rounds)
        for method, fls in groups.items():
            kernel_calls += air.sweep_launches(
                method, fls[0].transport, len(fls) * self.seeds_per_call,
                self.cfg, fls[0].rounds)
        launches = [(*launch[:4], launch[4] * calls)
                    for launch in kernel_calls]
        return {"model_flops": flops * calls, "aircomp_launches": launches,
                "kernel_launches": {"aircomp": sum(launch[4]
                                                   for launch in launches)}}

    def release(self):
        self.model = None

    # -- correct ----------------------------------------------------------
    def check(self, control: str | None = None) -> list:
        """The numbers compared, each with its limit. ``control`` names a
        lower precision in which the reference itself takes the program's
        place (the control that has to fail)."""
        ref = load_module(BENCH / "configs" / f"{self.cfg['name']}.ref.py")
        chk = self.traffic["check"]
        rng = gen.seed_stream(self.seed, 200)
        picks = sorted(rng.choice(len(self.results),
                                  size=min(chk["calls"], len(self.results)),
                                  replace=False).tolist())
        data = jax.device_put(self.data)
        rounds = min(chk["rounds"], self.cfg["rounds"])
        gaps = {leaf: 0.0 for leaf, _ in COMPARED}
        bias, compared = 0.0, 0
        for i in picks:
            seeds, res = self.results[i]
            for (label, fl), hist in zip(self.specs, res.histories,
                                         strict=True):
                point = {"lr0": fl.lr0, "lr_decay": fl.lr_decay,
                         "ascent_lr": fl.ascent_lr, "energy_C": fl.energy_C}
                for r, s in enumerate(seeds):
                    want = ref.first_rounds(self.cfg, fl.method, point, s,
                                            data, rounds)
                    if control:
                        # the bfloat16 reference does not finish on the TPU
                        # (PERF.md, Open questions): it runs on the host
                        with jax.default_device(jax.devices("cpu")[0]):
                            got = ref.first_rounds(self.cfg, fl.method, point,
                                                   s, self.data, rounds,
                                                   control)
                    else:
                        got = {leaf: np.asarray(getattr(hist, leaf))[r]
                               for leaf, _ in COMPARED}
                    margin = self.cfg["tie_margin"][
                        "gca" if fl.method == "gca" else "exact_k"]
                    upto = comparable_rounds(want, got["lam"],
                                             fl.method in LAMBDA_RULES,
                                             margin)
                    compared += upto
                    for leaf, kind in COMPARED:
                        gaps[leaf] = max(gaps[leaf], gap(
                            got[leaf][:upto], want[leaf][:upto], kind))
                    w = want["loss"][:upto].astype(np.float64)
                    bias += float(np.sum((got["loss"][:upto] - w)
                                         / np.maximum(np.abs(w), 1e-30)))
        if not compared:
            raise RuntimeError("no round of the sampled calls could be "
                               "compared")
        limits = self.cfg["limits"]
        vals = {f"{leaf}_{kind}": gaps[leaf] for leaf, kind in COMPARED}
        vals["loss_bias"] = abs(bias) / compared
        return [{"name": k, "value": v, "limit": limits[k]}
                for k, v in vals.items()]


def comparable_rounds(want: dict, got_lam: np.ndarray, on_lam: bool,
                      tie: float) -> int:
    """How many rounds from the first can be compared: those before the
    first round whose selection is within ``tie`` of a tie, in the
    reference or, for a rule that selects on log λ, under the compared
    run's λ of the round before (the reference's other terms of the score
    held). A selection that another rounding could flip does not
    determine what follows."""
    close = want["margin"] < tie
    if on_lam:
        n_rounds, n = want["lam"].shape
        before = np.concatenate([np.full((1, n), 1.0 / n),
                                 np.asarray(got_lam[:n_rounds - 1],
                                            np.float64)])
        scores = np.log(np.maximum(before, 1e-38)) + want["noise"]
        sel = want["mask"] > 0
        in_min = np.min(np.where(sel, scores, np.inf), axis=1)
        out_max = np.max(np.where(sel, -np.inf, scores), axis=1)
        close |= in_min - out_max < tie
    ties = np.flatnonzero(close)
    return int(ties[0]) if ties.size else len(close)


def gap(got: np.ndarray, want: np.ndarray, kind: str) -> float:
    """The widest gap between program and reference over the rounds given:
    absolute, relative, or (λ rows) relative in the L2 norm."""
    if got.size == 0:
        return 0.0
    got, want = got.astype(np.float64), want.astype(np.float64)
    if kind == "abs":
        return float(np.max(np.abs(got - want)))
    if kind == "rel":
        return float(np.max(np.abs(got - want)
                            / np.maximum(np.abs(want), 1e-30)))
    num = np.linalg.norm(got - want, axis=-1)
    return float(np.max(num / np.maximum(np.linalg.norm(want, axis=-1),
                                         1e-30)))
