"""Tier driver: the model tier's ``repro.federated.server.ParameterServer``.

Set-up builds one server and its state from the seed: the weights are made
by the benchmark on the device in one jitted call, in the program's own
parameter layout (read from the model's abstract shapes), and a pool of
distinct batches is made on the host. The first rounds go through the
window's own feed (a host-to-device copy of the next batch, then
``ParameterServer.step``, waited for), so they compile every program the
window uses and leave the readings that ``correct`` compares; the same
server and state then run the window.

``correct``: once the window has closed and the server is freed, the
configuration's plain reference replays from the seed those first rounds and
on to a round of the window drawn from the seed (one of its first
``check.window_rounds``). Compared: each replayed round's descent loss,
energy and scheduled count; λ after each first round; the per-leaf norms of
the first round's gradient as the optimizer receives it (``(w0 - w1)/lr``
for SGD), and of the change of the parameters over the first rounds (see
``leaf_gap``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import traffic as gen
from harness import BENCH, load_module


def make_params(shapes, key):
    """Weights for the program's parameter tree: N(0, 0.02) matrices and
    biases, 1 + N(0, 0.02) norm scales (every leaf whose name ends in
    ``norm``)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(paths))
    out = []
    for (path, sd), k in zip(paths, keys, strict=True):
        z = 0.02 * jax.random.normal(k, sd.shape, jnp.float32)
        if str(getattr(path[-1], "key", "")).endswith("norm"):
            z = z + 1.0
        out.append(z.astype(sd.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


@jax.jit
def _diff_norms(a, b):
    return jnp.stack([jnp.linalg.norm((x - y).ravel()) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b),
        strict=True)])


def leaf_norms(tree_a, tree_b, scale: float = 1.0) -> np.ndarray:
    """Per-leaf L2 norms of ``a - b``, times ``scale``."""
    return np.asarray(_diff_norms(tree_a, tree_b)) * scale


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.fl = dict(config["fl"], energy_C=traffic["energy_C"])
        self.window_rows = []

    def program_config(self):
        """The program's model configuration, held to the file's widths."""
        from repro.configs import get_config

        c = self.cfg
        mc = get_config(c["program_config"]).with_(
            dtype=c["dtype"], remat=False,
            norm_eps=c["model"]["rms_norm_eps"])
        have = {"num_hidden_layers": mc.num_layers,
                "hidden_size": mc.d_model,
                "num_attention_heads": mc.num_heads,
                "num_key_value_heads": mc.num_kv_heads,
                "head_dim": mc.resolved_head_dim,
                "intermediate_size": mc.d_ff, "vocab_size": mc.vocab_size,
                "rope_theta": mc.rope_theta, "rms_norm_eps": mc.norm_eps}
        for key, value in have.items():
            if c["model"][key] != value:
                raise ValueError(f"the program runs {key}={value}, the "
                                 f"configuration file says {c['model'][key]}")
        return mc

    def setup(self):
        from repro.configs.base import FLConfig
        from repro.federated.server import ParameterServer, ServerState
        from repro.models.api import build_model
        from repro.optim import sgd

        fl, m = self.fl, self.cfg["model"]
        model = build_model(self.program_config())
        self.shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        self.model_size = sum(int(np.prod(s.shape))
                              for s in jax.tree_util.tree_leaves(self.shapes))
        self.ps_seed = int(gen.int31(self.seed, 300))
        self.w_key = jax.random.PRNGKey(int(gen.int31(self.seed, 301)))
        self._init = jax.jit(lambda k: make_params(self.shapes, k))
        flc = FLConfig(
            num_clients=fl["num_clients"],
            clients_per_round=fl["clients_per_round"], rounds=1,
            method=self.traffic["method"], energy_C=fl["energy_C"],
            noise_std=fl["noise_std"], ascent_lr=fl["ascent_lr"],
            num_subcarriers=fl["num_subcarriers"],
            channel_floor=fl["channel_floor"], psi=fl["psi"], tau=fl["tau"],
            transport=self.traffic["transport"])
        self.ps = ParameterServer(model, sgd(fl["lr"]), flc,
                                  seed=self.ps_seed)
        params = self._init(self.w_key)
        self.state = ServerState(
            params=params, opt_state=self.ps.optimizer.init(params),
            lam=jnp.full((fl["num_clients"],), 1.0 / fl["num_clients"]))
        corpus = gen.lm_corpus(fl["num_clients"],
                               self.traffic["tokens_per_client"],
                               m["vocab_size"], self.traffic["heterogeneity"],
                               self.seed)
        self.batches = gen.lm_batches(
            corpus, fl["rows_per_client"], fl["seq_len"],
            self.traffic["distinct_batches"], self.seed)
        self.fed = 0
        # the first rounds: warm-up and the readings `correct` compares
        first = self.traffic["check"]["rounds"]
        self.lams = []
        for r in range(first):
            self._feed()
            self.lams.append(np.asarray(self.state.lam))
            if r == 0:
                self.grad_norms = leaf_norms(self._init(self.w_key),
                                             self.state.params,
                                             1.0 / fl["lr"])
        self.change_norms = leaf_norms(self.state.params,
                                       self._init(self.w_key))
        self.first_rows = list(self.state.history[:first])

    def _feed(self) -> float:
        t0 = time.perf_counter()
        batch = self.batches[self.fed % len(self.batches)]
        with jax.profiler.TraceAnnotation("h2d_batch"):
            batch = jax.device_put(batch)
        with jax.profiler.TraceAnnotation("ps_step"):
            self.state = self.ps.step(self.state, batch)
            jax.block_until_ready(self.state.params)
        self.fed += 1
        return time.perf_counter() - t0

    # -- the window -------------------------------------------------------
    def window(self, seconds: float) -> dict:
        fl = self.fl
        start = len(self.state.history)
        times = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            times.append(self._feed())
        elapsed = time.perf_counter() - t0
        rows = self.state.history[start:]
        self.window_rows = rows[:self.traffic["check"]["window_rounds"]]
        tokens = sum(r["num_scheduled"] for r in rows) \
            * fl["rows_per_client"] * fl["seq_len"]
        costs = load_module(BENCH / "costs" / "ps_step.py")
        return {"metrics": {"ps_tokens_per_s": tokens / elapsed,
                            "ps_round_ms_p95":
                                float(np.percentile(times, 95)) * 1e3},
                "attempted": len(times), "failed": 0, "seconds": elapsed,
                "work": {"model_flops": len(times) * costs.flops_per_step(
                    self.cfg["model"], fl)}}

    def release(self):
        self.state = self.ps = None

    # -- correct ----------------------------------------------------------
    def check(self, control: str | None = None) -> list:
        ref = load_module(BENCH / "configs" / f"{self.cfg['name']}.ref.py")
        n = self.traffic["check"]["rounds"]
        # the window's round that is replayed besides, drawn from the seed
        j = int(gen.seed_stream(self.seed, 400).integers(
            len(self.window_rows))) if self.window_rows else -1
        key = jax.random.PRNGKey(self.ps_seed)
        p0 = self._init(self.w_key)
        dtype = control or "float32"

        def replay(dt):
            first, p, lam, k = ref.first_rounds(
                self.cfg["model"], self.fl, self._init(self.w_key), key,
                self.batches[:n], self.model_size, dt)
            change = leaf_norms(
                jax.tree.map(lambda x: x.astype(jnp.float32), p), p0)
            later, p, _, _ = ref.first_rounds(
                self.cfg["model"], self.fl, p, k,
                self.batches[n:n + j + 1], self.model_size, dt, lam)
            del p
            return first, later, change

        want, want_later, want_change = replay("float32")
        if control:
            got, got_later, got_change = replay(dtype)
            got_rows = [{"loss": g["loss"], "energy_j": g["energy"],
                         "num_scheduled": g["num_scheduled"]}
                        for g in got + got_later]
            got_lams = [g["lam"] for g in got]
            got_grad = got[0]["grad_norms"]
        else:
            got_rows = self.first_rows + self.window_rows[:j + 1]
            got_lams = self.lams
            got_grad, got_change = self.grad_norms, self.change_norms
        want_rows = want + want_later
        limits = self.cfg["limits"]
        pure = want[0]["pure_grad_norms"]
        # leaves whose gradient is nought to rounding in the reference move
        # by round-off alone: not compared
        live = pure >= 1e-3 * np.median(pure)
        vals = {
            "loss_rel": max(_rel(g["loss"], w["loss"]) for g, w in
                            zip(got_rows, want_rows, strict=True)),
            "energy_rel": max(_rel(g["energy_j"], w["energy"]) for g, w in
                              zip(got_rows, want_rows, strict=True)),
            "num_scheduled_abs": max(
                abs(float(g["num_scheduled"]) - float(w["num_scheduled"]))
                for g, w in zip(got_rows, want_rows, strict=True)),
            "lam_rel_l2": max(
                float(np.linalg.norm(np.asarray(gl, np.float64) - w["lam"])
                      / np.linalg.norm(w["lam"]))
                for gl, w in zip(got_lams, want, strict=True)),
            "grad_leaf_gap": leaf_gap(got_grad, want[0]["grad_norms"], live),
            "change_leaf_gap": leaf_gap(got_change, want_change, live),
        }
        return [{"name": k, "value": v, "limit": limits[k]}
                for k, v in vals.items()]


def _rel(got, want) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), 1e-30)


def leaf_gap(got: np.ndarray, want: np.ndarray, live: np.ndarray) -> float:
    """The worst leaf's gap between the program's norm and the reference's,
    against the larger of the reference's norm of that leaf and of the
    median leaf."""
    floor = np.median(want[live])
    gaps = np.abs(got - want) / np.maximum(want, floor)
    return float(np.max(gaps[live]))
