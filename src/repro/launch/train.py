"""Federated training launcher (production tier).

Runs CA-AFL rounds of a (possibly reduced) assigned architecture on whatever
mesh the host provides — the same code path the dry-run lowers for the
production mesh. Each mesh ``data`` slice hosts one client; batches are
assembled from a synthetic heterogeneous LM corpus (offline container).

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2-0.5b --reduced --rounds 50 --method ca_afl --C 8
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.configs.base import FLConfig
from repro.data.synthetic import make_lm_tokens
from repro.federated.server import ParameterServer, ServerState
from repro.models.api import build_model
from repro.optim import sgd, adamw
from repro.utils.compile_cache import enable_compile_cache


def lm_batches(corpus: np.ndarray, batch_per_client: int, seq: int,
               cfg, seed: int = 0):
    """Infinite batches: every client contributes batch_per_client rows."""
    n, tlen = corpus.shape
    rng = np.random.default_rng(seed)
    while True:
        toks, cids = [], []
        for c in range(n):
            for _ in range(batch_per_client):
                off = rng.integers(0, tlen - seq - 1)
                toks.append(corpus[c, off:off + seq])
                cids.append(c)
        batch = {
            "tokens": jnp.asarray(np.stack(toks)),
            "labels": jnp.asarray(np.stack(toks)),
            "client_ids": jnp.asarray(np.array(cids, np.int32)),
        }
        b = len(toks)
        if cfg.family == "vlm":
            batch["images"] = jnp.zeros(
                (b, cfg.num_image_tokens, cfg.d_model), jnp.float32)
        if cfg.family == "audio":
            batch["audio"] = jnp.zeros(
                (b, cfg.num_audio_frames, cfg.d_model), jnp.float32)
        yield batch


def main(argv=None) -> ServerState:
    """Parse ``argv`` (default: ``sys.argv[1:]``), train, return the final
    server state (callers in-process read ``state.history``)."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch-per-client", type=int, default=2)
    ap.add_argument("--method", default="ca_afl",
                    choices=["ca_afl", "afl", "fedavg", "greedy"])
    ap.add_argument("--C", type=float, default=8.0)
    ap.add_argument("--noise-std", type=float, default=1e-3)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--server-opt", default="sgd", choices=["sgd", "adamw"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    cfg = cfg.with_(dtype="float32", remat=False)
    model = build_model(cfg)
    fl = FLConfig(num_clients=args.clients, clients_per_round=args.k,
                  rounds=args.rounds, method=args.method, energy_C=args.C,
                  noise_std=args.noise_std, seed=args.seed)
    opt = adamw(args.lr) if args.server_opt == "adamw" else sgd(args.lr)

    print(f"arch={cfg.name} reduced={args.reduced} method={fl.method} "
          f"C={fl.energy_C} N={fl.num_clients} K={fl.clients_per_round}")
    ps = ParameterServer(model, opt, fl, seed=args.seed)
    state = ps.init_state(jax.random.PRNGKey(args.seed))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(state.params))
    print(f"params: {n_params:,}")

    corpus = make_lm_tokens(args.clients, max(8 * args.seq, 4096),
                            cfg.vocab_size, seed=args.seed)
    t0 = time.time()
    state = ps.run(state, lm_batches(corpus, args.batch_per_client, args.seq,
                                     cfg, args.seed),
                   rounds=args.rounds, log_every=max(args.rounds // 10, 1))
    dt = time.time() - t0
    print(f"{args.rounds} rounds in {dt:.1f}s "
          f"({dt / args.rounds:.2f} s/round); total E = "
          f"{state.energy_joules:.3e} J")
    if args.out:
        Path(args.out).write_text(json.dumps(state.history, indent=2))
        print(f"history -> {args.out}")
    return state


if __name__ == "__main__":
    main()
