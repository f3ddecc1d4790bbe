"""The benchmark's one traffic generator: data, seeds and batches from a seed.

Copied from the program's generators (``repro.data.synthetic.make_fmnist_like``
and ``make_lm_tokens``, ``repro.federated.partition.sorted_label_shards``,
``repro.launch.train.lm_batches``) so that a change to the program cannot
move the yardstick. Everything here is a pure function of its arguments and
the run's ``--seed``; a traffic file under ``traffic/`` only chooses the
parameters.
"""
from __future__ import annotations

import numpy as np


def seed_stream(seed: int, *tag: int) -> np.random.Generator:
    """A numpy generator for one purpose of one run: any whole ``seed``
    (negative or beyond 64 bits too) and a tag that names the purpose."""
    return np.random.default_rng(
        np.random.SeedSequence([abs(int(seed)), int(seed < 0), *tag]))


def int31(seed: int, *tag: int, size=None):
    """Whole numbers in [0, 2**31) drawn from ``seed``: the seeds handed to
    the program, whose PRNG keys take 32-bit integers."""
    return seed_stream(seed, *tag).integers(0, 2**31, size=size)


def fmnist_like(num_train: int, num_test: int, num_classes: int, dim: int,
                seed: int, noise: float = 0.30, difficulty_spread: float = 1.0):
    """(x_train, y_train, x_test, y_test): the FMNIST-shaped synthetic set.

    Class prototypes on a sphere, each leaning toward its neighbour, with a
    per-class noise level that makes later classes harder (the asymmetry the
    robust methods exploit). Every class has the same count in each split.
    """
    rng = seed_stream(seed, 0)
    protos = rng.normal(size=(num_classes, dim)).astype(np.float32)
    protos /= np.linalg.norm(protos, axis=1, keepdims=True)
    frac = np.arange(num_classes) / max(num_classes - 1, 1)
    overlap = 0.1 + 0.35 * frac
    protos = ((1 - overlap[:, None]) * protos
              + overlap[:, None] * np.roll(protos, 1, axis=0))
    cls_noise = (noise * (1.0 + difficulty_spread * (frac - 0.5))).astype(
        np.float32)

    def draw(n, tag):
        r = seed_stream(seed, tag)
        y = np.repeat(np.arange(num_classes), n // num_classes).astype(
            np.int32)
        r.shuffle(y)
        x = protos[y] + cls_noise[y][:, None] * r.standard_normal(
            size=(n, dim), dtype=np.float32)
        return x.astype(np.float32), y

    x_tr, y_tr = draw(num_train, 1)
    x_te, y_te = draw(num_test, 2)
    return x_tr, y_tr, x_te, y_te


def sorted_label_shards(x: np.ndarray, y: np.ndarray, num_clients: int):
    """The paper's partition (§IV-A): sort by label, cut into equal
    contiguous shards. Returns [N, S, ...] and [N, S]."""
    order = np.argsort(y, kind="stable")
    usable = (len(y) // num_clients) * num_clients
    xs, ys = x[order][:usable], y[order][:usable]
    return (xs.reshape(num_clients, -1, *x.shape[1:]),
            ys.reshape(num_clients, -1))


def client_data(data_cfg: dict, num_clients: int, seed: int):
    """The clients' shards ``(x [N, S, D], y [N, S], x_test, y_test)`` as
    host arrays, the way a user's script hands them to ``run_sweep``."""
    x, y, xt, yt = fmnist_like(data_cfg["train"], data_cfg["test"],
                               data_cfg["num_classes"], data_cfg["dim"], seed)
    xs, ys = sorted_label_shards(x, y, num_clients)
    xts, yts = sorted_label_shards(xt, yt, num_clients)
    return xs, ys, xts, yts


def lm_corpus(num_clients: int, tokens_per_client: int, vocab_size: int,
              heterogeneity: float, seed: int) -> np.ndarray:
    """[N, tokens] int32: each client draws from its own Zipf-permuted
    unigram mixture (the language-model analogue of label skew)."""
    rng = seed_stream(seed, 10)
    base = 1.0 / np.arange(1, vocab_size + 1) ** 1.1
    base /= base.sum()
    out = np.empty((num_clients, tokens_per_client), dtype=np.int32)
    for c in range(num_clients):
        perm = seed_stream(seed, 11, c).permutation(vocab_size)
        mix = (1 - heterogeneity) * base + heterogeneity * base[perm]
        mix /= mix.sum()
        out[c] = rng.choice(vocab_size, size=tokens_per_client, p=mix)
    return out


def lm_batches(corpus: np.ndarray, rows_per_client: int, seq: int,
               count: int, seed: int) -> list[dict]:
    """``count`` host batches; in each, client c owns rows
    [c·rows, (c+1)·rows) of ``tokens`` (the canonical block layout), every
    row a window of that client's corpus at an offset drawn from the seed.
    Labels are the tokens (the model shifts them)."""
    n, tlen = corpus.shape
    rng = seed_stream(seed, 12)
    cids = np.repeat(np.arange(n, dtype=np.int32), rows_per_client)
    out = []
    for _ in range(count):
        offs = rng.integers(0, tlen - seq - 1, size=n * rows_per_client)
        toks = np.stack([corpus[c, o:o + seq] for c, o in zip(cids, offs)])
        out.append({"tokens": toks, "labels": toks.copy(),
                    "client_ids": cids.copy()})
    return out
