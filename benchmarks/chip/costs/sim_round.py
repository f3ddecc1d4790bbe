"""Model FLOPs of one seed-round of the simulator (logistic regression).

Counted from shapes. One forward of a batch of ``b`` rows is ``2·b·D·C``;
its backward needs only the weight gradient (the input is data), another
``2·b·D·C``. Per seed-round:

- exact-K methods (fedavg, afl, ca_afl): the K scheduled clients' local
  step (forward + backward), the K ascent clients' losses and the K
  scheduled clients' loss metric (forwards);
- GCA: the probe's forward + backward for all N clients (it doubles as the
  local step) and the ascent losses of all N clients (forwards);
- every method: the test-set eval of every client (a forward over all test
  rows).

Bias additions and softmax are left out. The FLOPs are f32 products at
JAX's default TPU precision, which runs them as one bfloat16 pass on the
MXU, so the peak they are held against is the bfloat16 peak.
"""
from __future__ import annotations


def flops_per_seed_round(method: str, cfg: dict) -> float:
    d, c = cfg["data"]["dim"], cfg["data"]["num_classes"]
    n, k, b = cfg["num_clients"], cfg["clients_per_round"], cfg["batch_size"]
    fwd = 2.0 * b * d * c
    if method == "gca":
        train = n * (2 * fwd) + n * fwd
    else:
        train = k * (2 * fwd) + 2 * k * fwd
    return train + 2.0 * cfg["data"]["test"] * d * c
