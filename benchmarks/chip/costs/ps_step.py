"""Model FLOPs of one ``ParameterServer.step`` on a dense decoder.

Counted from the configuration's published widths:

- the matmul parameters per token: per layer the Q, K, V and O projections
  and the three SwiGLU matrices, plus the output head once (``D·V`` with
  the real vocabulary; counted once whether or not it is tied to the
  embedding, whose lookup is no product);
- the descent (the K scheduled clients' rows): forward and backward,
  ``6·P`` per token, plus causal attention's two score products, three times
  their forward count;
- the ascent probe (every client's rows at the new model): forward only,
  ``2·P`` per token, plus attention's forward.

Recomputation is not counted. The products are f32 at JAX's default TPU
precision, one bfloat16 pass on the MXU, so the bfloat16 peak is the one
they are held against.
"""
from __future__ import annotations


def matmul_params(m: dict) -> float:
    d, f, hd = m["hidden_size"], m["intermediate_size"], m["head_dim"]
    h, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    per_layer = d * h * hd * 2 + d * hkv * hd * 2 + 3 * d * f
    return float(m["num_hidden_layers"] * per_layer
                 + d * m["vocab_size"])


def attention_fwd_per_sequence(m: dict, seq: int) -> float:
    """QKᵀ and PV over the causal triangle, all layers, one sequence."""
    pairs = seq * (seq + 1) / 2
    return (m["num_hidden_layers"] * 2 * 2 * m["num_attention_heads"]
            * m["head_dim"] * pairs)


def flops_per_step(m: dict, fl: dict) -> float:
    rows, seq = fl["rows_per_client"], fl["seq_len"]
    desc_rows = fl["clients_per_round"] * rows
    probe_rows = fl["num_clients"] * rows
    p = matmul_params(m)
    att = attention_fwd_per_sequence(m, seq)
    return (6 * p * desc_rows * seq + 3 * att * desc_rows
            + 2 * p * probe_rows * seq + att * probe_rows)
