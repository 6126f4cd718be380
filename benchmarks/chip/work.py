"""The work a window step must do, from the published shapes and the
useful tokens only.

A step's useful positions are the prompt tokens it fed plus the rows it
decoded; its emitted tokens are the positions whose logits a client
receives (a row's last prompt chunk, and every decode).  The trunk's
work is counted per useful position, the head's per emitted token, and
attention per key each position attends to.  Pad positions of the
window and padding rows of the vocabulary are never counted, so a change
that stops computing them is read against the same work, and no share
can pass 100% because a count went stale.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

KV_BYTES = 2        # bf16 K/V, as the configuration serves them
WEIGHT_BYTES = 2    # bf16: the least a digital step can read per weight


def linears(conf) -> Tuple[List[Tuple[str, int, int]], Tuple[int, int]]:
    """(name, K, N) of one layer's linears at published widths, and the
    head's (K, N)."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    layer = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
             ("wi", d, ff), ("wg", d, ff), ("wo_mlp", ff, d)]
    return layer, (d, conf["vocab_size"])


def trunk_weights(conf) -> int:
    layer, _ = linears(conf)
    return conf["num_hidden_layers"] * sum(k * n for _, k, n in layer)


def head_weights(conf) -> int:
    _, (k, n) = linears(conf)
    return k * n


def step_flops(conf, positions: int, context: int, tokens: int) -> float:
    """Model FLOPs of one step: 2 per weight per useful position (head:
    per emitted token) plus QK^T and PV over each position's keys."""
    attn = 4 * conf["num_attention_heads"] * conf["head_dim"] * context
    return (2.0 * trunk_weights(conf) * positions
            + 2.0 * head_weights(conf) * tokens
            + conf["num_hidden_layers"] * attn)


def step_bytes(conf, positions: int, context: int) -> float:
    """Least HBM bytes of one step: every weight once at bf16, the KV of
    every attended key read and of every useful position written."""
    kv = (2 * conf["num_key_value_heads"] * conf["head_dim"] * KV_BYTES
          * conf["num_hidden_layers"])
    return (WEIGHT_BYTES * (trunk_weights(conf) + head_weights(conf))
            + kv * (context + positions))


def least_step_seconds(conf, steps, peaks: Dict[str, float]) -> float:
    """Sum over ``steps`` of the larger of compute and memory bound."""
    return sum(max(step_flops(conf, s.positions, s.context, s.tokens)
                   / peaks["bf16_flops"],
                   step_bytes(conf, s.positions, s.context)
                   / peaks["hbm_bytes_s"]) for s in steps)
