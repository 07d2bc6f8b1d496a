"""Plain reference lowering of a mixture-of-experts decoder to GEMM rows.

Reads the Hugging Face `config.json` keys of the configuration file. Per
layer: the Q, K, V and O projections; attention scores and the weighted
sum of values as one GEMM per (sequence, query head), serialized on the
array like grouped convolutions; a router over all experts; and SwiGLU
experts (gate and up, then down), each expert seeing its expected share
T * top_k / E of the T tokens in flight. One unembedding row per
sequence closes the pass. Decode steps one token per sequence against a
KV span of `seq` tokens; prefill runs all `seq` tokens.
Rows are (M, K, N, groups, repeats).
"""
from __future__ import annotations


def lower(cfg, shape):
    """GEMM rows of one step of `shape` = (phase, batch, seq)."""
    phase, B, S = shape
    d = cfg["hidden_size"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    L, E, k = (cfg["num_hidden_layers"], cfg["num_experts"],
               cfg["num_experts_per_tok"])
    ff = cfg["intermediate_size"]
    if phase == "decode":
        sq, T = 1, B
    elif phase == "prefill":
        sq, T = S, B * S
    else:
        raise ValueError(f"unknown phase {phase!r}")
    per_expert = max(1, T * k // E)
    return [
        (T, d, heads * hd, 1, L),                 # Q
        (T, d, kv_heads * hd, 1, 2 * L),          # K, V
        (T, d, d, 1, L),                          # O
        (sq, hd, S, B * heads, L),                # scores
        (sq, S, hd, B * heads, L),                # weights @ V
        (T, d, E, 1, L),                          # router
        (per_expert, d, ff, E, 2 * L),            # expert gate, up
        (per_expert, ff, d, E, L),                # expert down
        (B, d, cfg["vocab_size"], 1, 1),          # unembedding
    ]
