"""Operations and bytes that the algorithm needs, computed from shapes.

These are the yardstick's counts, kept with the benchmark so that no
change to the program can alter them.  They count the work of the
model as published, not what the compiled program happens to do:
recomputation under remat is not counted, and a decode step reads the
keys and values of the positions filled so far, not the cache's length.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2, "int8": 1}


def dense_param_count(c: dict) -> int:
    """Parameters of a Llama-style decoder with tied or untied
    embeddings, from the keys of a Hugging Face ``config.json``."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    hd = c.get("head_dim") or d // c["num_attention_heads"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    per_layer = 2 * d + d * q + 2 * d * kv + q * d + 3 * d * f
    head = 0 if c["tie_word_embeddings"] else d * v
    return v * d + head + c["num_hidden_layers"] * per_layer + d


def attn_width(c: dict) -> int:
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return c["num_attention_heads"] * hd


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """6 N for the weights (forward and backward) plus causal attention,
    6 L S d_attn: 2 matmuls of 2 S d_attn per token forward, halved by
    the causal mask, times 3 for forward and backward."""
    return (6.0 * dense_param_count(c)
            + 6.0 * c["num_hidden_layers"] * seq_len * attn_width(c))


def decode_flops_per_token(c: dict, context: float) -> float:
    """2 N for the weights plus scores and values over ``context``
    cached positions: 2 matmuls of 2 context d_attn per layer."""
    return (2.0 * dense_param_count(c)
            + 4.0 * c["num_hidden_layers"] * context * attn_width(c))


def decode_bytes_per_step(c: dict, batch: int, context: float) -> float:
    """Bytes a decode step must read from HBM: every parameter once, and
    the keys and values of ``context`` filled positions of each of
    ``batch`` sequences."""
    pb = DTYPE_BYTES[c["param_dtype"]]
    kb = DTYPE_BYTES[c["cache_dtype"]]
    hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    kv = (2 * c["num_hidden_layers"] * batch * context
          * c["num_key_value_heads"] * hd * kb)
    return pb * dense_param_count(c) + kv


def mean_context(prompt_len: int, new_tokens: int) -> float:
    """Mean number of cached positions a decode step attends over when
    ``new_tokens`` are decoded after a ``prompt_len`` prompt: the step
    that makes token j (1-based) attends over prompt_len + j - 1 + 1."""
    return prompt_len + (new_tokens - 1) / 2.0
