"""Token traffic made from a seed, on the device.

A copy of the program's ``data.pipeline.DataPipeline`` arithmetic, kept
with the benchmark so that no change to the program alters its inputs:
a Zipf-like token marginal (``floor(exp(u log(V - 2))) + 1``, rank
frequency about 1/rank), documents cut by BOS at a geometric rate of
1/``mean_doc_len``, and labels masked (-100) at the end of each row and
where the next token opens a document.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _row(key, seq_len: int, vocab: int, mean_doc_len: int, bos: int):
    k1, k2 = jax.random.split(key)
    u = jax.random.uniform(k1, (seq_len,), jnp.float32)
    toks = jnp.exp(u * np.log(vocab - 2)).astype(jnp.int32) + 1
    is_bos = jax.random.uniform(k2, (seq_len,), jnp.float32) \
        < 1.0 / mean_doc_len
    return jnp.clip(jnp.where(is_bos, bos, toks), 0, vocab - 1)


def batch(key, rows: int, seq_len: int, vocab: int, mean_doc_len: int,
          bos: int) -> dict:
    """{"tokens", "labels"}: [rows, seq_len] int32 each."""
    toks = jax.vmap(lambda k: _row(k, seq_len, vocab, mean_doc_len, bos))(
        jax.random.split(key, rows))
    labels = jnp.concatenate(
        [toks[:, 1:], jnp.full((rows, 1), -100, jnp.int32)], 1)
    return {"tokens": toks, "labels": jnp.where(labels == bos, -100, labels)}


def batches(key, n: int, rows: int, seq_len: int, vocab: int,
            mean_doc_len: int, bos: int) -> tuple:
    """``n`` distinct batches (jit it: one device call)."""
    return tuple(batch(jax.random.fold_in(key, i), rows, seq_len, vocab,
                       mean_doc_len, bos) for i in range(n))
