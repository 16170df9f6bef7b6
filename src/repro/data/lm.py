"""Synthetic LM token pipeline: deterministic Zipf streams per client.

Used by the LM training examples and smoke tests (no corpora ship offline).
Markov structure gives the model something learnable; per-client seeds give
federated non-IIDness (each client = its own topic mixture).
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def zipf_tokens(rng: np.random.Generator, n: int, vocab: int, alpha: float = 1.2):
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    p /= p.sum()
    return rng.choice(vocab, size=n, p=p).astype(np.int32)


def markov_tokens(rng: np.random.Generator, n: int, vocab: int,
                  order_bias: float = 0.7):
    """Learnable stream: next token = f(prev) w.p. order_bias else Zipf."""
    base = zipf_tokens(rng, n, vocab)
    perm = rng.permutation(vocab)
    out = base.copy()
    follow = rng.random(n) < order_bias
    out[1:][follow[1:]] = perm[out[:-1][follow[1:]]] % vocab
    return out


def lm_batches(seed: int, n_steps: int, global_batch: int, seq_len: int,
               vocab: int) -> Iterator[Dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    for _ in range(n_steps):
        toks = markov_tokens(rng, global_batch * (seq_len + 0), vocab)
        yield {"tokens": toks.reshape(global_batch, seq_len)}


def client_lm_batches(seed: int, client_id: int, steps: int, batch: int,
                      seq_len: int, vocab: int) -> Dict[str, np.ndarray]:
    """(steps, batch, seq) stack for one federated client."""
    rng = np.random.default_rng(seed * 100003 + client_id)
    toks = markov_tokens(rng, steps * batch * seq_len, vocab)
    return {"tokens": toks.reshape(steps, batch, seq_len)}


# sequences each client holds of its own stream (the default round batch)
CLIENT_ROWS = 8


def client_rows(seed: int, seq_len: int, vocab: int,
                n_rows: int = CLIENT_ROWS):
    """A round-batch function ``(rng, clients) -> (len(clients), seq_len)``
    int32 tokens: row r is one of client ``clients[r]``'s ``n_rows``
    sequences of its own stream (``client_lm_batches(seed, c, n_rows, 1,
    ...)``), the sequence drawn from ``rng`` in row order. A client's
    sequences are made the first time it is seen and kept."""
    streams: Dict[int, np.ndarray] = {}

    def rows(rng: np.random.Generator, clients) -> np.ndarray:
        idx = rng.integers(0, n_rows, size=len(clients))
        out = []
        for c, i in zip(clients, idx):
            c = int(c)
            if c not in streams:
                streams[c] = client_lm_batches(seed, c, n_rows, 1, seq_len,
                                               vocab)["tokens"][:, 0]
            out.append(streams[c][i])
        return np.stack(out)
    return rows
