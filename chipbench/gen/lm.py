"""Federated LM token streams, generated on the host from a seed.

A copy of the program's ``repro.data.lm`` client streams (the original
stays where it is), kept here so that no change to the program can change
what the benchmark feeds it. Each client has its own Markov-Zipf stream:
Zipf-distributed tokens, each following its predecessor through the
client's own permutation with probability 0.7 (its "topic"). A client
holds ``rows`` sequences of its stream; a round draws one of them per row
from the round's generator. Its weight k (the sequences it would train on,
50 to 399) sets its training time on the PON and its aggregation weight.
"""
from __future__ import annotations

import numpy as np


def zipf_tokens(rng: np.random.Generator, n: int, vocab: int,
                alpha: float = 1.2):
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    p /= p.sum()
    return rng.choice(vocab, size=n, p=p).astype(np.int32)


def markov_tokens(rng: np.random.Generator, n: int, vocab: int,
                  order_bias: float = 0.7):
    """Next token = perm[previous] with probability ``order_bias``, else
    a fresh Zipf draw."""
    base = zipf_tokens(rng, n, vocab)
    perm = rng.permutation(vocab)
    out = base.copy()
    follow = rng.random(n) < order_bias
    out[1:][follow[1:]] = perm[out[:-1][follow[1:]]] % vocab
    return out


def client_stream(seed: int, client: int, rows: int, seq: int,
                  vocab: int) -> np.ndarray:
    """(rows, seq) int32: the client's first ``rows`` sequences."""
    rng = np.random.default_rng(seed * 100003 + client)
    return markov_tokens(rng, rows * seq, vocab).reshape(rows, seq)


def generate(n_clients: int, seed: int, rows: int, seq: int, vocab: int):
    """``(streams, counts)``: (n_clients, rows, seq) int32 and the
    clients' weights, (n_clients,) float32."""
    counts = np.random.default_rng(seed).integers(50, 400, n_clients)
    streams = np.stack([client_stream(seed, c, rows, seq, vocab)
                        for c in range(n_clients)])
    return streams, counts.astype(np.float32)


def client_rows(rng: np.random.Generator, streams: np.ndarray,
                clients) -> np.ndarray:
    """(len(clients), seq): one sequence of each client, in row order."""
    idx = rng.integers(0, streams.shape[1], size=len(clients))
    return streams[np.asarray(clients), idx]
