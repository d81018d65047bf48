"""Seeded synthetic token corpus: the one module that owns a token batch's
format.

A token model trains through the same loader, prefetch thread and trainer
as the image models (``MNISTDataLoader`` indexes rows of two arrays and
stacks them; it never looks inside a row), so a token "image" is one packed
sequence of ``seq_len`` ids, ``(N, T)`` int32, and its "label" the next
token at every position, ``(N, T)`` int32 with :data:`IGNORE` at the last
one, which has no next token inside the sequence. The loss and the metrics
(``ops/loss.py``, ``ops/metrics.py``) leave positions labelled
:data:`IGNORE` out of their means and counts.

The corpus is what a pre-training job packs: documents whose lengths are
log-normal (heavy-tailed), ids drawn from a Zipf law over the vocabulary
with the rank-to-id map permuted by the seed, an end-of-document id
(:data:`EOD`) closing each document, documents laid back to back and cut at
each sequence's end. Attention is causal across document boundaries: no
document mask is made.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

IGNORE = -1  # label of a position that does not count
EOD = 0  # end-of-document id; content ids are 1 .. vocab_size - 1


def synthetic_token_corpus(
    n_sequences: int,
    seq_len: int,
    vocab_size: int,
    seed: int = 0,
    *,
    median_len: float = 1024.0,
    sigma: float = 1.0,
    min_len: int = 16,
    max_len: int | None = None,
    zipf_exponent: float = 1.0,
    vocab_seed: int | None = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """``(tokens, labels)``, both ``(n_sequences, seq_len)`` int32.

    Document lengths are ``lognormal(log(median_len), sigma)`` clipped to
    ``[min_len, max_len]`` (``max_len`` defaults to ``seq_len``) and count
    the closing :data:`EOD`. ``vocab_seed`` (default ``seed``) permutes the
    rank-to-id map, so that a train and a test split drawn from different
    seeds can share one vocabulary. Made in bulk: one draw of ids for the
    whole corpus, one of lengths."""
    if vocab_size < 2:
        raise ValueError("vocab_size must hold EOD and one content id")
    rng = np.random.default_rng(seed)
    total = n_sequences * seq_len
    max_len = seq_len if max_len is None else max_len
    min_len = max(1, min(min_len, max_len))
    # Zipf over the content ids by inverse CDF; rank r gets id perm[r].
    weights = np.arange(1, vocab_size, dtype=np.float64) ** -zipf_exponent
    cdf = np.cumsum(weights)
    ranks = np.searchsorted(cdf, rng.random(total) * cdf[-1], side="right")
    perm = np.random.default_rng(
        seed if vocab_seed is None else vocab_seed).permutation(
            np.arange(1, vocab_size, dtype=np.int32))
    flat = perm[np.minimum(ranks, vocab_size - 2)]
    # Enough documents to cover the corpus even if every one were min_len.
    lengths = np.clip(
        np.rint(rng.lognormal(np.log(median_len), sigma,
                              total // min_len + 1)),
        min_len, max_len).astype(np.int64)
    ends = np.cumsum(lengths)
    flat[ends[ends <= total] - 1] = EOD
    tokens = flat.reshape(n_sequences, seq_len).astype(np.int32)
    labels = np.full_like(tokens, IGNORE)
    labels[:, :-1] = tokens[:, 1:]
    return tokens, labels
