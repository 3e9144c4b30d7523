"""`"shape": "mrpc_pairs"`: MRPC-shaped rows (the arithmetic of
`examples/nlp_example.get_dataset`, copied): two half-length token sequences; a
paraphrase (label 1) is a permutation of the first, a non-paraphrase is fresh
tokens."""

import numpy as np


def rows(traffic: dict, vocab: int, seed: int) -> dict:
    n, seq_len = int(traffic["examples"]), int(traffic["seq_len"])
    rng = np.random.default_rng([int(seed), 4])
    half = seq_len // 2
    labels = rng.integers(0, 2, n).astype(np.int32)
    first = rng.integers(5, vocab, (n, half))
    other = rng.integers(5, vocab, (n, half))
    shuffled = rng.permuted(first, axis=1)
    second = np.where(labels[:, None] == 1, shuffled, other)
    return {
        "input_ids": np.concatenate([first, second], axis=1).astype(np.int32),
        "token_type_ids": np.concatenate(
            [np.zeros((n, half), np.int32), np.ones((n, seq_len - half), np.int32)], axis=1),
        "labels": labels,
    }
