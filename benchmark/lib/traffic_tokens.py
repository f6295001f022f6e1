"""Pre-training traffic for a causal language model: rows of
``seq_len`` token ids, documents packed back to back.

Kind ``packed_documents``: document lengths are lognormal (median
``doc_len_median``, log-sigma ``doc_len_sigma``: a heavy tail) and cut
at ``seq_len``; documents follow each other without padding and are
separated by id 0, a row simply ends where it is full (a trainer's
packing). Ids are drawn Zipf(``token_zipf``) over 1 .. vocab_size - 1
-- the slice of the vocabulary this chip holds -- so a unigram prior is
there to learn and a falling loss is a real check. Labels are each
position's next token. Attention runs across document boundaries: there
is no per-document mask (the configuration's ``assumed`` says so).

Same contract as ``traffic.generate``: the same seed gives the same
arrays; a new mix of this kind is a new data file and no code.
"""

from __future__ import annotations

import numpy as np


def generate(data: dict, config: dict, seed: int) -> tuple:
    """``({"input_ids": [n, L]}, labels [n, L])`` for one epoch."""
    if data["kind"] != "packed_documents":
        raise ValueError(f"unknown traffic kind {data['kind']!r}")
    rng = np.random.default_rng(seed)
    n = int(data["batch"]) * int(data["steps_per_epoch"])
    seq, vocab = int(data["seq_len"]), int(config["vocab_size"])
    total = n * (seq + 1)
    prior = 1.0 / np.arange(1, vocab) ** float(data["token_zipf"])
    cdf = np.cumsum(prior / prior.sum())
    tokens = 1 + np.minimum(np.searchsorted(cdf, rng.random(total)),
                            vocab - 2).astype(np.int32)
    # more documents than can fit, then the separators that fall inside
    lengths = np.minimum(
        rng.lognormal(np.log(float(data["doc_len_median"])),
                      float(data["doc_len_sigma"]),
                      size=total // 8 + 16).astype(np.int64) + 1, seq)
    ends = np.cumsum(lengths + 1) - 1
    tokens[ends[ends < total]] = 0
    rows = tokens.reshape(n, seq + 1)
    return {"input_ids": rows[:, :-1].copy()}, rows[:, 1:].copy()
