"""Fine-tune driver: an endless backlog of identical-size jobs, each a new
tenant with ``samples`` x ``seq`` seeded random tokens and next-token
labels, in ingest batches of ``ingest_batch``."""

from __future__ import annotations

import numpy as np

from bench import gen


class Stream:
    def __init__(self, finetune: dict, vocab: int, seed: int):
        self.ft, self.vocab = finetune, vocab
        self.seed = seed
        self.n = 0

    def next(self) -> gen.Job:
        ft = self.ft
        rng = np.random.default_rng([self.seed, 2, self.n])
        s, b = int(ft["seq"]), int(ft["ingest_batch"])
        toks = rng.integers(0, self.vocab, (int(ft["samples"]), s + 1),
                            dtype=np.int32)
        # Next-token labels: sample i predicts its own shifted sequence.
        tokens, labels = toks[:, :-1], toks[:, 1:]
        batches = [
            (tokens[i:i + b], labels[i:i + b])
            for i in range(0, tokens.shape[0], b)
        ]
        job = gen.Job(tenant=f"job-{self.n:05d}", batches=batches)
        self.n += 1
        return job


def stream(finetune: dict, *, vocab: int, seed: int) -> Stream:
    return Stream(finetune, vocab, seed)
