"""Training batches from a mix's parameters: whole sequences of random
tokens, every row different, drawn from the seed.

Takes the place of `singa_tpu.models.transformer.synthetic_token_batches`
(whose Markov chain is there to be learnable, which a timed window of
random-weight steps does not need, and whose per-position Python loop
costs tens of milliseconds a batch at S 4096).
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def batches(mix: Dict, seed: int, batch: int, vocab: int,
            data_layer: str = "data") -> Iterator[Dict]:
    seq = int(mix["seq_len"])
    rng = np.random.default_rng(int(seed))
    while True:
        toks = rng.integers(0, vocab, (batch, seq + 1)).astype(np.int32)
        yield {data_layer: {"input": toks[:, :-1], "target": toks[:, 1:]}}
