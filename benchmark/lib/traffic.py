"""One general traffic generator, driven by a data file under
``benchmark/traffic/``. A mix is parameters only; a later PR adds a mix by
adding a file. Every seed gets the same sizes (rows and sequence length are
the cell's and the mix's); the seed gives every token id.

Training mix keys (the only kind a cell uses today; a serving kind comes with
the PR that lists the first serving cell, PERF.md section 7)::

    {"kind": "train", "seq_len": 1024, "prefetch": 2}
"""

from typing import Dict, Iterator

import numpy as np


class TrainFeed:
    """Endless host batches ``{"input_ids": int32 [rows, seq_len]}`` by index:
    batch ``k`` is a function of (seed, k) alone and every row differs, so
    what a closed prefetcher fetched ahead and dropped can be handed out
    again (``rewind``) and a seed always trains on the same rows."""

    def __init__(self, mix: dict, seed: int, vocab: int, rows: int):
        self.seq, self.seed, self.vocab, self.rows = int(mix["seq_len"]), seed, vocab, rows
        self.index = 0

    def batch(self, k: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([int(self.seed), 0x7a11, int(k)])
        return {"input_ids": rng.integers(
            0, self.vocab, size=(self.rows, self.seq), dtype=np.int64).astype(np.int32)}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        b = self.batch(self.index)
        self.index += 1
        return b

    def rewind(self, consumed: int) -> None:
        self.index = consumed
