"""Training batches from the seed: ids uniform over the vocabulary.

Parameters (the traffic file): ``batch``, ``sequence``. Every row
differs, and the same seed gives the same batches in the same order, so
the reference can make the first steps' batches again after the window.
Made on the host, as a loader hands them over: the copy to the device
is inside the step that the window times.
"""

from __future__ import annotations

import numpy as np


class Batches:
    def __init__(self, params: dict, vocab: int, seed: int):
        self.shape = (params["batch"], params["sequence"])
        self.vocab = vocab
        self.rng = np.random.default_rng([seed, 7])

    def next(self) -> np.ndarray:
        return self.rng.integers(0, self.vocab, self.shape, dtype=np.int32)
