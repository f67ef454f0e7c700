"""Training steps: one global batch of seeded random token ids, fed to
every step (the loss on a repeated batch must fall, which is the
correctness check; the work of a step does not depend on the ids)."""

import numpy as np


def generate(params, seed, seconds, vocab):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (int(params["sequences_per_step"]), int(params["seq_len"])),
                       dtype=np.int32)
    return {"loop": "train", "ids": ids}
