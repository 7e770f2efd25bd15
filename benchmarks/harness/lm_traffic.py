"""Token traffic for the language-model cells (the image cells' generator is
`traffic.py`). A traffic mix is a block of parameters in a cell's file;
`token_stream` is one flat stream of ids, cut by the program's own loader
(`--dataset tokens`) into windows of the cell's sequence length.

The stream is seeded from the cell's `data_seed`, not `--seed`: written once per
checkout and reused by every run; `--seed` drives the loader's shuffle and the
weights. Ids are uniform over the rows of the vocabulary the configuration
holds, so no id falls outside the slice and no operation can fail on one.
"""
from __future__ import annotations

import os

import numpy as np


def write_token_stream(root: str, stream: dict, vocab: int) -> str:
    """`root/train.bin` (`tokens` ids) and a small `root/validation.bin` that
    `train.main` insists on, raw little-endian int32. Returns `root`."""
    done = os.path.join(root, '.complete')
    stamp = repr((sorted(stream.items()), vocab))
    if os.path.exists(done) and open(done).read() == stamp:
        return root
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(stream['data_seed'])
    for name, n in (('train', stream['tokens']), ('validation', stream['validation_tokens'])):
        rng.integers(0, vocab, n, dtype=np.int32).astype('<i4').tofile(os.path.join(root, name + '.bin'))
    with open(done, 'w') as f:
        f.write(stamp)
    return root
