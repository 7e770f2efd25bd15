"""The one general generator of traffic. A traffic mix is a block of
parameters in a cell's file (`traffic`); this module turns it into inputs.

`image_folder`: a seeded folder of JPEGs, written once per checkout and linked
many times so that one epoch outlasts warm-up and window. The program's own
loader reads it; `--seed` drives its shuffle and every augmentation draw.
"""
from __future__ import annotations

import os

import numpy as np


def write_image_folder(root: str, traffic: dict) -> str:
    """ImageNet-like JPEGs (`width` x `height`, quality 90; smooth random
    colour fields plus noise, so decode and crop see image-like content) in
    `classes` class folders under `root/train`, each file linked `links` times,
    and a small `root/validation` that `train.main` insists on. Seeded from the
    cell's `data_seed`, not `--seed`: written once, reused by every run of the
    checkout. Returns `root`."""
    from PIL import Image
    done = os.path.join(root, '.complete')
    stamp = repr(sorted(traffic.items()))
    if os.path.exists(done) and open(done).read() == stamp:
        return root
    rng = np.random.default_rng(traffic['data_seed'])
    w, h = traffic['width'], traffic['height']
    for split, files, links in (('train', traffic['files'], traffic['links']), ('validation', traffic['classes'], 1)):
        for i in range(files):
            d = os.path.join(root, split, f'class_{i % traffic["classes"]:03d}')
            os.makedirs(d, exist_ok=True)
            coarse = rng.integers(0, 256, (6, 8, 3), dtype=np.uint8)
            img = np.asarray(Image.fromarray(coarse).resize((w, h), Image.BICUBIC), np.int16)
            img = np.clip(img + rng.integers(-12, 13, img.shape), 0, 255).astype(np.uint8)
            first = os.path.join(d, f'{i:05d}_000.jpg')
            if os.path.lexists(first):
                os.remove(first)
            Image.fromarray(img).save(first, quality=90)
            for k in range(1, links):
                link = os.path.join(d, f'{i:05d}_{k:03d}.jpg')
                if os.path.lexists(link):
                    os.remove(link)
                os.link(first, link)
    with open(done, 'w') as f:
        f.write(stamp)
    return root
