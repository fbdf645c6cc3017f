"""MNIST-profile images from ``--seed``: the rows of the configuration
``knn-mnist8m`` (no file can be fetched here, so the profile is
SYNTHESISED; the configuration's ``assumed`` says which of its numbers
are remembered from the source and which are set here). NumPy only; the
streams come from ``datagen.rng`` (``datagen.py`` is imported, not
edited) under tags of their own.

An image is 28 x 28 float32 pixels, each ``level / 255`` for an integer
level 0-255, so a pixel is NOT exact in bfloat16. About 19 % of the
pixels are non-zero and they lie in the centre 20 x 20, as MNIST's do.
Before it is quantised an image is

    field = z @ basis + template[class] - offset + PIXEL_NOISE * normal

- ten class ``templates``: a few soft strokes each, drawn from the seed
  in the centre; ``CLASS_SHARE`` of a template is its own and the rest
  is one figure every class shares;
- ``z`` standard normal in ``RANK`` = 14 dimensions and ``basis`` as
  many smooth fields over the centre: the seeded low-rank deformation
  (published estimates put MNIST's intrinsic dimension at 13-15);
- **two classes are one figure**: ``TWIN`` (9) has the template of
  ``TWIN_OF`` (4), and the first deformation is positive for the one and
  negative for the other, as a 4 closes into a 9. Their rows lie on one
  sheet cut at ``z[0] = 0``, and a query near the cut has neighbours of
  both classes at every density: those are the mixed neighbourhoods a
  check of the vote can bite on. ``images`` returns each row's
  ``margin``, ``|z[0]|`` for the twins and infinity for the others;
- ``offset``: the level below which 81 % of block 0's field lies, so the
  share of lit pixels is the profile's whatever the seed.

Rows are filled block by block on a few threads; block ``i`` always comes
from stream ``(seed, tag, i)``, so the bytes do not depend on the thread
count and a shorter table is a prefix of a longer one.
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np
import threadpoolctl

from benchmark import datagen

# Stream tags (datagen.py holds 1-4, datagen_criteo.py 11-13).
TAG_PROFILE, TAG_TRAIN, TAG_QUERIES = 21, 22, 23

SIDE, CLASSES, RANK = 28, 10, 14
TWIN, TWIN_OF = 9, 4
DIM = SIDE * SIDE
LIT_SHARE = 0.19
#: How much of a class's template is its own (the rest is shared).
CLASS_SHARE = 0.45
#: Standard deviation of the deformation a pixel of the centre sees, and
#: of the independent noise of every pixel, in units of full brightness.
DEFORMATION, PIXEL_NOISE = 0.08, 0.01
#: The first deformation (the twins' cut runs across it) is this many
#: times the others: the larger, the thinner the band of mixed
#: neighbourhoods along the cut.
TWIN_STRETCH = 2.5

_THREADS = 8
_BLOCK_ROWS = 32_768


def _strokes(g: np.random.Generator, count: int, width: float) -> np.ndarray:
    """``[DIM]``: ``count`` soft blobs at seeded places of the centre."""
    yy, xx = np.mgrid[0:SIDE, 0:SIDE].astype(np.float64)
    out = np.zeros((SIDE, SIDE))
    for cy, cx in g.uniform(6.0, SIDE - 7.0, size=(count, 2)):
        out += np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2.0 * width ** 2))
    return out.reshape(-1)


def profile(seed: int):
    """``[RANK + CLASSES + 1, DIM]`` float32, what every image of a seed
    is drawn around, in levels (255 is full brightness): the ``basis``,
    the ten ``templates`` and a last row of minus the ``offset``, so that
    ``[z, one_hot(class), 1] @ profile`` is an image's field less its
    noise."""
    g = datagen.rng(seed, TAG_PROFILE)
    shared = _strokes(g, 6, 1.6)
    own = np.stack([_strokes(g, 6, 1.6) for _ in range(CLASSES)])
    templates = (1.0 - CLASS_SHARE) * shared[None, :] + CLASS_SHARE * own
    templates[TWIN] = templates[TWIN_OF]
    yy, xx = np.mgrid[0:SIDE, 0:SIDE]
    window = ((np.abs(yy - 13.5) < 10) & (np.abs(xx - 13.5) < 10)).reshape(-1)
    basis = np.stack([_strokes(g, 4, 2.5) - _strokes(g, 4, 2.5)
                      for _ in range(RANK)]) * window[None, :]
    # Each pixel of the centre then sees DEFORMATION of standard deviation.
    basis *= DEFORMATION / np.sqrt((basis[:, window] ** 2).sum(axis=0).mean())
    basis[0] *= TWIN_STRETCH
    prof = (255.0 * np.concatenate([basis, templates, np.zeros((1, DIM))])
            ).astype(np.float32)
    field = np.empty((_BLOCK_ROWS // 8, DIM), np.float32)
    _block_field(seed, TAG_TRAIN, 0, field, prof, np.empty_like(field))
    prof[-1] = -np.quantile(field, 1.0 - LIT_SHARE)
    return prof


def _block_field(seed, tag, block, out, prof, scratch) -> np.ndarray:
    """Fills ``out`` ([rows, DIM] float32) with one block's fields, in
    levels, before they are rounded; returns the rows' classes and
    margins. The first
    rows of a block do not depend on how many rows are asked for.
    ``scratch`` ([>= rows, DIM] float32) is a worker's own, reused over
    its blocks: fresh temporaries' page faults were half the time."""
    rows = out.shape[0]
    g = datagen.rng(seed, tag, block)
    classes = g.integers(0, CLASSES, size=_BLOCK_ROWS)[:rows]
    drawn = np.zeros((rows, RANK + CLASSES + 1), np.float32)
    drawn[:, :RANK] = g.standard_normal((_BLOCK_ROWS, RANK), dtype=np.float32)[:rows]
    margin = np.abs(drawn[:, 0])
    drawn[:, 0] = np.where(classes == TWIN, -margin, np.where(
        classes == TWIN_OF, margin, drawn[:, 0]))
    margin[(classes != TWIN) & (classes != TWIN_OF)] = np.inf
    drawn[np.arange(rows), RANK + classes] = 1.0
    drawn[:, -1] = 1.0
    # Uniform noise of PIXEL_NOISE standard deviation (a normal draw
    # costs three times as much, and the pixel is rounded anyway).
    g.random(out=out.reshape(-1), dtype=np.float32)
    out -= np.float32(0.5)
    out *= np.float32(255.0 * PIXEL_NOISE * np.sqrt(12.0))
    out += np.matmul(drawn, prof, out=scratch[:rows])
    return classes, margin


def _fill_blocks(seed, tag, blocks, x, labels, margins, prof) -> None:
    """One worker's blocks, through one scratch buffer."""
    scratch = np.empty((min(_BLOCK_ROWS, x.shape[0]), DIM), np.float32)
    for block in blocks:
        lo = block * _BLOCK_ROWS
        out = x[lo:lo + _BLOCK_ROWS]
        labels[lo:lo + _BLOCK_ROWS], margins[lo:lo + _BLOCK_ROWS] = _block_field(
            seed, tag, block, out, prof, scratch)
        np.rint(out, out=out)
        np.clip(out, 0.0, 255.0, out=out)
        out /= np.float32(255.0)


def images(seed: int, tag: int, rows: int, prof=None):
    """``(x [rows, DIM] float32, labels [rows] float32, margins [rows]
    float32)``: images of the seed's profile from the streams of ``tag``,
    labels the class ids 0-9, margins as the module docstring says."""
    prof = prof if prof is not None else profile(seed)
    x = np.empty((rows, DIM), np.float32)
    labels = np.empty(rows, np.float32)
    margins = np.empty(rows, np.float32)
    blocks = range(-(-rows // _BLOCK_ROWS))
    # Each worker's small sgemm on one thread: eight workers' products
    # on eight BLAS threads each took twice as long.
    with threadpoolctl.threadpool_limits(1, "blas"), \
            cf.ThreadPoolExecutor(_THREADS) as pool:
        tasks = [pool.submit(_fill_blocks, seed, tag, blocks[w::_THREADS], x, labels,
                             margins, prof)
                 for w in range(_THREADS)]
        for t in tasks:
            t.result()
    return x, labels, margins
