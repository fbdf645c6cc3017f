"""A One-Billion-Word-profile corpus from ``--seed``, for the
configuration ``w2v-1bw``. NumPy only; streams from ``datagen.rng``
(imported, not edited) under tags of their own. No network: the counts are
the source's, the text synthesised.

- **Sentences**: lengths ``1 + Poisson(Gamma(LENGTH_SHAPE))`` of mean
  :data:`MEAN_LENGTH` (the source's 0.8 billion tokens in 30.3 million
  shuffled sentences), as many as hold ``tokens`` exactly (the last one
  cut).
- **Words**: a Zipf-Mandelbrot unigram over exactly ``vocab`` ranks,
  ``p(r) ~ (r + SHIFT) ** -SKEW``, drawn through a table of
  :func:`table_entries` slots (a rank a run of slots, as ``word2vec.c``
  draws its negatives): the head word holds about 6 % of the tokens and
  the rarest about 50 at the source's counts, ten times ``min_count``.
- **Topics**, so that co-occurrence is not independent: a sentence has one
  of :data:`TOPICS` topics; a token of rank :data:`FUNCTION_WORDS` or more
  is, with probability :data:`PLANTED`, moved to the rank of its own block
  of :data:`TOPICS` consecutive ranks whose place in the block is the
  sentence's topic. Ranks of a block are nearly as frequent as one
  another, so the unigram stays what it was, and a sentence's content
  words lean to one class of ranks.
- **Ids**: the word of a rank is a seeded permutation's, so an id says
  nothing of its frequency.

Tokens are filled block by block (of sentences) on a few threads; block
``i`` always comes from stream ``(seed, tag, i)``, so the bytes do not
depend on the thread count.
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np

from benchmark import datagen

# Stream tags (datagen.py holds 1-4, the other generators 11-45, the ALS
# driver 51).
TAG_LENGTHS, TAG_IDS, TAG_TOPICS, TAG_TOKENS = 61, 62, 63, 64

_THREADS = 8
_BLOCK_SENTENCES = 1 << 15
MEAN_LENGTH, LENGTH_SHAPE = 26.6, 3.0
SKEW, SHIFT = 1.0, 1.35
TOPICS, FUNCTION_WORDS = 64, 1024
#: Of 32: a content token follows its sentence's topic 12 times in 32.
PLANTED_OF_32 = 12


def unigram(vocab: int) -> np.ndarray:
    """A rank's share of the tokens, float64 ``[vocab]``."""
    law = (np.arange(vocab, dtype=np.float64) + SHIFT) ** -SKEW
    return law / law.sum()


def table_entries(vocab: int) -> int:
    return 1 << (27 if vocab > (1 << 16) else 22)


def sentence_bounds(seed: int, tokens: int) -> np.ndarray:
    """``indptr`` int64 of sentences that hold ``tokens`` exactly."""
    g = datagen.rng(seed, TAG_LENGTHS)
    n = int(tokens / MEAN_LENGTH * 1.05) + 64
    lengths = 1 + g.poisson(g.gamma(LENGTH_SHAPE, (MEAN_LENGTH - 1.0) / LENGTH_SHAPE, n))
    ends = np.cumsum(lengths)
    last = int(np.searchsorted(ends, tokens, side="left"))
    if last >= n:
        raise ValueError(f"{n} sentences hold fewer than {tokens} tokens")
    indptr = np.zeros(last + 2, np.int64)
    indptr[1:] = ends[:last + 1]
    indptr[-1] = tokens
    return indptr


def corpus(seed: int, vocab: int, tokens: int):
    """``(indptr int64 [sentences + 1], ids int32 [tokens])``: word ids
    ``0 .. vocab - 1``."""
    indptr = sentence_bounds(seed, tokens)
    sentences = indptr.shape[0] - 1
    slots = table_entries(vocab)
    bounds = np.floor(np.cumsum(unigram(vocab)) * slots).astype(np.int64)
    bounds[-1] = slots
    table = np.repeat(np.arange(vocab, dtype=np.int32), np.diff(bounds, prepend=0))
    shift = np.uint32(32 - int(np.log2(slots)))
    word_of_rank = datagen.rng(seed, TAG_IDS).permutation(vocab).astype(np.int32)
    topic = datagen.rng(seed, TAG_TOPICS).integers(0, TOPICS, sentences, dtype=np.int32)
    ids = np.empty(tokens, np.int32)
    blocks = list(enumerate(range(0, sentences, _BLOCK_SENTENCES)))

    def work(mine) -> None:
        for block, s0 in mine:
            s1 = min(s0 + _BLOCK_SENTENCES, sentences)
            lo, hi = int(indptr[s0]), int(indptr[s1])
            u = datagen.rng(seed, TAG_TOKENS, block).integers(
                0, 1 << 32, hi - lo, dtype=np.uint32)
            rank = table[u >> shift]
            of = np.repeat(topic[s0:s1], np.diff(indptr[s0:s1 + 1]))
            moved = (rank & np.int32(~(TOPICS - 1))) | of
            follow = ((u & np.uint32(31)) < PLANTED_OF_32) & (
                rank >= FUNCTION_WORDS) & (moved < vocab)
            ids[lo:hi] = word_of_rank[np.where(follow, moved, rank)]

    with cf.ThreadPoolExecutor(_THREADS) as pool:
        # list(): an executor keeps a task's exception until it is read.
        list(pool.map(work, [blocks[t::_THREADS] for t in range(_THREADS)]))
    return indptr, ids
