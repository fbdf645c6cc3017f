"""Hand-written Pallas (Mosaic) kernels.

**Nine kernels.** Each is chosen where it applies by an
``unsupported_reason`` its caller reads (the backend, the dtype, the
shapes: no environment variable, no knob), and every other backend runs
the XLA lowering of the same result:

- :mod:`~flinkml_tpu.kernels.knn_search` — the KNN search's product and
  ranking in one kernel, the running ``k`` best in fast memory
  (``models.knn.nearest``: a TPU, float32 rows, ``k`` ≤ 128; the whole
  device time of ``knn-mnist8m.transform``);
- :mod:`~flinkml_tpu.kernels.sparse_blocks` — the blocked sparse step's
  lookup and accumulation, a slot's product never in HBM
  (``models._linear_sgd.make_sparse_step_bucketed``: a TPU, float32
  coefficients, a slot plan, a batch in whole tiles; ``lr-criteo.fit``);
- :mod:`~flinkml_tpu.kernels.payload_blocks` — the same walk with a
  payload axis: a factorization machine's rows looked up in their blocks
  and their gradient accumulated, a block walked in chunks of 16 rows of
  128 columns (``models._fm_sparse.make_step``: a TPU, float32
  parameters, a batch in whole tiles, blocks whose parts fast memory
  holds; ``fm-criteo.fit``);
- :mod:`~flinkml_tpu.kernels.dense_step` — the dense linear step, its
  window read once (``models._linear_sgd.make_dense_step``;
  ``lr-a9a.fit``);
- :mod:`~flinkml_tpu.kernels.row_update` — a table's rows updated in
  sorted order, each distinct group of eight read, added to and written
  once by DMA, many in flight (``models._w2v_table._program``: a TPU,
  float32 tables in whole groups of rows and whole rows of lanes, one
  device; ``w2v-1bw.fit``);
- :mod:`~flinkml_tpu.kernels.row_fetch` — a table's rows fetched where
  most ids name a few hot rows: those read out of fast memory a slot at
  a time, XLA's gather over the cold slots alone
  (``models._als_blocked``: a TPU, float32 rows of 128 lanes, hot rows
  that cover enough of the slots; ``als-yahoomusic.fit``);
- :mod:`~flinkml_tpu.kernels.gbt_hist` — a tree level's (node, feature,
  bin) sums of gradients and hessians as one-hot products, a feature's
  one-hot never outside fast memory, the bin's low bit folded into the
  product's columns where that is fewer MXU passes (1 to 8 nodes and 32:
  ``gbt_hist.fold``) (``models._gbt_table._program``: a
  TPU, float32 statistics, uint8 bins, a device's rows in whole tiles;
  ``gbt-airline.fit``);
- :mod:`~flinkml_tpu.kernels.spd_solve` — ALS's normal equations, a
  system a lane (``models._als_blocked``; ``als-yahoomusic.fit``);
- :mod:`~flinkml_tpu.kernels.topk` — exact top-k as ``k`` masked passes
  over a tile, what a TPU's tiled KNN fallback ranks a tile with
  (``models.knn._tile_top_k``).

:mod:`~flinkml_tpu.kernels._split` holds the two ways they make a
float32 from bfloat16 parts, :mod:`~flinkml_tpu.kernels._mosaic` what
they share (``interpret_mode``, ``out_struct``,
``import_beside_host_work``, :class:`KernelUnsupportedError`).

See ``docs/development/kernels.md`` for the supported-shape tables and
the equivalence-test recipe.
"""

from flinkml_tpu.kernels._mosaic import (  # noqa: F401
    ENV_INTERPRET_VAR,
    KernelUnsupportedError,
    interpret_mode,
)
from flinkml_tpu.kernels.topk import pallas_top_k  # noqa: F401

__all__ = [
    "ENV_INTERPRET_VAR",
    "KernelUnsupportedError",
    "interpret_mode",
    "pallas_top_k",
]
