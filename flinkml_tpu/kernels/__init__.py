"""Hand-written Pallas (Mosaic) kernels.

**Eight kernels run, ungated.** Each is chosen where it applies by an
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
float32 from bfloat16 parts. They share ``_gate``'s helpers
(``interpret_mode``, ``out_struct``, ``import_beside_host_work``).

**The gate is what is left of PR 12**: ``FLINKML_TPU_KERNELS`` (and the
autotune table's ``kernel_backend_<site>`` knobs) choosing between XLA
and Pallas for two sites that no benchmark cell runs and that default to
XLA (:mod:`flinkml_tpu.kernels._gate`): ``fused_chain``
(:mod:`~flinkml_tpu.kernels.chain`: the fused transform chain as one
row-tiled kernel; refuses the float64 constants a fitted chain carries)
and ``segment_sum`` (:mod:`~flinkml_tpu.kernels.segsum`: the padded-ELL
scatter-accumulate; refuses the sparse trainers' ``[1e6, 1]`` output).
The resolved backend joins the fused executor's program and AOT cache
identity, the trainer factories' lru keys and jit static args; an
explicit request for Pallas on unsupported operands raises
:class:`KernelUnsupportedError`, a table-chosen one warns once and runs
XLA. ROADMAP D3 has what deleting it takes.

See ``docs/development/kernels.md`` for the supported-shape tables and
the equivalence-test recipe.
"""

from flinkml_tpu.kernels._gate import (  # noqa: F401
    BACKENDS,
    ENV_INTERPRET_VAR,
    ENV_VAR,
    KNOB_PREFIX,
    SITES,
    KernelUnsupportedError,
    backend_for,
    interpret_mode,
    resolve_backend,
)
from flinkml_tpu.kernels.segsum import (  # noqa: F401
    pallas_segment_sum,
    segment_sum,
)
from flinkml_tpu.kernels.segsum import (  # noqa: F401
    factory_backend as segsum_backend,
)
from flinkml_tpu.kernels.topk import pallas_top_k  # noqa: F401

__all__ = [
    "BACKENDS",
    "ENV_INTERPRET_VAR",
    "ENV_VAR",
    "KNOB_PREFIX",
    "SITES",
    "KernelUnsupportedError",
    "backend_for",
    "interpret_mode",
    "resolve_backend",
    "pallas_segment_sum",
    "segment_sum",
    "segsum_backend",
    "pallas_top_k",
]
