"""Hand-written Pallas kernels for the hot inner loops (ROADMAP item 2).

PAPER.md's blueprint is a "JAX/XLA/pjit/**Pallas** design"; this package
is the Pallas half: a gated second backend for the three loops where the
executor's speed was hostage to XLA codegen (the bf16 fused-chain CPU
ratio of 0.24–0.49 in PR 10 is the motivating number):

- ``fused_chain`` — the fused 5-stage transform chain as ONE row-tiled
  Pallas kernel per bucket, validity mask applied in-kernel
  (:mod:`flinkml_tpu.kernels.chain`);
- ``segment_sum`` — the padded-ELL sparse gradient scatter-accumulate
  with an ``indices_are_sorted`` run-flush specialization and a
  multi-block cell grid (:mod:`flinkml_tpu.kernels.segsum`);
- ``topk`` — the bucketed top-k behind KNN voting and LSH candidate
  ranking as k masked row-max passes (:mod:`flinkml_tpu.kernels.topk`).

Everything rides the established gate idiom
(:mod:`flinkml_tpu.kernels._gate`): env-gated
(``FLINKML_TPU_KERNELS=pallas|xla`` or per-site pairs), measured
defaults from the autotune table's ``kernel_backend_<site>`` knobs
(XLA stays the default until a >1.10x committed win), lru-keyed (the
backend joins the fused executor's program/AOT cache identity, the
trainer factories' lru keys, and jit static args — a flip re-keys, it
never aliases), pinned-numerics equivalence (``interpret=True`` CPU
parity tests in ``tests/test_kernels.py``; bitwise at f32, policy
tolerance under bf16), and loud refusal on unsupported dtypes/shapes
(:class:`KernelUnsupportedError` on explicit requests, warn-once XLA
fallback for table-chosen backends).

Outside the gate, :mod:`flinkml_tpu.kernels.knn_search` is the KNN
search's product and ranking in one kernel; ``models.knn.nearest`` takes
it wherever it applies (a TPU, float32 rows, ``k`` ≤ 128);
:mod:`flinkml_tpu.kernels.spd_solve` is ALS's solve, a system a lane;
:mod:`flinkml_tpu.kernels.sparse_blocks` is the blocked sparse step's
lookup and accumulation in fast memory, which
``models._linear_sgd.make_sparse_step_bucketed`` takes wherever they
apply (a TPU, float32 coefficients, a slot plan, a batch in whole tiles).

See ``docs/development/kernels.md`` for the supported-shape tables,
the equivalence-test recipe, and the device re-tune runbook.
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
from flinkml_tpu.kernels.topk import (  # noqa: F401
    pallas_top_k,
    top_k,
)
from flinkml_tpu.kernels.topk import (  # noqa: F401
    factory_backend as topk_backend,
)

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
    "top_k",
    "topk_backend",
]
