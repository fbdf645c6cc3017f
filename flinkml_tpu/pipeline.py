"""Pipeline and PipelineModel — linear chains of stages.

Parity with ``ml/builder/Pipeline.java:45-107`` and
``PipelineModel.java:44-68``:
  - ``Pipeline.fit`` trains each Estimator on the running inputs and
    transforms inputs forward only while an Estimator remains downstream;
  - ``PipelineModel.transform`` chains every stage's output into the next;
  - both save as metadata + numbered per-stage subdirectories
    (``ReadWriteUtils.java:178-217``) and load reflectively.

A ``Pipeline`` is itself an Estimator and a ``PipelineModel`` a Model, so
pipelines nest.

TPU-native divergence: ``PipelineModel.transform`` does not simply chain
per-stage transforms. Runs of stages that expose a
:class:`~flinkml_tpu.api.ColumnKernel` fuse into single XLA programs with
device-resident intermediates and a shape-bucketed compile cache — see
:mod:`flinkml_tpu.pipeline_fusion` and ``docs/operators/pipeline_fusion.md``
for the protocol, the bucketing policy, and how to make a stage fusable.

Chains can be validated BEFORE any dispatch:
``flinkml_tpu.analysis.analyze_pipeline(model, schema_of(table))``
abstract-evaluates the whole chain (schema flow, kernel shape/dtype
compatibility, fusion topology, fingerprint stability) device-free — see
``docs/development/static_analysis.md``.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from flinkml_tpu.api import AlgoOperator, Estimator, Model, Stage
from flinkml_tpu.io import read_write
from flinkml_tpu.table import Table
from flinkml_tpu.utils.profiling import span


class Pipeline(Estimator):
    """Linear chain of stages, trained front to back.

    Semantics (Pipeline.java:79-107): for each stage in order — an Estimator
    is fit on the current inputs, producing a Model; an AlgoOperator is used
    as-is; the current inputs are advanced through the stage's transform only
    if another Estimator remains after it.
    """

    def __init__(self, stages: Sequence[Stage] = ()):  # noqa: D107
        super().__init__()
        self._stages: List[Stage] = list(stages)

    @property
    def stages(self) -> List[Stage]:
        return list(self._stages)

    def append_stage(self, stage: Stage) -> "Pipeline":
        self._stages.append(stage)
        return self

    def fit(self, *inputs: Table) -> "PipelineModel":
        last_estimator_idx = -1
        for i, stage in enumerate(self._stages):
            if isinstance(stage, Estimator):
                last_estimator_idx = i

        model_stages: List[AlgoOperator] = []
        last_inputs: Tuple[Table, ...] = tuple(inputs)
        for i, stage in enumerate(self._stages):
            if isinstance(stage, AlgoOperator):
                model_stage: AlgoOperator = stage
            else:
                model_stage = stage.fit(*last_inputs)  # type: ignore[union-attr]
            model_stages.append(model_stage)
            if i < last_estimator_idx:
                last_inputs = tuple(model_stage.transform(*last_inputs))
        return PipelineModel(model_stages)

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> None:
        _save_stage_chain(self, self._stages, path)

    @classmethod
    def load(cls, path: str) -> "Pipeline":
        return cls(_load_stage_chain(path))


class PipelineModel(Model):
    """Chain of transformer stages applied sequentially.

    Parity: ``PipelineModel.java:44-68`` — with one TPU-native execution
    upgrade: instead of dispatching every stage separately (N host↔device
    round trips for N stages), ``transform`` partitions the chain into
    maximal runs of kernel-capable stages (stages exposing
    :meth:`~flinkml_tpu.api.AlgoOperator.transform_kernel`) and compiles
    each run as ONE ``jax.jit`` program via
    :mod:`flinkml_tpu.pipeline_fusion` — intermediate columns stay in
    device memory, and a shape-bucketed compile cache serves repeated
    calls at any row count without retracing. Stages without kernels (or
    whose inputs aren't dense columns) fall back to the per-stage path, so
    mixed chains keep working; fused and per-stage execution produce
    bit-identical outputs.
    """

    def __init__(self, stages: Sequence[AlgoOperator] = ()):  # noqa: D107
        super().__init__()
        self._stages: List[AlgoOperator] = list(stages)

    @property
    def stages(self) -> List[AlgoOperator]:
        return list(self._stages)

    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        with span("transform"):
            return self._transform(inputs)

    def _transform(self, inputs: Tuple[Table, ...]) -> Tuple[Table, ...]:
        from flinkml_tpu import pipeline_fusion

        outputs: Tuple[Table, ...] = tuple(inputs)
        stages = self._stages
        i = 0
        while i < len(stages):
            # Fusion applies to the single-table spine of the chain; multi-
            # table stages (and disabled fusion) take the per-stage path.
            if len(outputs) == 1 and pipeline_fusion.enabled():
                kernels, end = pipeline_fusion.collect_run(
                    outputs[0], stages, i
                )
                if len(kernels) >= 2:
                    outputs = (
                        pipeline_fusion.execute_kernel_chain(
                            outputs[0], kernels
                        ),
                    )
                    i = end
                    continue
            outputs = tuple(stages[i].transform(*outputs))
            i += 1
        return outputs

    def save(self, path: str) -> None:
        _save_stage_chain(self, self._stages, path)

    @classmethod
    def load(cls, path: str) -> "PipelineModel":
        return cls(_load_stage_chain(path))


def _save_stage_chain(composite: Stage, stages: Sequence[Stage], path: str) -> None:
    read_write.save_metadata(composite, path, extra={"numStages": len(stages)})
    for i, stage in enumerate(stages):
        stage.save(read_write.stage_path(path, i))


def _load_stage_chain(path: str) -> List[Stage]:
    meta = read_write.load_metadata(path)
    num_stages = int(meta["numStages"])
    return [read_write.load_stage(read_write.stage_path(path, i)) for i in range(num_stages)]
