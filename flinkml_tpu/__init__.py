"""flinkml_tpu — a TPU-native ML pipeline framework.

A brand-new JAX/XLA/Pallas framework with the capabilities of Apache Flink ML
(reference: JingsongLi/flink-ml): a scikit-learn-style Estimator/Transformer/
Model/Pipeline API with typed validated params and JSON save/load, an
epoch-synchronized iteration runtime with termination criteria and mid-training
checkpoint/resume (bounded and unbounded/online modes), distributed primitives
(AllReduce via ``jax.lax.psum`` over ICI, broadcast model replication, keyed
aggregation via segment-sum, mapPartition-style per-shard compute), and an
algorithm library.

Design stance (see SURVEY.md §7): the reference spends ~10k LoC making a
dataflow engine loop (head/tail/feedback/alignment). On TPU the loop is the
program — a host loop (or ``lax.fori_loop``) around one jitted SPMD step —
and epoch alignment is implicit in SPMD lockstep. We keep the reference's API
surface and semantic guarantees, and discard its mechanism.
"""

from flinkml_tpu.params import (
    Param,
    IntParam,
    LongParam,
    FloatParam,
    BoolParam,
    StringParam,
    IntArrayParam,
    FloatArrayParam,
    StringArrayParam,
    ParamValidators,
    WithParams,
)
from flinkml_tpu.api import (
    Stage,
    AlgoOperator,
    Transformer,
    Model,
    Estimator,
)
from flinkml_tpu.table import CsrColumn, Table, TokenColumn
from flinkml_tpu.pipeline import Pipeline, PipelineModel
from flinkml_tpu.graph import GraphBuilder, Graph, GraphModel, TableId
from flinkml_tpu.tuning import (
    CrossValidator,
    CrossValidatorModel,
    ParamGridBuilder,
    TrainValidationSplit,
    TrainValidationSplitModel,
)

__version__ = "0.2.0"

__all__ = [
    "Param",
    "IntParam",
    "LongParam",
    "FloatParam",
    "BoolParam",
    "StringParam",
    "IntArrayParam",
    "FloatArrayParam",
    "StringArrayParam",
    "ParamValidators",
    "WithParams",
    "Stage",
    "AlgoOperator",
    "Transformer",
    "Model",
    "Estimator",
    "Table",
    "CsrColumn",
    "TokenColumn",
    "Pipeline",
    "PipelineModel",
    "GraphBuilder",
    "Graph",
    "GraphModel",
    "TableId",
    "ParamGridBuilder",
    "CrossValidator",
    "CrossValidatorModel",
    "TrainValidationSplit",
    "TrainValidationSplitModel",
    "__version__",
]
