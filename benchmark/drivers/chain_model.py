"""The five-stage scoring chain as the program's own ``PipelineModel``,
with model data planted from the seed through ``set_model_data`` (no
stage is fitted). The float64 dict that planted it is what the NumPy
reference is given."""

from __future__ import annotations

import numpy as np


def build(md: dict):
    """``StandardScaler -> MinMaxScaler -> MaxAbsScaler -> RobustScaler ->
    LogisticRegressionModel`` over the column ``features``, every stage at
    its default params (so the robust scaler scales and does not centre),
    reading each stage's model data from ``md``
    (``datagen.chain_model_data``)."""
    from flinkml_tpu.models.logistic_regression import LogisticRegressionModel
    from flinkml_tpu.models.scalers import (
        MaxAbsScalerModel, MinMaxScalerModel, RobustScalerModel,
        StandardScalerModel,
    )
    from flinkml_tpu.pipeline import PipelineModel
    from flinkml_tpu.table import Table

    def row(*names):
        return Table({n: np.asarray(md[n], np.float64)[None, :] for n in names})

    specs = [
        (StandardScalerModel, row("mean", "std")),
        (MinMaxScalerModel, row("dataMin", "dataMax")),
        (MaxAbsScalerModel, row("maxAbs")),
        (RobustScalerModel, row("median", "range")),
    ]
    stages, prev = [], "features"
    for i, (cls, data) in enumerate(specs, start=1):
        m = cls().set(cls.INPUT_COL, prev).set(cls.OUTPUT_COL, f"s{i}")
        m.set_model_data(data)
        stages.append(m)
        prev = f"s{i}"
    lr = LogisticRegressionModel()
    lr.set(LogisticRegressionModel.FEATURES_COL, prev)
    lr.set_model_data(row("coefficient"))
    stages.append(lr)
    return PipelineModel(stages)
