"""Topic modeling end to end: Tokenizer -> CountVectorizer -> LDA,
with topic descriptions mapped back through the fitted vocabulary.

Run: PYTHONPATH=. JAX_PLATFORMS=cpu python examples/topic_modeling.py
"""

# Runnable standalone from any cwd: put the repo root on sys.path when
# flinkml_tpu isn't already importable (pip-installed or PYTHONPATH set).
import os as _os
import sys as _sys

try:
    import flinkml_tpu  # noqa: F401
except ImportError:
    _sys.path.insert(
        0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    )

import numpy as np

from flinkml_tpu import Pipeline
from flinkml_tpu.models import LDA, CountVectorizer, Tokenizer
from flinkml_tpu.table import Table

rng = np.random.default_rng(0)
sports = ["game", "team", "score", "coach", "season", "player"]
cooking = ["recipe", "oven", "flour", "butter", "sauce", "bake"]
travel = ["flight", "hotel", "beach", "passport", "luggage", "tour"]
docs = []
for _ in range(600):
    pool = [sports, cooking, travel][int(rng.integers(0, 3))]
    docs.append(" ".join(rng.choice(pool, size=12)))
t = Table({"text": np.asarray(docs)})

prep = Pipeline([
    Tokenizer().set_input_col("text").set_output_col("tok"),
    CountVectorizer().set_input_col("tok").set_output_col("features"),
]).fit(t)
(tf,) = prep.transform(t)
vocab = prep.stages[1].vocabulary

lda = LDA().set_k(3).set_max_iter(30).set_seed(0).fit(tf)
desc = lda.describe_topics(4)
for r in range(3):
    words = [vocab[i] for i in desc["termIndices"][r]]
    weights = np.round(desc["termWeights"][r], 3)
    print(f"topic {r}: {list(zip(words, weights))}")

(out,) = lda.transform(tf)
print("doc 0 mixture:", np.round(out["topicDistribution"][0], 3))
