from analytics_zoo_tpu.models.common import ZooModel, register_model  # noqa: F401
from analytics_zoo_tpu.models.recommendation import (  # noqa: F401
    NeuralCF,
    Recommender,
    SessionRecommender,
    WideAndDeep,
    negative_sample,
    presample_implicit_epochs,
)
from analytics_zoo_tpu.models.text import (  # noqa: F401
    KNRM,
    Ranker,
    TextClassifier,
    mean_average_precision,
    ndcg,
)
from analytics_zoo_tpu.models.looped_lm import LoopedLM  # noqa: F401
from analytics_zoo_tpu.models.hybrid_lm import HybridLM  # noqa: F401
from analytics_zoo_tpu.models.seq2seq import (  # noqa: F401
    Bridge,
    RNNDecoder,
    RNNEncoder,
    Seq2seq,
)
from analytics_zoo_tpu.models.anomalydetection import (  # noqa: F401
    AnomalyDetector,
    detect_anomalies,
    unroll,
)
