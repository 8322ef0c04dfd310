"""Analytics Zoo TPU — a TPU-native deep-learning framework.

A from-scratch re-design of Analytics Zoo's capabilities
(reference: /root/reference, Scala/Spark/BigDL) as an idiomatic
JAX/XLA/Pallas framework:

- ``core``     — context/mesh init, config, triggers, TensorBoard writer
                 (replaces NNContext / ZooTrigger / zoo.tensorboard).
- ``data``     — FeatureSet-style host datasets with memory tiers, image &
                 text preprocessing (replaces zoo.feature.*).
- ``nn``       — Keras-style Sequential/Model + autograd Variable DSL,
                 layers, objectives, metrics (replaces
                 zoo.pipeline.api.keras / autograd).
- ``train``    — Estimator: one jitted SPMD train step with XLA collectives
                 (replaces InternalDistriOptimizer / AllReduceParameter).
- ``parallel`` — mesh construction, sharding rules, ring attention
                 (replaces the Spark block-manager allreduce backend).
- ``ops``      — Pallas TPU kernels (flash attention, NMS, ...).
- ``models``   — built-in model zoo (NCF, WideAndDeep, AnomalyDetector,
                 TextClassifier, Seq2seq, KNRM, SSD, BERT ...).
- ``deploy``   — InferenceModel multi-backend serving + cluster serving.
- ``tfpark``   — foreign-model ingestion: tf.keras/torch converted to
                 native JAX, TFDataset facades, GAN + BERT estimators.
- ``onnx``     — ONNX import without the onnx package (wire codec +
                 jax/lax op lowering); imported graphs train and serve.
- ``nnframes`` — Spark-ML-style NNEstimator/NNClassifier over DataFrames.
- ``automl``   — TimeSequencePredictor + in-process search engine.
- ``native``   — C++ host data-plane (crc32c, parallel gather) via ctypes.
- ``utils``    — nest flatten/pack + file helpers.
"""

__version__ = "0.2.0"

from analytics_zoo_tpu.core.config import ZooConfig  # noqa: F401
from analytics_zoo_tpu.core.context import (  # noqa: F401
    ZooContext,
    describe_devices,
    enable_compile_cache,
    get_zoo_context,
    init_zoo_context,
)
