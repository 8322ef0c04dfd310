"""INT8 quantized inference — the vnni/openvino example
(reference pyzoo/zoo/examples/vnni/openvino + apps model-inference:
load a model, calibrate to int8, compare latency and outputs; the
reference's DNNL/VNNI int8 claimed ~2x over f32, wp-bigdl.md:192).

Here quantization is native: per-channel symmetric int8 weights live in
HBM and the dequant fuses into the consuming matmul on the MXU's int8
path (`ops.quantization` / `quantize_pytree`).  The script quantizes a
trained classifier and reports agreement + weight-bytes saved; what the
int8 path is worth in time on the chip is not measured (`PERF.md` §7).
"""

import argparse

import numpy as np

from analytics_zoo_tpu import init_zoo_context
from analytics_zoo_tpu.deploy import InferenceModel
from analytics_zoo_tpu.models.text import TextClassifier
from analytics_zoo_tpu.data.datasets import generate_text_classification
from analytics_zoo_tpu.data.text import TextSet


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--requests", type=int, default=64)
    args = ap.parse_args()

    import jax

    init_zoo_context()
    texts, labels = generate_text_classification(n_classes=3, per_class=80)
    ts = (TextSet.from_texts(texts, labels).tokenize().normalize()
          .word2idx(max_words_num=4000).shape_sequence(32))
    x, y = ts.to_arrays()
    clf = TextClassifier(class_num=3, token_length=32,
                         sequence_length=32, encoder="cnn",
                         encoder_output_dim=64, max_words_num=4000)
    clf.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                metrics=["accuracy"])
    clf.fit(x, y.astype(np.int32), batch_size=64, nb_epoch=args.epochs)

    params = jax.device_get(clf.estimator.params)
    state = jax.device_get(clf.estimator.state)
    m_f32 = InferenceModel.from_keras_net(clf.model, params, state,
                                          batch_buckets=(64,))
    m_int8 = InferenceModel.from_keras_net(clf.model, params, state,
                                           int8=True, batch_buckets=(64,))

    q = x[: args.requests]
    p32 = np.asarray(m_f32.predict([q]))
    p8 = np.asarray(m_int8.predict([q]))
    agree = float((p32.argmax(-1) == p8.argmax(-1)).mean())
    drift = float(np.abs(p32 - p8).max())
    f32_bytes = sum(np.asarray(v).nbytes
                    for p in params.values() for v in p.values())
    from analytics_zoo_tpu.deploy.inference import quantize_pytree
    qt = quantize_pytree(params)
    q_bytes = sum(np.asarray(leaf).nbytes
                  for leaf in jax.tree_util.tree_leaves(qt))
    print(f"top-1 agreement int8 vs f32: {agree:.4f} "
          f"(max prob drift {drift:.4f})")
    print(f"weight bytes: f32 {f32_bytes:,} -> int8 {q_bytes:,} "
          f"({f32_bytes / q_bytes:.2f}x smaller)")
    assert agree > 0.95


if __name__ == "__main__":
    main()
