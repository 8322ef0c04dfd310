"""Loss functions (Keras-named), pure jax.

Reference capability: api/keras/objectives/ — 15 Keras-named losses
(BinaryCrossEntropy, CategoricalCrossEntropy, SparseCategoricalCrossEntropy,
MeanSquaredError, ..., RankHinge) and ClassNLLCriterion.  All are pure
``fn(y_true, y_pred) -> scalar`` reduced by mean over the batch; every one
is trivially fusable by XLA into the backward pass.
"""

from __future__ import annotations

from typing import Callable, Union

import jax
import jax.numpy as jnp

LossFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]

_EPS = 1e-7


def _align(y_true, y_pred):
    """Match a (B,) target against a (B, 1) prediction (and vice versa) so
    elementwise losses never silently broadcast to (B, B)."""
    y_true = jnp.asarray(y_true)
    if (y_pred.ndim == y_true.ndim + 1 and y_pred.shape[-1] == 1
            and y_pred.shape[:-1] == y_true.shape):
        y_pred = y_pred[..., 0]
    elif (y_true.ndim == y_pred.ndim + 1 and y_true.shape[-1] == 1
            and y_true.shape[:-1] == y_pred.shape):
        y_true = y_true[..., 0]
    return y_true, y_pred


def mean_squared_error(y_true, y_pred):
    y_true, y_pred = _align(y_true, y_pred)
    return jnp.mean(jnp.square(y_pred - y_true))


def mean_absolute_error(y_true, y_pred):
    y_true, y_pred = _align(y_true, y_pred)
    return jnp.mean(jnp.abs(y_pred - y_true))


def mean_absolute_percentage_error(y_true, y_pred):
    y_true, y_pred = _align(y_true, y_pred)
    diff = jnp.abs((y_true - y_pred) / jnp.clip(jnp.abs(y_true), _EPS, None))
    return 100.0 * jnp.mean(diff)


def mean_squared_logarithmic_error(y_true, y_pred):
    y_true, y_pred = _align(y_true, y_pred)
    a = jnp.log(jnp.clip(y_pred, _EPS, None) + 1.0)
    b = jnp.log(jnp.clip(y_true, _EPS, None) + 1.0)
    return jnp.mean(jnp.square(a - b))


def binary_crossentropy(y_true, y_pred):
    """y_pred are probabilities in (0, 1) (post-sigmoid), Keras semantics."""
    y_true, y_pred = _align(y_true, y_pred)
    p = jnp.clip(y_pred, _EPS, 1.0 - _EPS)
    return -jnp.mean(y_true * jnp.log(p) + (1.0 - y_true) * jnp.log1p(-p))


def binary_crossentropy_with_logits(y_true, logits):
    """Numerically stable BCE on logits (preferred on TPU)."""
    y_true, logits = _align(y_true, logits)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * y_true + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def categorical_crossentropy(y_true, y_pred):
    """One-hot targets vs probability outputs."""
    p = jnp.clip(y_pred, _EPS, 1.0)
    return -jnp.mean(jnp.sum(y_true * jnp.log(p), axis=-1))


def _sparse_labels(y_true, preds):
    """Integer labels matching preds' leading dims: supports (B,) vs
    (B, C), (B, 1) vs (B, C), and sequence targets (B, T) vs (B, T, C)."""
    labels = y_true.astype(jnp.int32)
    if labels.ndim == preds.ndim:          # trailing singleton
        labels = labels[..., 0]
    if labels.shape != preds.shape[:-1]:
        raise ValueError(
            f"label shape {labels.shape} incompatible with predictions "
            f"{preds.shape}")
    return labels


def sparse_categorical_crossentropy(y_true, y_pred, zero_based_label=True):
    """Integer targets vs PROBABILITY outputs
    (reference SparseCategoricalCrossEntropy, 0/1-based switch).

    Pair logits heads — e.g. the models.image zoo (resnet50/inception/
    mobilenet/vgg16 end in a raw Dense) — with
    ``sparse_categorical_crossentropy_with_logits`` instead: feeding
    logits here clips through the log and the model silently memorizes
    without generalizing."""
    labels = _sparse_labels(y_true, y_pred)
    if not zero_based_label:
        labels = labels - 1
    p = jnp.clip(y_pred, _EPS, 1.0)
    ll = jnp.take_along_axis(jnp.log(p), labels[..., None], axis=-1)
    return -jnp.mean(ll)


def sparse_categorical_crossentropy_with_logits(y_true, logits):
    """Integer targets vs raw logits (fused log-softmax; stable + fast).
    Sequence targets (B, T) vs (B, T, V) are averaged over all positions."""
    labels = _sparse_labels(y_true, logits)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(ll)


@jax.tree_util.register_pytree_node_class
class ExitHeads:
    """What a looped language model hands its loss in place of logits: the
    hidden states of every pass (T, B, L, d), each pass's exit-gate logit
    (T, B, L) and the one output kernel (d, V) all passes share.  The
    logits, T x tokens x V, are left for the loss to form a chunk of
    tokens at a time.  ``entropy_beta`` is the weight the model's objective
    gives the exit distribution's entropy.  ``dtype`` is the type the model
    computed in: the estimator hands every loss float32 values, and the
    loss multiplies in the model's type again."""

    def __init__(self, hidden, gate_logits, kernel, entropy_beta: float,
                 dtype=None):
        self.hidden, self.gate_logits, self.kernel = (hidden, gate_logits,
                                                      kernel)
        self.entropy_beta = float(entropy_beta)
        self.dtype = jnp.dtype(dtype or hidden.dtype).name

    def tree_flatten(self):
        return ((self.hidden, self.gate_logits, self.kernel),
                (self.entropy_beta, self.dtype))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)


def exit_log_probs(gate_logits):
    """Log of the exit distribution over T passes from the gates' logits
    (T, ...): ``p_t = lambda_t * prod_{j<t}(1 - lambda_j)`` for t < T and
    the last pass takes what is left, ``p_T = prod_{j<T}(1 - lambda_j)``
    (its own gate is not used), ``lambda = sigmoid(gate_logits)``."""
    s = gate_logits.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-s), axis=0)    # log prod (1 - l_j)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate(
        [jax.nn.log_sigmoid(s[:-1]) + before[:-1], before[-1:]], axis=0)


# the head of a looped model forms at most this many bytes of float32
# logits at a time (T passes x chunk of tokens x vocabulary)
_HEAD_CHUNK_BYTES = 512 << 20


def _head_chunk(tokens: int, passes: int, vocab: int) -> int:
    """Largest divisor of ``tokens`` whose logits fit the budget."""
    c = max(1, min(tokens, _HEAD_CHUNK_BYTES // (4 * passes * vocab)))
    while tokens % c:
        c -= 1
    return c


def _mean_over_token_chunks(chunk_sum, kernel, vocab, per_token, labels):
    """``sum over chunks of chunk_sum(kernel, *chunk of each per_token
    array, chunk of labels) / tokens``: the loop both token-level losses
    below form their logits in.  ``per_token`` arrays are (T, tokens, ...)
    and ``kernel`` is the head's, over a vocabulary of ``vocab``; the chunk
    is the largest divisor of the tokens whose T x chunk x V float32 logits
    fit ``_HEAD_CHUNK_BYTES``.  The chunks run as a ``lax.scan`` whose body is
    computed again in the backward pass, so at most one chunk of logits is
    alive, and the kernel's gradient adds up over the chunks in float32."""
    passes, n = per_token[0].shape[:2]
    c = _head_chunk(n, passes, vocab)

    def chunks(a):                  # (T, n, ...) -> (n / c, T, c, ...)
        return jnp.moveaxis(
            a.reshape((passes, n // c, c) + a.shape[2:]), 1, 0)

    chunk_sum = jax.checkpoint(chunk_sum)

    def body(total, xs):
        return total + chunk_sum(kernel, *xs), None

    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        tuple(chunks(a) for a in per_token) + (labels.reshape(n // c, c),))
    return total / n


def _chunk_crossentropy(logits, y):
    """Cross-entropy at every position of a chunk: logits (T, c, V) float32
    and labels (c,) -> (T, c)."""
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, y[None, :, None], axis=-1)[..., 0]


def expected_exit_crossentropy(y_true, heads):
    """The pre-training loss of a looped language model (Ouro,
    arXiv:2510.25741): per token, the cross-entropy of every pass's
    logits weighted by the exit distribution, less ``beta`` times that
    distribution's entropy; the mean over tokens.

        mean_tokens( sum_t p_t * CE(h_t W, y)  -  beta * H(p) )

    ``heads`` is the model's ``ExitHeads``, which carries ``beta``.  The T
    heads are formed a chunk of tokens at a time
    (``_mean_over_token_chunks``: at most one chunk of logits, T x chunk x
    V, 512 MiB of float32 at most, is alive).  Plain
    logits (B, L, V), as the model's ``predict`` path gives them, get the
    token-level cross-entropy."""
    if not isinstance(heads, ExitHeads):
        return sparse_categorical_crossentropy_with_logits(y_true, heads)
    with jax.named_scope("zoo:lm/head_loss"):
        passes, d = heads.hidden.shape[0], heads.hidden.shape[-1]
        labels = _sparse_labels(y_true, heads.hidden[0]).reshape(-1)
        n = labels.shape[0]
        beta, dt = heads.entropy_beta, jnp.dtype(heads.dtype)

        def chunk_sum(kernel, h, s, y):
            logits = jnp.einsum("tcd,dv->tcv", h.astype(dt),
                                kernel.astype(dt),
                                preferred_element_type=jnp.float32)
            ce = _chunk_crossentropy(logits, y)
            logp = exit_log_probs(s)
            p = jnp.exp(logp)
            return jnp.sum(p * ce) + beta * jnp.sum(p * logp)

        return _mean_over_token_chunks(
            chunk_sum, heads.kernel, heads.kernel.shape[-1],
            (heads.hidden.reshape(passes, n, d),
             heads.gate_logits.reshape(passes, n)), labels)


@jax.tree_util.register_pytree_node_class
class TiedHead:
    """What a language model whose head is its embedding hands its loss in
    place of logits: the final hidden states (B, L, d), the embedding (V, d)
    and the factor on the logits, ``logits = hidden embedding^T * scale``.
    The logits, tokens x V, are left for the loss to form a chunk of tokens
    at a time.  ``dtype`` as ``ExitHeads``'s: the type the model computed
    in."""

    def __init__(self, hidden, embedding, scale: float = 1.0, dtype=None):
        self.hidden, self.embedding = hidden, embedding
        self.scale = float(scale)
        self.dtype = jnp.dtype(dtype or hidden.dtype).name

    def tree_flatten(self):
        return (self.hidden, self.embedding), (self.scale, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)


def chunked_token_crossentropy(y_true, head):
    """The token-level cross-entropy of a language model in one pass over a
    head tied to the embedding: the mean over tokens of
    ``CE(h E^T * scale, y)``.  ``head`` is the model's ``TiedHead``; the
    logits are formed a chunk of tokens at a time
    (``_mean_over_token_chunks``), and the embedding's gradient from the
    head adds to the one from the look-up.  Plain logits (B, L, V), as the
    model's ``predict`` path gives them, get the same loss unchunked."""
    if not isinstance(head, TiedHead):
        return sparse_categorical_crossentropy_with_logits(y_true, head)
    with jax.named_scope("zoo:lm/head_loss"):
        labels = _sparse_labels(y_true, head.hidden).reshape(-1)
        scale, dt = head.scale, jnp.dtype(head.dtype)

        def chunk_sum(embedding, h, y):
            logits = scale * jnp.einsum(
                "tcd,vd->tcv", h.astype(dt), embedding.astype(dt),
                preferred_element_type=jnp.float32)
            return jnp.sum(_chunk_crossentropy(logits, y))

        return _mean_over_token_chunks(
            chunk_sum, head.embedding, head.embedding.shape[0],
            (head.hidden.reshape(1, labels.shape[0], -1),), labels)


def class_nll(y_true, log_probs):
    """NLL on log-probabilities (reference ClassNLLCriterion, 197 LoC)."""
    labels = _sparse_labels(y_true, log_probs)
    ll = jnp.take_along_axis(log_probs, labels[..., None], axis=-1)
    return -jnp.mean(ll)


def kullback_leibler_divergence(y_true, y_pred):
    yt = jnp.clip(y_true, _EPS, 1.0)
    yp = jnp.clip(y_pred, _EPS, 1.0)
    return jnp.mean(jnp.sum(yt * jnp.log(yt / yp), axis=-1))


def poisson(y_true, y_pred):
    y_true, y_pred = _align(y_true, y_pred)
    return jnp.mean(y_pred - y_true * jnp.log(y_pred + _EPS))


def cosine_proximity(y_true, y_pred):
    yt = y_true / (jnp.linalg.norm(y_true, axis=-1, keepdims=True) + _EPS)
    yp = y_pred / (jnp.linalg.norm(y_pred, axis=-1, keepdims=True) + _EPS)
    return -jnp.mean(jnp.sum(yt * yp, axis=-1))


def hinge(y_true, y_pred):
    y_true, y_pred = _align(y_true, y_pred)
    return jnp.mean(jnp.maximum(1.0 - y_true * y_pred, 0.0))


def squared_hinge(y_true, y_pred):
    y_true, y_pred = _align(y_true, y_pred)
    return jnp.mean(jnp.square(jnp.maximum(1.0 - y_true * y_pred, 0.0)))


def rank_hinge(y_true, y_pred, margin: float = 1.0, mask=None):
    """Pairwise ranking hinge for (pos, neg) interleaved batches
    (reference objectives/RankHinge.scala; used by KNRM/Ranker).

    ``mask`` is an optional per-row validity vector (B,): a pair counts
    only when both its rows are real, so padded rows on a final partial
    batch are excluded exactly instead of approximated.
    """
    pos = y_pred[0::2]
    neg = y_pred[1::2]
    per_pair = jnp.maximum(margin - pos + neg, 0.0)
    if mask is None:
        return jnp.mean(per_pair)
    pair_mask = (mask[0::2] * mask[1::2]).reshape(
        (-1,) + (1,) * (per_pair.ndim - 1))
    denom = jnp.maximum(jnp.sum(pair_mask), 1.0) * (
        per_pair.size / per_pair.shape[0])
    return jnp.sum(per_pair * pair_mask) / denom


# rank_hinge couples rows across the batch — eval must not vmap it per-row.
rank_hinge.batch_structured = True
# accepts mask= for exact padded-row exclusion; pair count for aggregation:
rank_hinge.supports_mask = True
rank_hinge.mask_count = lambda mask: jnp.sum(mask[0::2] * mask[1::2])


_REGISTRY = {
    "mse": mean_squared_error,
    "mean_squared_error": mean_squared_error,
    "mae": mean_absolute_error,
    "mean_absolute_error": mean_absolute_error,
    "mape": mean_absolute_percentage_error,
    "mean_absolute_percentage_error": mean_absolute_percentage_error,
    "msle": mean_squared_logarithmic_error,
    "mean_squared_logarithmic_error": mean_squared_logarithmic_error,
    "binary_crossentropy": binary_crossentropy,
    "bce": binary_crossentropy,
    "binary_crossentropy_with_logits": binary_crossentropy_with_logits,
    "categorical_crossentropy": categorical_crossentropy,
    "sparse_categorical_crossentropy": sparse_categorical_crossentropy,
    "sparse_categorical_crossentropy_with_logits":
        sparse_categorical_crossentropy_with_logits,
    "expected_exit_crossentropy": expected_exit_crossentropy,
    "chunked_token_crossentropy": chunked_token_crossentropy,
    "class_nll": class_nll,
    "kld": kullback_leibler_divergence,
    "kullback_leibler_divergence": kullback_leibler_divergence,
    "poisson": poisson,
    "cosine_proximity": cosine_proximity,
    "hinge": hinge,
    "squared_hinge": squared_hinge,
    "rank_hinge": rank_hinge,
}


def get(loss: Union[str, LossFn]) -> LossFn:
    """String → loss lowering (reference KerasUtils.scala:165-167)."""
    if callable(loss):
        return loss
    key = loss.lower()
    if key not in _REGISTRY:
        raise ValueError(f"unknown loss {loss!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[key]
