"""HybridLM: a decoder of Mamba-2 and grouped-query attention layers in a
stated order, a tied head whose loss forms its logits a chunk at a time."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.models import HybridLM, LoopedLM
from analytics_zoo_tpu.nn import objectives
from analytics_zoo_tpu.nn.layers import attention
from analytics_zoo_tpu.nn.layers.attention import (
    HybridDecoderStack, MultiHeadAttention, PreNormDecoderBlock)
from analytics_zoo_tpu.nn.layers.ssm import Mamba2Mixer
from analytics_zoo_tpu.observe.metrics import METRICS
from analytics_zoo_tpu.ops import attention as ops_attention
from analytics_zoo_tpu.train.optimizers import Adam

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TYPES = ["mamba", "mamba", "attention", "mamba"]
TINY = dict(vocab_size=96, hidden_size=32, layer_types=TYPES,
            num_attention_heads=4, num_key_value_heads=2,
            shared_intermediate_size=48, mamba_n_heads=4, mamba_d_head=16,
            mamba_d_state=8, mamba_chunk_size=8, embedding_multiplier=12,
            attention_multiplier=0.0625, residual_multiplier=0.22,
            logits_scaling=8)


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _net(**over):
    return HybridLM(**dict(TINY, **over)).model


def _built(seed=0, rows=2, l=16, **over):
    net = _net(**over)
    params, state = net.build(jax.random.PRNGKey(seed), (rows, l))
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (rows, l + 1), 0,
                             net.vocab_size)
    return net, params, state, ids[:, :-1], ids[:, 1:]


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol,
                               atol=tol * max(1e-6, np.abs(b).max()))


# ------------------------------------------------------------- the model ---

def test_fit_trains_on_the_normal_path_and_predict_returns_logits():
    rows, l = 32, 16
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 96, (rows, l + 1), dtype=np.int32)
    # a stream the model can learn: the next token is this one plus one
    tokens = (tokens[:, :1] + np.arange(l + 1)) % 96
    ids, y = tokens[:, :-1].astype(np.int32), tokens[:, 1:].astype(np.int32)
    model = HybridLM(**TINY)
    model.compile(optimizer=Adam(lr=1e-2, beta_2=0.95),
                  loss="chunked_token_crossentropy")
    before = METRICS.snapshot()
    hist = model.fit(ids, y, batch_size=8, nb_epoch=8, verbose=False)
    losses = [h["loss"] for h in hist]
    assert losses[0] == pytest.approx(np.log(96), rel=0.2)
    assert losses[-1] < 0.8 * losses[0]
    counters = METRICS.delta(before)["counters"]
    assert counters["train_tokens_total"] == 8 * rows * l
    logits = model.predict(ids[:8], batch_size=8)
    assert logits.shape == (8, l, 96)
    assert model.config()["layer_types"] == TYPES


def test_layers_run_in_the_stated_order_in_runs_of_like_layers():
    net, params, state, ids, _ = _built()
    stack = net.stack
    assert stack.runs == [("mamba", 2), ("attention", 1), ("mamba", 1)]
    sp = params[stack.name]
    assert sorted(sp) == ["final_norm", "run0", "run1", "run2"]
    assert sp["run0"]["mixer"]["in_proj"]["kernel"].shape[0] == 2
    assert sp["run1"]["mixer"]["k"]["kernel"].shape == (1, 32, 16)
    assert "bias" not in sp["run1"]["mixer"]["q"]
    # by hand: a layer at a time, each with its own slice of its run
    x = params["embed"][ids] * 12.0
    for i, (kind, n) in enumerate(stack.runs):
        for j in range(n):
            x = stack.blocks[kind].forward(jax.tree_util.tree_map(
                lambda a: a[j], sp[f"run{i}"]), x)
    want = stack.final_norm.forward(sp["final_norm"], x)
    _close(net.call(params, state, ids)[0],
           jnp.dot(want, params["embed"].T) / 8.0)
    # the block: a = x + r Mixer(N1 x), y = a + r FFN(N2 a)
    blk, p = stack.blocks["attention"], jax.tree_util.tree_map(
        lambda a: a[0], sp["run1"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32))
    a = h + 0.22 * blk.mixer.forward(p["mixer"],
                                     blk.norm.forward(p["norm1"], h))
    _close(blk.forward(p, h), a + 0.22 * blk.ffn.forward(
        p["ffn"], blk.norm.forward(p["norm2"], a)))
    with pytest.raises(ValueError, match="none for \\['window'\\]"):
        HybridDecoderStack(["mamba", "window"], stack.blocks, 32)


def test_grouped_heads_are_repeated_heads_with_the_stated_scale():
    """Key/value head j serves query heads 2j and 2j + 1; no positions; the
    scores times ``sm_scale`` and not 1 / sqrt(D)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32))
    grouped = MultiHeadAttention(4, 32, causal=True, use_bias=False,
                                 n_kv_head=2, sm_scale=0.3, name="gqa")
    p = grouped.build_params(jax.random.PRNGKey(1), x.shape)
    assert p["k"]["kernel"].shape == p["v"]["kernel"].shape == (32, 16)

    def repeat(w):      # (32, 2 heads x 8) -> (32, 4 heads x 8)
        return jnp.repeat(w.reshape(32, 2, 8), 2, axis=1).reshape(32, 32)

    full = MultiHeadAttention(4, 32, causal=True, use_bias=False,
                              sm_scale=0.3, name="mha")
    p_full = dict(p, k={"kernel": repeat(p["k"]["kernel"])},
                  v={"kernel": repeat(p["v"]["kernel"])})
    _close(grouped.forward(p, x), full.forward(p_full, x))
    # by hand, one query head
    q = (x @ p["q"]["kernel"]).reshape(2, 12, 4, 8)
    k = (x @ p["k"]["kernel"]).reshape(2, 12, 2, 8)
    v = (x @ p["v"]["kernel"]).reshape(2, 12, 2, 8)
    s = jnp.einsum("bqd,bkd->bqk", q[:, :, 3], k[:, :, 1]) * 0.3
    s = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), s, -1e30)
    head3 = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v[:, :, 1])
    ctx = jnp.zeros((2, 12, 32)).at[..., 24:].set(head3)
    got = grouped.forward(dict(p, o={"kernel": jnp.eye(32)}), x)
    _close(got[..., 24:], ctx[..., 24:])
    # no positions: the last token's output does not change when the
    # tokens before it change places
    swapped = x.at[:, 2].set(x[:, 7]).at[:, 7].set(x[:, 2])
    _close(grouped.forward(p, swapped)[:, -1], grouped.forward(p, x)[:, -1])
    # the key/value kernels' gradient is the sum over the query heads
    g = jax.grad(lambda q_: jnp.sum(jnp.square(grouped.forward(q_, x))))(p)
    g_full = jax.grad(lambda q_: jnp.sum(jnp.square(
        full.forward(q_, x))))(p_full)
    _close(g["k"]["kernel"], g_full["k"]["kernel"].reshape(
        32, 2, 2, 8).sum(2).reshape(32, 16))
    with pytest.raises(ValueError, match="do not divide"):
        MultiHeadAttention(4, 32, n_kv_head=3)


def test_a_narrow_head_goes_to_the_flash_kernel_padded_with_zeros(
        monkeypatch):
    """D 64: zero columns up to 128, the unpadded 1 / sqrt(D) unless a scale
    is given, the first D columns back; D 32 and a masked call stay on the
    XLA paths."""
    import sys

    fa = sys.modules["analytics_zoo_tpu.ops.flash_attention"]
    seen = []

    def kernel(q, k, v, causal=False, sm_scale=None):
        seen.append((q.shape, sm_scale))
        assert not np.asarray(q[..., 64:]).any()
        return ops_attention.reference_attention(q, k, v, causal=causal,
                                                 sm_scale=sm_scale)

    monkeypatch.setattr(fa, "flash_attention", kernel)
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 128, 64))
               for i in range(3))
    for scale in (None, 0.015625):
        got = ops_attention.dot_product_attention(
            q, k, v, causal=True, sm_scale=scale, use_flash=True)
        assert got.shape == q.shape
        _close(got, ops_attention.reference_attention(
            q, k, v, causal=True, sm_scale=scale))
    assert seen == [((1, 2, 128, 128), 0.125), ((1, 2, 128, 128), 0.015625)]
    before = METRICS.snapshot()
    ops_attention.dot_product_attention(q[..., :32], k[..., :32],
                                        v[..., :32], causal=True)
    counters = METRICS.delta(before)["counters"]
    assert counters['ops_kernel_selected_total{kernel="flash_attention",'
                    'path="reference"}'] == 1
    assert len(seen) == 2


# --------------------------------------------------------------- the loss ---

def test_chunked_loss_is_the_unchunked_loss_with_the_same_gradients(
        monkeypatch):
    net, params, state, ids, y = _built(rows=4)

    def chunked(p):
        head, _ = net.call(p, state, ids, training=True)
        assert isinstance(head, objectives.TiedHead)
        assert head.scale == 1 / 8 and head.hidden.shape == (4, 16, 32)
        return objectives.chunked_token_crossentropy(y, head)

    def plain(p):
        logits, _ = net.call(p, state, ids)
        return objectives.sparse_categorical_crossentropy_with_logits(
            y, logits)

    want, g_want = jax.value_and_grad(plain)(params)
    for chunk in (64, 16, 1):       # all tokens at once, four chunks, one each
        monkeypatch.setattr(objectives, "_HEAD_CHUNK_BYTES", 4 * 96 * chunk)
        assert objectives._head_chunk(64, 1, 96) == chunk
        got, g = jax.value_and_grad(chunked)(params)
        _close(got, want, 1e-6)
        for a, b in zip(*map(jax.tree_util.tree_leaves, (g, g_want))):
            _close(a, b)
    # plain logits, as predict gives them, get the same loss
    _close(objectives.chunked_token_crossentropy(
        y, net.call(params, state, ids)[0]), want, 1e-6)
    assert objectives.get("chunked_token_crossentropy") is \
        objectives.chunked_token_crossentropy


def test_the_tied_embeddings_gradient_comes_from_both_its_uses():
    net, params, state, ids, y = _built(seed=3)

    def loss(embed_in, embed_out):
        x = embed_in[ids] * 12.0
        h = net.stack.forward(params[net.stack.name], x, training=True)
        return objectives.chunked_token_crossentropy(
            y, objectives.TiedHead(h, embed_out, 1 / 8))

    e = params["embed"]
    g_in, g_out = jax.grad(loss, argnums=(0, 1))(e, e)
    assert float(jnp.abs(g_in).max()) > 0 and float(jnp.abs(g_out).max()) > 0
    tied = jax.grad(lambda p: objectives.chunked_token_crossentropy(
        y, net.call(p, state, ids, training=True)[0]))(params)["embed"]
    _close(tied, g_in + g_out)
    # a row never looked up gets the head's gradient alone
    unused = np.setdiff1d(np.arange(96), np.asarray(ids).ravel())
    _close(tied[unused], g_out[unused])


def test_the_two_losses_share_one_chunk_loop():
    """``expected_exit_crossentropy`` and ``chunked_token_crossentropy``
    both go through ``_mean_over_token_chunks``."""
    calls = []
    real = objectives._mean_over_token_chunks

    def noting(chunk_sum, kernel, vocab, per_token, labels):
        calls.append((kernel.shape, vocab, per_token[0].shape))
        return real(chunk_sum, kernel, vocab, per_token, labels)

    objectives._mean_over_token_chunks = noting
    try:
        y = jnp.zeros((2, 4), jnp.int32)
        objectives.chunked_token_crossentropy(y, objectives.TiedHead(
            jnp.ones((2, 4, 8)), jnp.ones((16, 8))))
        objectives.expected_exit_crossentropy(y, objectives.ExitHeads(
            jnp.ones((3, 2, 4, 8)), jnp.zeros((3, 2, 4)), jnp.ones((8, 16)),
            0.1))
    finally:
        objectives._mean_over_token_chunks = real
    assert calls == [((16, 8), 16, (1, 8, 8)), ((8, 16), 16, (3, 8, 8))]


# ---------------------------------------------------------- recomputation ---

def _stack_gradient(monkeypatch, budget):
    monkeypatch.setattr(attention, "_KEEP_BYTES", budget)
    net, params, _, ids, _ = _built()
    x = params["embed"][ids]
    grad = jax.grad(lambda p: jnp.sum(jnp.square(net.stack.forward(p, x))))
    kept = attention._kept_names(net.stack._kept(x))
    return kept, grad, params[net.stack.name]


# a value of the toy stack: 4 layers x 32 tokens x 4 bytes x 32 wide
_VALUE = 4 * 32 * 4 * 32


@pytest.mark.parametrize("budget,names", [
    (1 << 40, None), (0, []),
    # the mixers' out_proj first (fan-in 64; three of the four layers);
    # down (fan-in 48, all four) no longer fits and is passed over; of the
    # fan-in 32 only the attention layer's o still fits
    (_VALUE, ["out_proj", "o"]),
    # room for out_proj and down, then the attention layer's four (in_proj,
    # gate and up are wider than what is left); named in the table's order
    (3 * _VALUE, ["down", "out_proj", "o", "q", "k", "v"])],
    ids=["none", "full", "two", "six"])
def test_recomputation_of_any_grade_gives_the_same_gradients(
        monkeypatch, budget, names):
    kept, grad, params = _stack_gradient(monkeypatch, budget)
    assert kept == names
    jaxpr = str(jax.make_jaxpr(grad)(params))
    assert ("checkpoint" in jaxpr or "remat" in jaxpr) is (names is not None)
    got = grad(params)
    _, grad, params = _stack_gradient(monkeypatch, 0)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (got, grad(params)))):
        _close(a, b, tol=1e-5)


def test_what_the_hybrid_stack_keeps_at_the_benchmarks_shapes():
    """The rule and the budget are the looped stack's; the table is the
    kinds': at 2 x 4,096 bfloat16 tokens over nine Mamba layers and one
    attention layer, ``down`` (320 MiB over the ten), ``out_proj`` (288 MiB)
    and the attention layer's four fit; ``in_proj`` (1.17 GiB) and the
    FFN's ``gate`` and ``up`` (1.25 GiB each) do not."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    stack = HybridLM.from_config(cfg).model.stack
    assert stack.runs == [("mamba", 5), ("attention", 1), ("mamba", 4)]
    before = METRICS.snapshot()
    kept = stack._kept(jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16))
    mib = 1 << 20
    assert kept == {
        "block_input": 320 * mib, "down": 320 * mib, "out_proj": 288 * mib,
        "in_proj": 0, "gate": 0, "up": 0, "o": 32 * mib, "q": 32 * mib,
        "k": 8 * mib, "v": 8 * mib, "rest": 0}
    assert attention._kept_names(kept) == ["down", "out_proj", "o", "q", "k",
                                           "v"]
    gauges = METRICS.delta(before)["gauges"]
    assert gauges['stack_kept_bytes{name="out_proj"}'] == 288 * mib
    assert gauges['stack_kept_bytes{name="in_proj"}'] == 0
    # the looped stack's answer is what it was
    assert attention._kept_names(attention._kept_for_backward(
        8192, 2, 2048, 5632, 24)) == ["down"]


# ------------------------------------------------------------ from_config ---

def _granite():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def test_from_config_takes_the_familys_keys_by_name():
    model = HybridLM.from_config(_granite())
    cfg = model.config()
    assert cfg["vocab_size"] == 12544 and cfg["hidden_size"] == 2048
    assert cfg["layer_types"].count("mamba") == 9
    assert cfg["attention_multiplier"] == 0.015625
    assert cfg["residual_multiplier"] == 0.22 and cfg["logits_scaling"] == 8
    mamba = model.model.stack.blocks["mamba"].mixer
    assert (mamba.n_heads, mamba.head_dim, mamba.d_state, mamba.chunk_size,
            mamba.proj_dim, mamba.conv_dim) == (64, 64, 128, 256, 8512, 4352)
    attn = model.model.stack.blocks["attention"].mixer
    assert (attn.nhead, attn.n_kv_head, attn.sm_scale, attn.rotary) == (
        32, 8, 0.015625, None)


@pytest.mark.parametrize("change,says", [
    (dict(num_local_experts=8), "routed experts"),
    (dict(position_embedding_type="rope"), "no positions"),
    (dict(rope_scaling={"type": "linear", "factor": 2}), "rope_scaling"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(tie_word_embeddings=False), "tie_word_embeddings"),
    (dict(num_hidden_layers=12), "10 layer_types"),
    (dict(mamba_expand=4), "mamba_expand"),
])
def test_from_config_refuses_what_it_cannot_run(change, says):
    with pytest.raises(ValueError, match=says):
        HybridLM.from_config(dict(_granite(), **change))


def test_the_looped_model_names_the_model_that_takes_grouped_heads():
    cfg = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=48)
    with pytest.raises(ValueError, match="HybridLM"):
        LoopedLM.from_config(cfg)


# ------------------------------------------------------ scopes, counters ---

def test_the_lowered_step_carries_the_scopes_and_counts_the_selections():
    net, params, state, ids, y = _built()

    def step(p):
        return jax.grad(lambda q: objectives.chunked_token_crossentropy(
            y, net.call(q, state, ids, training=True)[0]))(p)

    before = METRICS.snapshot()
    text = jax.jit(step).lower(params).as_text(debug_info=True)
    for scope in ("zoo:lm/embed", "zoo:lm/stack", "zoo:lm/head_loss",
                  "jvp(zoo:lm/stack)/", "zoo:ssm/mixer",
                  "zoo:ssm/mixer/zoo:ssm/scan",
                  "zoo:lm/attn", "transpose(jvp(zoo:lm/stack))"):
        assert scope in text, scope
    counters = METRICS.delta(before)["counters"]
    # a run is traced once, whatever its length: two Mamba runs, one
    # attention run
    assert counters[
        'ops_kernel_selected_total{kernel="ssm_scan",path="reference"}'] >= 2
    assert counters['ops_kernel_selected_total{kernel="flash_attention",'
                    'path="reference"}'] >= 1
