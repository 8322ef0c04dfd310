"""KimiLinearLM: KDA and latent attention layers in the published order,
dense and sparse FFNs, an untied head; latent attention through the flash
kernels' padding; the model through ``compile().fit()`` and what
``from_config`` refuses."""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.models import KimiLinearLM
from analytics_zoo_tpu.nn import objectives
from analytics_zoo_tpu.nn.layers.attention import LatentAttention
from analytics_zoo_tpu.nn.layers.kda import KDAMixer
from analytics_zoo_tpu.nn.layers.moe import DroplessMoE
from analytics_zoo_tpu.observe.metrics import METRICS
from analytics_zoo_tpu.ops import attention as ops_attention
from analytics_zoo_tpu.train.optimizers import Adam

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab_size=64, hidden_size=32, num_hidden_layers=5,
            num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, q_lora_rank=None,
            mla_use_nope=True, intermediate_size=48,
            moe_intermediate_size=16, num_experts=4, router_num_experts=16,
            first_held_expert=0, num_experts_per_token=4,
            num_shared_experts=1, routed_scaling_factor=2.446,
            moe_renormalize=True, first_k_dense_replace=1,
            kda_chunk_size=16, tie_word_embeddings=False,
            linear_attn_config=dict(kda_layers=[1, 2, 3, 5],
                                    full_attn_layers=[4], num_heads=2,
                                    head_dim=16, short_conv_kernel_size=4))


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _close(a, b, tol=2e-5):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=tol,
                               atol=tol * max(1e-6, np.abs(b).max()))


def _kimi():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi-linear-48b-a3b-instruct.json")) as f:
        return json.load(f)


# ------------------------------------------------------------- the model ---

def test_fit_trains_on_the_normal_path_and_predict_returns_logits():
    rows, l = 16, 32
    tokens = np.random.default_rng(0).integers(
        0, 64, (rows, l + 1)).astype(np.int32)
    model = KimiLinearLM.from_config(TINY)
    model.compile(optimizer=Adam(lr=3e-3), loss="chunked_token_crossentropy")
    before = METRICS.snapshot()
    hist = model.fit(tokens[:, :-1], tokens[:, 1:], batch_size=8,
                     nb_epoch=3, verbose=False)
    losses = [h["loss"] for h in hist]
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    delta = METRICS.delta(before)
    # 8 x 32 tokens a step, 4 choices each, 4 of 16 experts held
    assert delta["gauges"]['moe_rows{kind="buffer"}'] == 8 * l * 4
    assert delta["gauges"]['moe_rows{kind="expected"}'] == 8 * l * 4 * 4 / 16
    out = model.predict(tokens[:3, :-1])
    assert out.shape == (3, l, 64) and np.isfinite(out).all()


def test_the_layers_follow_the_published_lists():
    model = KimiLinearLM.from_config(TINY)
    stack = model.model.stack
    assert stack.layer_types == ("kda_dense", "kda", "kda", "mla", "kda")
    assert stack.runs == [("kda_dense", 1), ("kda", 2), ("mla", 1),
                          ("kda", 1)]
    blocks = stack.blocks
    assert isinstance(blocks["kda_dense"].mixer, KDAMixer)
    assert isinstance(blocks["mla"].mixer, LatentAttention)
    assert not isinstance(blocks["kda_dense"].ffn, DroplessMoE)
    moe = blocks["kda"].ffn
    assert isinstance(moe, DroplessMoE)
    assert (moe.n_experts, moe.top_k, moe.first, moe.count,
            moe.routed_scale) == (16, 4, 0, 4, 2.446)


def test_from_config_takes_the_publisheds_keys_at_the_cells_cut():
    model = KimiLinearLM.from_config(_kimi())
    cfg, net = model.config(), model.model
    assert cfg["vocab_size"] == 20480 and cfg["hidden_size"] == 2304
    assert cfg["layer_types"] == ["kda_dense", "kda", "kda", "mla", "kda"]
    assert cfg["experts_held"] == (0, 8) and cfg["num_experts"] == 256
    kda = net.stack.blocks["kda"].mixer
    assert (kda.n_heads, kda.head_dim, kda.d_conv, kda.chunk_size) == (
        32, 128, 4, 64)
    mla = net.stack.blocks["mla"].mixer
    assert (mla.nhead, mla.rank, mla.nope, mla.rope, mla.v_head,
            mla.sm_scale) == (32, 512, 128, 64, 128, 192 ** -0.5)
    shapes = jax.eval_shape(lambda k: net.build(k, (2, 8192))[0],
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == 602_450_816
    assert shapes["head"].shape == shapes["embed"].shape == (20480, 2304)


def _refused(**change):
    cfg = dict(TINY, **change)
    with pytest.raises(ValueError) as e:
        KimiLinearLM.from_config(cfg)
    return str(e.value)


@pytest.mark.parametrize("change,says", [
    (dict(q_lora_rank=1536), "q_lora_rank"),
    (dict(mla_use_nope=False), "rotary"),
    (dict(num_expert_group=8), "num_expert_group"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(moe_router_activation_func="softmax"), "sigmoid"),
    (dict(num_nextn_predict_layers=1), "multi-token"),
    (dict(moe_renormalize=False), "moe_renormalize"),
    (dict(num_hidden_layers=6), "do not cover"),
    (dict(linear_attn_config=dict(TINY["linear_attn_config"],
                                  full_attn_layers=[3, 4])), "do not cover"),
])
def test_from_config_refuses_what_it_cannot_run(change, says):
    assert says in _refused(**change)


def test_the_untied_heads_gradient_is_its_own():
    """The head and the embedding are two matrices: the head's gradient
    comes from the logits alone, the embedding's from the look-up alone."""
    net = KimiLinearLM.from_config(TINY).model
    params, state = net.build(jax.random.PRNGKey(0), (2, 16))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 64)

    def loss(p):
        head, _ = net.call(p, state, ids[:, :-1], training=True)
        return objectives.chunked_token_crossentropy(ids[:, 1:], head)

    g = jax.grad(loss)(params)
    used = np.unique(np.asarray(ids[:, :-1]))
    unused = np.setdiff1d(np.arange(64), used)
    assert np.abs(np.asarray(g["embed"])[unused]).max() == 0
    assert np.abs(np.asarray(g["head"])[unused]).max() > 0
    logits, _ = net.call(params, state, ids[:, :-1])
    _close(objectives.chunked_token_crossentropy(ids[:, 1:], logits),
           loss(params))


def test_the_stack_keeps_by_the_kinds_tables(monkeypatch):
    """The recomputation rule over the three kinds' tables: every name of
    the mixers' and the FFNs' projections told to the registry (what this
    call sets: the registry's gauges also hold other stacks' names)."""
    net = KimiLinearLM.from_config(TINY).model
    kept, real = {}, METRICS.set

    def noting(name, /, value, **labels):
        if name == "stack_kept_bytes":
            kept[labels["name"]] = value
        real(name, value, **labels)

    monkeypatch.setattr(METRICS, "set", noting)
    net.stack._kept(jnp.zeros((2, 32, 32), jnp.bfloat16))
    assert set(kept) == {"block_input", "down", "o_proj", "q_proj", "k_proj",
                         "v_proj", "o", "q", "kv_a", "kv_b", "gate", "up",
                         "rest", "kda_scan"}
    # a tiny stack keeps everything: nothing is computed again
    assert kept["rest"] > 0


_MIB = 1 << 20
# what the projections keep at the cell's 2 x 8,192 tokens and at twice
# that: the kernels' entry has a budget of its own and moves none of them
_PROJECTIONS_KEPT = {
    2: {"block_input": 360 * _MIB, "down": 360 * _MIB, "o_proj": 288 * _MIB,
        "q_proj": 0, "k_proj": 0, "v_proj": 0, "gate": 0, "up": 0,
        "o": 72 * _MIB, "q": 192 * _MIB, "kv_a": 18 * _MIB, "kv_b": 0,
        "rest": 0},
    4: {"block_input": 720 * _MIB, "down": 720 * _MIB, "o_proj": 0,
        "q_proj": 0, "k_proj": 0, "v_proj": 0, "gate": 0, "up": 0,
        "o": 144 * _MIB, "q": 0, "kv_a": 36 * _MIB, "kv_b": 0, "rest": 0}}


@pytest.mark.parametrize("on_tpu,sequences,scan_kept", [
    # the cell: four KDA layers' chunk-start states (32 KiB a token) and
    # scaled outputs (8 KiB a token) over 16,384 tokens, 2.5 GiB
    (True, 2, 2560 * _MIB),
    # the reference path writes no states
    (False, 2, 0),
    # 5 GiB: over the kernels' budget, so each scan is computed again
    (True, 4, 0)], ids=["cell", "reference", "over-budget"])
def test_the_stack_keeps_the_scans_residuals_where_they_fit(
        monkeypatch, on_tpu, sequences, scan_kept):
    """The second table: ``kda_scan`` kept whole where the kernels are
    selected and it fits its budget, 0 elsewhere, told to the registry;
    the projections keep what they kept without it."""
    from analytics_zoo_tpu.nn.layers import attention
    from analytics_zoo_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "on_tpu", lambda: on_tpu)
    stack = KimiLinearLM.from_config(_kimi()).model.stack
    before = METRICS.snapshot()
    kept = stack._kept(jax.ShapeDtypeStruct((sequences, 8192, 2304),
                                            jnp.bfloat16))
    assert kept == {**_PROJECTIONS_KEPT[sequences], "kda_scan": scan_kept}
    assert ("kda_scan" in attention._kept_names(kept)) is bool(scan_kept)
    gauges = METRICS.delta(before)["gauges"]
    assert gauges['stack_kept_bytes{name="kda_scan"}'] == scan_kept
    # asked without counting a selection
    assert not any(k.startswith("ops_kernel_selected_total")
                   for k in METRICS.delta(before)["counters"])


# ------------------------------------------------------ latent attention ---

def test_latent_attention_is_full_scores_over_its_equations():
    m = LatentAttention(4, 32, 16, 8, 8, 8, name="t_mla")
    p = m.build_params(jax.random.PRNGKey(0), (2, 24, 32))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    q = (x @ p["q"]["kernel"]).reshape(2, 24, 4, 16)
    c, k_r = jnp.split(x @ p["kv_a"]["kernel"], [16], axis=-1)
    c = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-6)
    kv = (c @ p["kv_b"]["kernel"]).reshape(2, 24, 4, 16)
    k = jnp.concatenate([kv[..., :8], jnp.broadcast_to(
        k_r[:, :, None], (2, 24, 4, 8))], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    s = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., 8:])
    _close(m.forward(p, x), o.reshape(2, 24, 32) @ p["o"]["kernel"])


def test_a_wide_key_and_narrower_value_go_to_the_flash_kernel_padded(
        monkeypatch):
    """D 192 and v 128 (latent attention): zero columns up to 256 for all
    three, the unpadded 1 / sqrt(192), the value's 128 columns back."""
    fa = sys.modules["analytics_zoo_tpu.ops.flash_attention"]
    seen = []

    def kernel(q, k, v, causal=False, sm_scale=None):
        seen.append((q.shape, k.shape, v.shape, sm_scale))
        assert not np.asarray(q[..., 192:]).any()
        assert not np.asarray(v[..., 128:]).any()
        return ops_attention.reference_attention(q, k, v, causal=causal,
                                                 sm_scale=sm_scale)

    monkeypatch.setattr(fa, "flash_attention", kernel)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k = (jax.random.normal(kk, (1, 2, 128, 192)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (1, 2, 128, 128))
    got = ops_attention.dot_product_attention(q, k, v, causal=True,
                                              use_flash=True)
    assert got.shape == (1, 2, 128, 128)
    _close(got, ops_attention.reference_attention(q, k, v, causal=True))
    assert seen == [((1, 2, 128, 256),) * 3 + (192 ** -0.5,)]
    # the XLA paths take a narrower value head too
    blockwise = ops_attention.blockwise_attention(q, k, v, causal=True,
                                                  block_size=32)
    _close(blockwise, got)


@pytest.mark.parametrize("d,dv,width", [(64, 64, 128), (128, 128, 128),
                                        (192, 128, 256), (256, 256, 256)])
def test_head_sizes_select_the_kernel_at_their_width(monkeypatch, d, dv,
                                                     width):
    """The padding rule on a TPU: D 64 (granite) and 128 (ouro) as before,
    latent attention's 192 / 128 at 256; what is sent is of that width."""
    from analytics_zoo_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    fa = sys.modules["analytics_zoo_tpu.ops.flash_attention"]
    seen = []
    monkeypatch.setattr(fa, "flash_attention",
                        lambda q, k, v, causal, sm_scale:
                        seen.append(q.shape[-1]) or v)
    q = k = jax.ShapeDtypeStruct((1, 1, 2048, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 1, 2048, dv), jnp.bfloat16)
    before = METRICS.snapshot()
    out = jax.eval_shape(lambda q, k, v: ops_attention.dot_product_attention(
        q, k, v, causal=True), q, k, v)
    counters = METRICS.delta(before)["counters"]
    assert counters['ops_kernel_selected_total{kernel="flash_attention",'
                    'path="pallas"}'] == 1
    assert seen == [width] and out.shape[-1] == dv


def test_a_head_under_64_or_a_wider_value_stays_on_xla(monkeypatch):
    from analytics_zoo_tpu.ops import dispatch

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    for d, dv in ((32, 32), (128, 192)):
        q = k = jnp.zeros((1, 1, 128, d))
        before = METRICS.snapshot()
        ops_attention.dot_product_attention(q, k, jnp.zeros((1, 1, 128, dv)),
                                            causal=True)
        counters = METRICS.delta(before)["counters"]
        assert counters['ops_kernel_selected_total{kernel="flash_attention",'
                        'path="reference"}'] == 1


# ------------------------------------------------------ scopes, counters ---

def test_the_lowered_step_carries_the_scopes_and_counts_the_selections():
    net = KimiLinearLM.from_config(TINY).model
    params, state = net.build(jax.random.PRNGKey(0), (2, 32))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 64)

    def step(p):
        return jax.grad(lambda q: objectives.chunked_token_crossentropy(
            ids[:, 1:], net.call(q, state, ids[:, :-1], training=True)[0]))(p)

    before = METRICS.snapshot()
    text = jax.jit(step).lower(params).as_text(debug_info=True)
    for scope in ("zoo:lm/embed", "zoo:lm/stack", "zoo:lm/head_loss",
                  "zoo:kda/mixer", "zoo:mla/attn", "zoo:moe/experts",
                  "transpose(jvp(zoo:lm/stack))"):
        assert scope in text, scope
    assert re.search(r"zoo:kda/mixer\)?/zoo:kda/scan", text)
    counters = METRICS.delta(before)["counters"]
    # a run is traced once, whatever its length: three KDA runs, one MLA
    assert counters[
        'ops_kernel_selected_total{kernel="kda_scan",path="reference"}'] >= 3
    assert counters['ops_kernel_selected_total{kernel="flash_attention",'
                    'path="reference"}'] >= 1
