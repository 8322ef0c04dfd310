"""A looped decoder through the program against its plain reference
(``benchmark/references/ouro-2.6b.py``, which imports nothing of the
program), at a tiny size on the CPU in float32: every pass's logits, the
gates, the loss and every gradient leaf; the loop's parts each against its
formula; and two ``fit`` steps with (B, L) labels against the reference's."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import optim as plain_optim  # noqa: E402
from harness.spec import load_module  # noqa: E402

from analytics_zoo_tpu import init_zoo_context  # noqa: E402
from analytics_zoo_tpu.nn import objectives  # noqa: E402
from analytics_zoo_tpu.nn.layers import attention  # noqa: E402
from analytics_zoo_tpu.nn.layers import (  # noqa: E402
    GatedFFN, LoopedDecoderStack, MultiHeadAttention, RMSNorm,
    RotaryEmbedding, SandwichDecoderBlock)
from analytics_zoo_tpu.observe.metrics import METRICS  # noqa: E402
from analytics_zoo_tpu.ops import dispatch  # noqa: E402
from analytics_zoo_tpu.ops.attention import (  # noqa: E402
    dot_product_attention, reference_attention)
from analytics_zoo_tpu.ops.flash_attention import flash_attention  # noqa: E402

ref = load_module(os.path.join(BENCH, "references", "ouro-2.6b.py"))
mod = load_module(os.path.join(BENCH, "configs", "ouro-2.6b.py"))

TINY = dict(hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
            head_dim=32, num_hidden_layers=3, intermediate_size=96,
            vocab_size=128, seq_len=32, total_ut_steps=4)
B = 4


def _cfg(**over):
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg.update(over)
    cfg["deployment"]["compute_dtype"] = "float32"
    return cfg


@pytest.fixture(scope="module")
def world():
    """(cfg, net, reference params, program params, ids, labels)."""
    init_zoo_context()
    cfg = _cfg()
    net = mod.build(cfg)
    p = ref.init_params(jax.random.PRNGKey(7), cfg)
    (ids,), y = mod.make_data(cfg, 11, B)
    return (cfg, net, p, mod.to_program(p, net, None), jnp.asarray(ids),
            jnp.asarray(y))


@pytest.fixture(autouse=True)
def _float32_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _close(a, b, tol=2e-5):
    """To float32 rounding, against the larger of the two's size."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-6)
    assert np.abs(a - b).max() <= tol * scale, (np.abs(a - b).max(), scale)


def _ref_passes(p, ids, cfg):
    """[(h_t, lambda_t)] of one sequence, pass by pass, in the reference."""
    r = ref._Rounding(lambda a: a, lambda a: a)
    h, out = p["embed"][ids], []
    for _ in range(cfg["total_ut_steps"]):
        h = ref.one_pass(p, h, cfg, r)
        out.append((h, ref.exit_gate(p, h)))
    return out


# ------------------------------------------------ program against reference

def test_every_pass_logits_and_gates_meet_the_reference(world):
    cfg, net, p, pp, ids, y = world
    heads, _ = net.call(pp, {}, ids, training=True)
    assert heads.hidden.shape == (4, B, 32, 64)
    assert heads.gate_logits.shape == (4, B, 32)
    for b in range(B):
        for t, (h, lam) in enumerate(_ref_passes(p, ids[b], cfg)):
            _close(heads.hidden[t, b], h)
            _close(heads.hidden[t, b] @ heads.kernel, h @ p["head"])
            _close(jax.nn.sigmoid(heads.gate_logits[t, b]), lam)
    # predict's path: the last pass's logits
    logits, _ = net.call(pp, {}, ids, training=False)
    _close(logits[0], _ref_passes(p, ids[0], cfg)[-1][0] @ p["head"])


def test_loss_and_every_gradient_leaf_meet_the_reference(world):
    cfg, net, p, pp, ids, y = world

    def program(pp):
        heads, _ = net.call(pp, {}, ids, training=True)
        return objectives.get("expected_exit_crossentropy")(y, heads)

    lp, gp = jax.value_and_grad(program)(pp)
    lr, gr = jax.value_and_grad(
        lambda p: ref.loss_fn(p, (ids,), y, cfg))(p)
    assert abs(float(lp) - float(lr)) < 2e-6 * float(lr)
    got = jax.tree_util.tree_leaves(mod.from_program(gp, net))
    want = jax.tree_util.tree_flatten_with_path(gr)[0]
    assert len(got) == len(want) == 16
    for g, (path, w) in zip(got, want):
        assert g.shape == w.shape, path
        _close(g, w, tol=5e-5)
    # every layer's gradient is the sum over the passes: with one pass
    # fewer it is another gradient
    g3 = jax.grad(lambda p: ref.loss_fn(
        p, (ids,), y, dict(cfg, total_ut_steps=3)))(p)
    assert float(jnp.abs(g3["layers"]["wq"] - gr["layers"]["wq"]).max()) \
        > 1e-3 * float(jnp.abs(gr["layers"]["wq"]).max())


def test_one_pass_is_a_plain_decoder():
    """T = 1: the layers once, in order, then the final norm."""
    stack = LoopedDecoderStack(3, 2, 64, 96, passes=1, rotary_theta=1e6,
                               name="t1")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64))
    params = stack.build_params(jax.random.PRNGKey(1), x.shape)
    (out,) = stack.forward(params, x)
    h = x
    for i in range(3):
        h = stack.block.forward(jax.tree_util.tree_map(
            lambda a: a[i], params["blocks"]), h)
    _close(out, stack.final_norm.forward(params["final_norm"], h))
    # and four passes feed each pass's output to the next
    four = LoopedDecoderStack(3, 2, 64, 96, passes=4, rotary_theta=1e6,
                              name="t4")
    hs = four.forward(params, x)
    assert hs.shape == (4, 2, 16, 64)
    _close(hs[0], out)
    _close(hs[1], stack.forward(params, hs[0])[0])


# What the backward pass of the toy stack keeps, by the budget: everything
# (no jax.checkpoint), ``down`` and ``o`` alone (a value is 2 blocks x 3
# passes x 32 tokens x 4 bytes x 64 wide), each of them alone, nothing.
_TOY_VALUE = 2 * 3 * 32 * 4 * 64
_TOY_BUDGETS = {"none": (1 << 40, None), "down+o": (2 * _TOY_VALUE,
                                                    ["down", "o"]),
                "down": (_TOY_VALUE, ["down"]), "full": (0, [])}


def _toy_stack_gradient(monkeypatch, budget):
    """(kept names, gradient function, parameters) of the toy stack under
    a budget of ``budget`` bytes."""
    monkeypatch.setattr(attention, "_KEEP_BYTES", budget)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 64))
    stack = LoopedDecoderStack(2, 2, 64, 96, passes=3, name="remat")
    params = stack.build_params(jax.random.PRNGKey(1), x.shape)
    grad = jax.grad(lambda p: jnp.sum(jnp.square(stack.forward(p, x))))
    return attention._kept_names(stack._kept(x)), grad, params


@pytest.mark.parametrize("case", list(_TOY_BUDGETS))
def test_recomputation_of_any_grade_gives_the_same_gradients(monkeypatch,
                                                             case):
    budget, names = _TOY_BUDGETS[case]
    kept, grad, params = _toy_stack_gradient(monkeypatch, budget)
    assert kept == names
    jaxpr = str(jax.make_jaxpr(grad)(params))
    assert ("checkpoint" in jaxpr or "remat" in jaxpr) is (names is not None)
    got = grad(params)
    _, grad, params = _toy_stack_gradient(monkeypatch, 0)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (got, grad(params)))):
        _close(a, b, tol=1e-5)


def _dots(jaxpr) -> int:
    """``dot_general`` equations of a jaxpr and of every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _dots(sub)
    return n


def test_each_kept_value_takes_its_product_out_of_the_backward_pass(
        monkeypatch):
    """The gradient's jaxpr holds one ``dot_general`` fewer with each
    projection kept: 9 of a block's 36 are the forward computed again."""
    counts = {}
    for case, (budget, _) in _TOY_BUDGETS.items():
        _, grad, params = _toy_stack_gradient(monkeypatch, budget)
        counts[case] = _dots(jax.make_jaxpr(grad)(params).jaxpr)
    assert counts == {"full": 36, "down": 35, "down+o": 34, "none": 27}


# (case, tokens, itemsize, hidden, intermediate, applications, budget, kept)
_RULE_CASES = [
    # the toy stacks of this file keep everything: no jax.checkpoint
    ("tiny", 4 * 32, 4, 64, 96, 3 * 4, None, None),
    # the benchmark's shapes under the module's own constant
    ("benchmark", 2 * 4096, 2, 2048, 5632, 6 * 4, None, "table"),
    # ... which admits the one value that paid on the chip (PERF.md, PR 34)
    ("benchmark-as-set", 2 * 4096, 2, 2048, 5632, 6 * 4, None, ["down"]),
    ("benchmark-nothing-fits", 2 * 4096, 2, 2048, 5632, 6 * 4,
     (3 << 28) - 1, []),
    ("benchmark-one", 2 * 4096, 2, 2048, 5632, 6 * 4, 3 << 28, ["down"]),
    ("benchmark-five", 2 * 4096, 2, 2048, 5632, 6 * 4, 15 << 28,
     ["down", "o", "q", "k", "v"]),
    # float32, twice the rows: a value is 3 GiB and none fits
    ("benchmark-float32-x2", 4 * 4096, 4, 2048, 5632, 6 * 4, None, []),
    # a narrow FFN: ``o`` does not fit, is passed over, ``gate`` is taken
    ("passed-over", 64, 4, 64, 16, 6, 64 * 4 * 6 * 40, ["gate", "up"]),
]


@pytest.mark.parametrize("case", _RULE_CASES, ids=lambda c: c[0])
def test_what_is_kept_follows_from_the_shapes(monkeypatch, case):
    _, tokens, itemsize, hidden, ffn, applications, budget, want = case
    if budget is not None:
        monkeypatch.setattr(attention, "_KEEP_BYTES", budget)
    kept = attention._kept_for_backward(tokens, itemsize, hidden, ffn,
                                        applications)
    assert list(kept) == ["block_input"] + [
        p[0] for p in attention._PROJECTIONS] + ["rest"]
    assert kept["block_input"] == tokens * itemsize * applications * hidden
    names = attention._kept_names(kept)
    if want == "table":
        # the names in the table's order, dearest first, as far as they
        # fit; ``gate`` and ``up`` (2.06 GiB each here) never among them
        order = [p[0] for p in attention._PROJECTIONS]
        assert names == order[:len(names)] and 1 <= len(names) <= 5
        assert {kept[n] for n in names} == {3 << 28}
        took = sum(kept[n] for n in names)
        assert took <= attention._KEEP_BYTES < took + (3 << 28)
    else:
        assert names == want
    if names is None:
        # nothing computed again: ten hidden-wide, three FFN-wide values
        assert sum(kept.values()) == tokens * itemsize * applications * (
            10 * hidden + 3 * ffn)
    # the stack asks the same rule with its own widths
    stack = LoopedDecoderStack(applications, 1, hidden, ffn, passes=1,
                               name=f"rule_{case[0]}")
    x = jax.ShapeDtypeStruct((1, tokens, hidden),
                             {2: jnp.bfloat16, 4: jnp.float32}[itemsize])
    assert stack._kept(x) == kept


def test_a_trace_tells_the_registry_what_it_keeps(monkeypatch):
    """``stack_kept_bytes{name}``: once a trace, every name, the bytes the
    rule reckoned; 0 for what is computed again."""
    sets = []
    real = METRICS.set

    def noting(name, /, value, **labels):
        # the trace also books its own build (jit_build_seconds{phase})
        if name == "stack_kept_bytes":
            sets.append((name, labels["name"]))
        real(name, value, **labels)

    monkeypatch.setattr(METRICS, "set", noting)
    _, grad, params = _toy_stack_gradient(monkeypatch,
                                          _TOY_BUDGETS["down+o"][0])
    sets.clear()
    jax.jit(grad).lower(params)
    want = {"block_input": _TOY_VALUE, "down": _TOY_VALUE, "o": _TOY_VALUE,
            "q": 0, "k": 0, "v": 0, "gate": 0, "up": 0, "rest": 0}
    assert sorted(sets) == sorted(("stack_kept_bytes", n) for n in want)
    gauges = METRICS.delta(None)["gauges"]
    assert {n: gauges[f'stack_kept_bytes{{name="{n}"}}']
            for n in want} == want
    # everything kept: each projection's result at its size, and the rest
    _, grad, params = _toy_stack_gradient(monkeypatch, 1 << 40)
    gauges = METRICS.delta(None)["gauges"]
    assert gauges['stack_kept_bytes{name="gate"}'] == _TOY_VALUE * 96 // 64
    assert gauges['stack_kept_bytes{name="q"}'] == _TOY_VALUE
    assert gauges['stack_kept_bytes{name="rest"}'] == _TOY_VALUE * (
        4 * 64 + 96) // 64


# ------------------------------------------------------ the loss's two parts

def test_exit_distribution_sums_to_one_and_meets_the_reference():
    s = 3.0 * jax.random.normal(jax.random.PRNGKey(3), (4, 5, 7))
    p = jnp.exp(objectives.exit_log_probs(s))
    _close(p.sum(0), jnp.ones((5, 7)), tol=1e-6)
    lam = jax.nn.sigmoid(s)
    _close(p[0], lam[0])
    _close(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]))
    _close(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]))
    _close(p, ref.exit_distribution(lam))


def _unchunked(y, heads, beta):
    """The formula as written: all T x tokens x V logits at once."""
    logits = jnp.einsum("tbld,dv->tblv", heads.hidden, heads.kernel)
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                              y[None, ..., None], -1)[..., 0]
    lam = jax.nn.sigmoid(heads.gate_logits)
    p = ref.exit_distribution(lam)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy)


@pytest.mark.parametrize("chunk", [8, 32, 128])
def test_chunked_head_and_loss_equal_the_unchunked_formula(world, chunk,
                                                           monkeypatch):
    cfg, net, p, pp, ids, y = world
    # B x 32 = 128 tokens, 4 passes, 128 words: this budget gives the chunk
    monkeypatch.setattr(objectives, "_HEAD_CHUNK_BYTES", 4 * 4 * 128 * chunk)
    assert objectives._head_chunk(B * 32, 4, 128) == chunk

    def both(pp, fn):
        heads, _ = net.call(pp, {}, ids, training=True)
        return fn(heads)

    chunked = lambda h: objectives.expected_exit_crossentropy(y, h)
    whole = lambda h: _unchunked(y, h, cfg["exit_entropy_beta"])
    lc, gc = jax.value_and_grad(both)(pp, chunked)
    lw, gw = jax.value_and_grad(both)(pp, whole)
    assert abs(float(lc) - float(lw)) < 2e-6 * float(lw)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (gc, gw))):
        _close(a, b, tol=5e-5)


def test_head_chunk_follows_from_the_shapes():
    # 512 MiB of float32 logits: 4 passes x 512 tokens x 49,152
    assert objectives._head_chunk(8192, 4, 49152) == 512
    assert objectives._head_chunk(128, 4, 128) == 128       # all at once
    assert objectives._head_chunk(8190, 4, 49152) == 630    # a divisor


def test_loss_takes_plain_logits_as_token_level_crossentropy():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 11))
    y = jnp.arange(10).reshape(2, 5) % 11
    _close(objectives.expected_exit_crossentropy(y, logits),
           objectives.sparse_categorical_crossentropy_with_logits(y, logits))


def test_exit_heads_keep_the_models_type_through_a_cast():
    heads = objectives.ExitHeads(jnp.zeros((2, 1, 4, 8), jnp.bfloat16),
                                 jnp.zeros((2, 1, 4), jnp.bfloat16),
                                 jnp.zeros((8, 16), jnp.bfloat16), 0.25)
    cast = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), heads)
    assert cast.hidden.dtype == jnp.float32 and cast.dtype == "bfloat16"
    assert cast.entropy_beta == 0.25


# --------------------------------------------------- each part, its formula

def test_rmsnorm_against_its_formula():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16)) * 4.0
    layer = RMSNorm(epsilon=1e-6, name="rms_t")
    params = {"gamma": jnp.linspace(0.5, 1.5, 16)}
    assert layer.build_params(None, x.shape)["gamma"].shape == (16,)
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6) \
        * params["gamma"]
    _close(layer.forward(params, x), want, tol=1e-6)
    half = layer.forward(params, x.astype(jnp.bfloat16))
    assert half.dtype == jnp.bfloat16
    _close(half, want, tol=2e-2)


def test_rotary_against_its_formula():
    l, d, theta = 12, 8, 1e6
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, l, d))
    got = RotaryEmbedding(theta, name="rope_t").forward({}, x)
    want = np.zeros_like(x)
    for m in range(l):
        for i in range(d // 2):
            a = m * theta ** (-2.0 * i / d)
            x1, x2 = x[..., m, i], x[..., m, i + d // 2]
            want[..., m, i] = x1 * np.cos(a) - x2 * np.sin(a)
            want[..., m, i + d // 2] = x2 * np.cos(a) + x1 * np.sin(a)
    _close(got, want, tol=1e-5)
    _close(got[..., 0, :], x[..., 0, :])          # position 0: no turn
    # scores depend on the distance only
    q = RotaryEmbedding(theta).forward({}, jnp.broadcast_to(x[0, 0, :1],
                                                             (l, d)))
    k = RotaryEmbedding(theta).forward({}, jnp.broadcast_to(x[0, 1, :1],
                                                             (l, d)))
    scores = q @ k.T
    _close(scores[3, 1], scores[9, 7], tol=1e-5)
    _close(got, ref._rotary(x, theta), tol=1e-6)


def test_gated_ffn_against_its_formula():
    layer = GatedFFN(16, 24, name="ffn_t")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16))
    p = layer.build_params(jax.random.PRNGKey(1), x.shape)
    assert set(p) == {"gate", "up", "down"}
    assert all(set(v) == {"kernel"} for v in p.values())      # no biases
    g = x @ p["gate"]["kernel"]
    want = ((g / (1 + np.exp(-g))) * (x @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]
    _close(layer.forward(p, x), want, tol=1e-5)


def test_sandwich_block_has_four_norms_and_no_bias():
    block = SandwichDecoderBlock(2, 16, 24, rotary_theta=1e4, name="sw_t")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 16))
    p = block.build_params(jax.random.PRNGKey(1), x.shape)
    assert {"norm1", "norm2", "norm3", "norm4", "attn", "ffn"} == set(p)
    assert all("bias" not in p["attn"][n] for n in "qkvo")
    n = lambda i, v: block.norm.forward(p[f"norm{i}"], v)
    a = x + n(2, block.attn.forward(p["attn"], n(1, x)))
    _close(block.forward(p, x), a + n(4, block.ffn.forward(p["ffn"],
                                                           n(3, a))))
    # causal: a later token does not move an earlier one
    moved = block.forward(p, x.at[:, -1].add(1.0))
    _close(moved[:, :-1], block.forward(p, x)[:, :-1])


def test_attention_keeps_its_biases_unless_told():
    """BERT's attention is what it was: four kernels, four biases."""
    mha = MultiHeadAttention(2, 16, name="mha_t")
    p = mha.build_params(jax.random.PRNGKey(0), (1, 4, 16))
    assert all(set(p[n]) == {"kernel", "bias"} for n in "qkvo")
    assert mha.rotary is None


# -------------------------------------------------------- flash attention --

def test_flash_kernels_interpreted_meet_the_reference_attention():
    """Causal, L 256, D 128: the forward and both backward kernels, as the
    looped stack calls them, in interpret mode."""
    q, k, v = (jax.random.normal(key, (1, 2, 256, 128))
               for key in jax.random.split(jax.random.PRNGKey(0), 3))

    def flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, True, None, 128,
                                               128, True)))

    def plain(q, k, v):
        return jnp.sum(jnp.sin(reference_attention(q, k, v, causal=True)))

    _close(flash_attention(q, k, v, True, None, 128, 128, True),
           reference_attention(q, k, v, causal=True), tol=1e-5)
    for a, b in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                    jax.grad(plain, (0, 1, 2))(q, k, v)):
        _close(a, b, tol=1e-4)


def test_the_cells_attention_call_selects_the_pallas_kernel(monkeypatch):
    """On a TPU the stack's call (causal, no mask, no dropout, L 4,096,
    D 128) goes to the kernel by the dispatch's own rule, and a short or
    masked call does not."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)

    def chosen(l, d, **kw):
        mark = METRICS.snapshot()
        x = jax.ShapeDtypeStruct((2, 16, l, d), jnp.bfloat16)
        try:
            jax.eval_shape(lambda q: dot_product_attention(
                q, q, q, causal=True, **kw), x)
        except Exception:       # the CPU cannot lower the TPU kernel
            pass
        return [k for k in METRICS.delta(mark)["counters"]
                if k.startswith("ops_kernel_selected_total")]

    assert chosen(4096, 128) == [
        'ops_kernel_selected_total{kernel="flash_attention",path="pallas"}']
    assert "reference" in chosen(512, 128)[0]
    # since PR 36 a head of 64 reaches the kernel padded to 128 lanes
    # (ops/attention.py); a narrower one stays on the XLA paths
    assert "pallas" in chosen(4096, 64)[0]
    assert "reference" in chosen(4096, 32)[0]
    assert "reference" in chosen(4096, 128,
                                 mask=jnp.ones((2, 1, 1, 4096)))[0]


# ------------------------------------------------------------ through fit --

class LazyRows:
    """Labels as a user's lazy array-like: a shape, a dtype, fancy
    indexing; never turned into one array."""

    def __init__(self, a):
        self.a, self.shape, self.dtype, self.ndim = a, a.shape, a.dtype, 2

    def __len__(self):
        return self.shape[0]

    def __getitem__(self, idx):
        # a row at a time is how numpy would build one array of it
        assert not np.isscalar(idx), "fit turned the labels into one array"
        return self.a[idx]


def test_two_fit_steps_follow_the_reference():
    """``compile(...).fit(ids, next_ids)`` with (rows, L) labels handed in
    as an array-like: two Adam steps, in order, against two steps of the
    reference under the harness's plain Adam."""
    from analytics_zoo_tpu.models import LoopedLM
    from analytics_zoo_tpu.train.optimizers import Adam

    init_zoo_context()
    cfg = _cfg()
    opt = dict(cfg["deployment"]["optimizer"], lr=1e-3)
    model = LoopedLM.from_config(cfg)
    model.compile(optimizer=Adam(lr=opt["lr"], beta_1=opt["beta_1"],
                                 beta_2=opt["beta_2"],
                                 epsilon=opt["epsilon"]),
                  loss="expected_exit_crossentropy")
    p = ref.init_params(jax.random.PRNGKey(5), cfg)
    net = model.model
    # the step donates what it is given: the program gets a copy
    net.set_initial_weights(mod.to_program(
        jax.tree_util.tree_map(jnp.copy, p), net, None))
    batch = 8               # the test's eight virtual devices share a batch
    (ids,), y = mod.make_data(cfg, 3, 2 * batch)
    mark = METRICS.snapshot()
    hist = model.fit(ids, LazyRows(y), batch_size=batch, nb_epoch=1,
                     shuffle=False, verbose=False)
    assert net.estimator.last_data_path == "host_prefetch"
    assert net.estimator.global_step == 2
    counters = METRICS.delta(mark)["counters"]
    assert counters["train_tokens_total"] == 2 * batch * 32
    state = plain_optim.init_state(opt, p)
    p0, losses = p, []
    for k in range(2):
        rows = slice(k * batch, (k + 1) * batch)
        loss, grads = jax.value_and_grad(lambda p: ref.loss_fn(
            p, (jnp.asarray(ids[rows]),), jnp.asarray(y[rows]), cfg))(p)
        p, state = plain_optim.apply(opt, p, grads, state)
        losses.append(float(loss))
    assert abs(hist[-1]["loss"] - np.mean(losses)) < 1e-5 * np.mean(losses)
    # Adam divides a gradient by its own size, so where a gradient is all
    # rounding the two may step apart: held leaf by leaf against the change
    got = mod.from_program(net.estimator.params, net)
    for a, b, b0 in zip(*map(jax.tree_util.tree_leaves, (got, p, p0))):
        moved = float(jnp.linalg.norm(b - b0))
        assert moved > 0
        assert float(jnp.linalg.norm(a - b)) <= 0.02 * moved, (
            float(jnp.linalg.norm(a - b)), moved)


def test_from_config_refuses_what_the_program_lacks():
    from analytics_zoo_tpu.models import LoopedLM

    with pytest.raises(ValueError, match="grouped-query"):
        LoopedLM.from_config(dict(_cfg(), num_key_value_heads=1))
    with pytest.raises(ValueError, match="head_dim"):
        LoopedLM.from_config(dict(_cfg(), head_dim=64))
    model = LoopedLM.from_config(_cfg())
    assert model.config()["total_ut_steps"] == 4
    assert model.config()["rope_theta"] == 1000000


def test_entropy_weight_comes_from_the_config(world):
    """``exit_entropy_beta`` reaches the loss through the model, so that a
    changed configuration changes program and reference together."""
    cfg, net, p, pp, ids, y = world
    other = dict(cfg, exit_entropy_beta=0.5)
    heads, _ = mod.build(other).call(pp, {}, ids, training=True)
    assert heads.entropy_beta == 0.5
    got = objectives.expected_exit_crossentropy(y, heads)
    want = ref.loss_fn(p, (ids,), y, other)
    assert abs(float(got) - float(want)) < 2e-6 * float(want)
    assert abs(float(want) - float(ref.loss_fn(p, (ids,), y, cfg))) \
        > 1e-3 * float(want)
