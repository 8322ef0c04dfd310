"""The ``granite-4.0-h-micro`` cell's own files, rehearsed on the CPU at a tiny
size: a tiny hybrid decoder added to a copy of the benchmark as ``tiny.py``
adds its cells, driven through ``run.measure`` by the ``fit_tokens`` driver
against the step-at-a-time reference; ``work`` and the scan's own count
against hand counts; the three readers on a made-up reduction; the published
keys; the control, the half batch and both planted faults coming out not
correct by the comparison's own judgement; and the vocabulary's slice tied to
the whole model."""

import json
import os
import sys
import time

import numpy as np
import pytest

import tiny

if os.path.join(tiny.REPO, "benchmark") not in sys.path:
    sys.path.insert(0, os.path.join(tiny.REPO, "benchmark"))

NAME = "granite-4.0-h-micro"
REAL_CELL = NAME + "-fit-packed4k"
CELL = "granite-tiny-fit"
# float32 program against float32 reference: rounding only
LIMITS = dict(loss=1e-4, grad_norm=2e-3, delta_norm=5e-3)
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=96, shared_intermediate_size=96,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=8,
            mamba_chunk_size=8, vocab_size=128, seq_len=32,
            num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"])


@pytest.fixture(scope="module", autouse=True)
def _default_context_afterwards():
    """A run seeds the program's global context from its own seed: leave
    the default one behind for the tests this worker runs next."""
    yield
    from analytics_zoo_tpu import init_zoo_context

    init_zoo_context()


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def _config():
    with open(os.path.join(tiny.REPO, "benchmark", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def _module(kind):
    from harness import spec

    return spec.load_module(os.path.join(tiny.REPO, "benchmark", kind,
                                         NAME + ".py"))


def _tiny_config(**over):
    cfg = _config()
    cfg.update(TINY, **over)
    cfg["deployment"].update(batch_per_chip=4, compute_dtype="float32")
    cfg["deployment"]["optimizer"]["lr"] = 1e-3
    return cfg


@pytest.fixture(scope="module")
def granite_tree(tmp_path_factory):
    """``tiny.make_tree`` plus a tiny hybrid decoder: new files and new
    entries."""
    root = tiny.make_tree(str(tmp_path_factory.mktemp("granite_checkout")))
    bench_dir = os.path.join(root, "benchmark")
    _dump(_tiny_config(name="granite-tiny", module=NAME),
          os.path.join(bench_dir, "configs", "granite-tiny.json"))
    with open(os.path.join(bench_dir, "traffic",
                           "fit-host-packed4k.json")) as f:
        traffic = json.load(f)
    traffic.update(rows=1000000, pool_rows=96, open_after_steps=2)
    _dump(traffic, os.path.join(bench_dir, "traffic",
                                "fit-host-packed-tiny.json"))
    _dump({"limits": LIMITS},
          os.path.join(bench_dir, "limits", CELL + ".json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(
        name="granite-tiny", source="a test's cut of the published one",
        file="benchmark/configs/granite-tiny.json", reduced=[], why="test"))
    bench["workloads"].append(dict(
        name=CELL, config="granite-tiny", traffic="fit-host-packed-tiny",
        chips=1, why="test"))
    for m in bench["per_layer"]:
        if REAL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root


@pytest.fixture(scope="module")
def granite_harness(granite_tree):
    return tiny.harness_of(granite_tree)


def _bench():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _entry(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, (name, [e["name"] for e in entries])
    return found[0]


# ------------------------------------------------------- BENCHMARK.json ---

def test_benchmark_json_holds_the_configuration_and_the_cell():
    bench = _bench()
    config = _entry(bench["configs"], NAME)
    assert config["file"] == f"benchmark/configs/{NAME}.json"
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    assert config["source"] == ("https://huggingface.co/ibm-granite/"
                                "granite-4.0-h-micro/blob/main/config.json")
    cell = _entry(bench["workloads"], REAL_CELL)
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        NAME, 1, "fit-host-packed4k")
    assert len(cell["why"]) <= 200 and "quarter" in cell["why"]


@pytest.mark.parametrize("name,unit,better,layer", [
    ("ssm_mixer_ms.fit", "ms", "lower", "model step"),
    ("ssm_scan_ms.fit", "ms", "lower", "model step"),
    ("ssm_scan_roofline_pct.fit", "%", "higher", "kernels")])
def test_the_three_metrics_list_the_cell_alone(name, unit, better, layer):
    assert _entry(_bench()["per_layer"], name) == dict(
        name=name, unit=unit, better=better, source="device_trace",
        layer=layer, moves="fit_samples_per_s", workloads=[REAL_CELL])


def test_the_cell_reads_the_shared_metrics_and_its_own_three():
    from harness import spec

    bench = _bench()
    c = spec.load_cell(tiny.REPO, REAL_CELL)
    shared = [m["name"] for m in bench["per_layer"] if "workloads" not in m]
    assert len(shared) == 9
    assert [m["name"] for m in c.per_layer] == shared + [
        "ssm_mixer_ms.fit", "ssm_scan_ms.fit", "ssm_scan_roofline_pct.fit"]
    assert callable(c.config_mod.attention_kernel_work)
    assert c.traffic["driver"] == "fit_tokens"
    assert set(c.limits["limits"]) == {"loss", "grad_norm",
                                       "delta_norm_median"}
    for other in ("resnet50-fit-host", "bert-base-fit-host",
                  "ouro-2.6b-fit-packed4k"):
        names = {m["name"] for m in spec.load_cell(tiny.REPO,
                                                   other).per_layer}
        assert not any(n.startswith("ssm_") for n in names)


def test_configuration_keeps_the_published_keys():
    """Every key of the catalog row at its value, but the three reduced."""
    cfg = _config()
    published = dict(
        attention_bias=False, attention_multiplier=0.015625,
        embedding_multiplier=12, hidden_act="silu", hidden_size=2048,
        intermediate_size=8192, logits_scaling=8, mamba_chunk_size=256,
        mamba_conv_bias=True, mamba_d_conv=4, mamba_d_head=64,
        mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
        mamba_n_heads=64, mamba_proj_bias=False,
        max_position_embeddings=131072, model_type="granitemoehybrid",
        normalization_function="rmsnorm", num_attention_heads=32,
        num_experts_per_tok=0, num_key_value_heads=8, num_local_experts=0,
        position_embedding_type="nope", residual_multiplier=0.22,
        rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
        shared_intermediate_size=8192, tie_word_embeddings=True)
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "vocab_size"]
    assert cfg["num_hidden_layers"] == 10
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["vocab_size"] == 12544 == 100352 // 8
    for item in ("seq_len", "norm placement", "mamba", "attention",
                 "document mask", "initialisation", "data", "optimizer"):
        assert item in cfg["assumed"], item
    assert cfg["deployment"]["loss"] == "chunked_token_crossentropy"
    assert cfg["deployment"]["batch_per_chip"] == 2


# ---------------------------------------------------------- hand counts ---

def test_work_against_a_hand_count():
    mod, cfg = _module("configs"), _config()
    mixer = (2048 * 8512 + 4096 * 2048 + 4352 * 4 + 4352 + 3 * 64 + 4096)
    assert mod.mixer_params(cfg) == mixer == 25_847_232
    mlp = 3 * 2048 * 8192
    assert mod.layer_params(cfg, "mamba") == mixer + mlp + 4096 == 76_182_976
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert mod.layer_params(cfg, "attention") == attn + mlp + 4096 \
        == 60_821_504
    params = 9 * 76_182_976 + 60_821_504 + 2048 + 12544 * 2048
    assert mod.param_count(cfg) == params == 772_160_448
    # a token's scan in a layer: C B^T and its product with delta x at the
    # causal half of a chunk of 256, the chunk's state, the carried state
    scan = 128 * 128 + 64 * 128 * 64 + 2 * 64 * 64 * 128
    assert mod.scan_macs_per_token(cfg) == scan == 1_589_248
    mamba = 2048 * 8512 + 4096 * 2048 + 4 * 4352 + scan + mlp
    assert mamba == 77_759_488
    attention = attn + 2 * 2048 * 2048 + mlp
    assert attention == 69_206_016
    macs = 9 * mamba + attention + 12544 * 2048
    assert mod.forward_macs_per_token(cfg) == macs == 794_731_520
    w = mod.work(cfg, 2)
    assert w["flops"] == 2 * 3 * macs * 8192
    assert w["flops"] == pytest.approx(39.06e12, rel=1e-3)
    assert w["bytes"] == 32 * params + 2 * 4 * 8192
    assert w["samples"] == 2
    # the nine layers' scans, forward and backward twice: 0.7 TFLOP and
    # 3.2 GB (5 x-wide, 6 state-wide, 3 head-wide bfloat16 values a token)
    assert w["ssm_scan"] == mod.scan_work(cfg, 2)
    assert w["ssm_scan"]["flops"] == 2 * 3 * 9 * 8192 * scan
    assert w["ssm_scan"]["bytes"] == 2 * 9 * 8192 * (5 * 4096 + 6 * 128
                                                     + 3 * 64)
    assert w["ssm_scan"]["flops"] < 0.02 * w["flops"]


def test_attention_kernel_count_against_a_hand_count():
    mod, cfg = _module("configs"), _config()
    k = mod.attention_kernel_work(cfg, 2)
    scores = 4096 * 4097 // 2
    # 2 sequences x 32 heads x 1 layer; forward twice, backward once: 9
    # products of 2 * 64 FLOPs a score
    assert k["flops"] == 2 * 32 * scores * 2 * 64 * 9
    # (4,096 x 64) bfloat16 tensors: 2 x (2 x 32 + 2 x 8) forward, 4 x 32
    # + 4 x 8 backward
    assert k["bytes"] == 2 * (2 * 80 + 160) * 4096 * 64 * 2
    assert k["flops"] < 0.02 * mod.work(cfg, 2)["flops"]


def _run(scopes, steps=2, work=None):
    return {"trace": {"steps": steps, "scope_seconds_under": scopes},
            "chips": 1, "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e9},
            "work": {"flops": 1e12, "bytes": 1e9, "samples": 2,
                     **({"ssm_scan": work} if work else {})},
            "window": {}}


SCOPES = {"zoo:lm/stack": 1.8, "zoo:ssm/mixer": 0.9, "zoo:ssm/scan": 0.5,
          "zoo:lm/attn": 0.1}


def _reader(name):
    from harness import spec

    return spec.load_cell(tiny.REPO, REAL_CELL).layer_metric_reader(name)


@pytest.mark.parametrize("work,share", [
    # 50 GFLOP at 1 TFLOP/s: 50 ms a step (the bytes: 10 ms); the scans ran
    # 0.5 s in 2 steps, 250 ms a step: 20 %
    ({"flops": 50e9, "bytes": 10e6}, 20.0),
    # 100 MB at 1 GB/s: 100 ms a step binds: 40 %
    ({"flops": 50e9, "bytes": 100e6}, 40.0)])
def test_the_readers_against_a_hand_count(work, share):
    run = _run(SCOPES, work=work)
    assert _reader("ssm_mixer_ms.fit")(run) == pytest.approx(450.0)
    assert _reader("ssm_scan_ms.fit")(run) == pytest.approx(250.0)
    assert _reader("ssm_scan_roofline_pct.fit")(run) == pytest.approx(share)
    assert _reader("ssm_scan_roofline_pct.fit")(dict(run, chips=4)) == \
        pytest.approx(share / 4)


@pytest.mark.parametrize("name", ["ssm_mixer_ms.fit", "ssm_scan_ms.fit",
                                  "ssm_scan_roofline_pct.fit"])
def test_a_reader_reads_nothing_where_nothing_is_to_be_read(name):
    """No such scope (the parent's program), no device trace, no steps, and
    for the share no peaks or no work named: nothing, never a 0."""
    read = _reader(name)
    work = {"flops": 50e9, "bytes": 10e6}
    assert read(_run(SCOPES, work=work)) is not None
    assert read(_run({"zoo:lm/stack": 1.8}, work=work)) is None
    assert read(_run({}, work=work)) is None
    assert read(_run(SCOPES, steps=0, work=work)) is None
    assert read(dict(_run(SCOPES, work=work), trace=None)) is None
    if "roofline" in name:
        assert read(_run(SCOPES)) is None
        assert read(dict(_run(SCOPES, work=work), peaks=None)) is None


# ------------------------------------------------- through the harness ---

def _measure(harness, tree, monkeypatch, seed, trace):
    bench_run, spec = harness
    bench_run.ROOT = tree
    cell = spec.load_cell(tree, CELL)
    tiny.only_chips(monkeypatch, 1)
    return cell, bench_run.measure(cell, seed, 0.3, trace,
                                   time.perf_counter())


def test_cell_runs_and_meets_its_reference(granite_harness, granite_tree,
                                           monkeypatch):
    cell, out = _measure(granite_harness, granite_tree, monkeypatch,
                         2 ** 31 + 4321, trace=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"fit_samples_per_s", "setup_s"}
    assert out["notes"]["data path"] == "host_prefetch"
    exact = {"rows_asked_twice", "compiles_in_window", "data_path_differs"}
    assert set(out["checks"]) == set(LIMITS) | exact
    assert all(out["checks"][k] == [0.0, 0.0] for k in exact)
    win = out["run"]["window"]
    assert win["samples"] == win["steps"] * 4 and win["steps"] > 0
    counters = out["notes"]["program counters"]
    tokens = [v for k, v in counters.items()
              if k.startswith("train_tokens_total")]
    assert tokens and tokens[0] >= (3 + 2 + win["steps"]) * 4 * 32
    for kernel in ("ssm_scan", "flash_attention"):
        assert any(k.startswith("ops_kernel_selected_total") and kernel in k
                   and v > 0 for k, v in counters.items()), counters
    assert out["run"]["work"]["ssm_scan"] == cell.config_mod.scan_work(
        cell.config, 4)
    assert out["run"]["attention_kernel_work"] == \
        cell.config_mod.attention_kernel_work(cell.config, 4)


def test_cell_traced_reads_what_a_cpu_trace_holds(granite_harness,
                                                  granite_tree, monkeypatch):
    """``--trace 1`` on the CPU: no device plane, so the three readers of
    the device trace leave their metrics out and raise nothing."""
    cell, out = _measure(granite_harness, granite_tree, monkeypatch, 11,
                         trace=True)
    assert out["correct"], out["checks"]
    names = [m["name"] for m in cell.per_layer]
    assert names[-3:] == ["ssm_mixer_ms.fit", "ssm_scan_ms.fit",
                          "ssm_scan_roofline_pct.fit"]
    assert set(out["metrics"]) <= set(names)
    assert "dispatch_ms.fit" in out["metrics"]
    assert not any(n.startswith("ssm_") for n in out["metrics"])


def test_limits_readings_tell_the_faults_from_the_model(
        granite_harness, granite_tree, monkeypatch, tmp_path, capsys):
    """``limits_planted.py`` as it is run on the chip, on one seed: by
    ``compare.judge`` under the cell's limits the program is correct, and
    the control in fp8, the half batch, the scan that carries no state from
    chunk to chunk and the attention scaled by 1 / sqrt(D) each are not."""
    from harness import compare

    bench_run, spec = granite_harness
    limits = spec.load_cell(granite_tree, CELL).limits
    tiny.only_chips(monkeypatch, 1)
    import limits_planted

    monkeypatch.setattr(limits_planted, "ROOT", granite_tree)
    monkeypatch.setattr(bench_run, "require_chips", lambda chips: None)
    monkeypatch.setattr(bench_run, "place_compile_cache", lambda: None)
    out = tmp_path / "readings.json"
    assert limits_planted.main(["--workload", CELL, "--seeds", "1",
                                "--control-seeds", "1", "--first-seed",
                                str(2 ** 31 + 9), "--out", str(out)]) == 0
    capsys.readouterr()
    doc = json.loads(out.read_text())
    (seed,) = doc["program"]
    ok, checks = compare.judge(doc["program"][seed], limits)
    assert ok and set(checks) == set(LIMITS), checks
    assert set(doc["faults"]) == {"control_fp8", "half_batch",
                                  "no_carried_state",
                                  "attention_scale_rsqrt"}
    for fault, readings in doc["faults"].items():
        ok, checks = compare.judge(readings[seed], limits)
        assert not ok, (fault, checks)


# ------------------------------------------- the slice tied to the model ---

def test_the_slices_logits_are_the_first_eighth_of_the_whole_models():
    """A sliced vocabulary is a smaller vocabulary: with the whole tiny
    vocabulary's weights, the model that holds rows 0..V/8-1 gives, on ids
    of the slice, the first eighth of the whole model's logits, in the
    reference and in the program."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu import init_zoo_context

    init_zoo_context()
    mod, ref = _module("configs"), _module("references")
    whole = _tiny_config()
    part = _tiny_config(vocab_size=whole["vocab_size"] // 8)
    p = ref.init_params(jax.random.PRNGKey(3), whole)
    p_part = dict(p, embed=p["embed"][:part["vocab_size"]])
    ids = np.random.default_rng(5).integers(
        0, part["vocab_size"], (2, whole["seq_len"]), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda i: ref.logits(p, i, whole))(jnp.asarray(ids))
        got = jax.vmap(lambda i: ref.logits(p_part, i, part))(
            jnp.asarray(ids))
        np.testing.assert_allclose(got, want[..., :part["vocab_size"]],
                                   atol=1e-6)
        for cfg, params in ((whole, p), (part, p_part)):
            net = mod.build(cfg)
            out, _ = net.call(mod.to_program(params, net, None),
                              {net.stack.name: {}}, jnp.asarray(ids))
            np.testing.assert_allclose(
                out, want[..., :cfg["vocab_size"]], atol=2e-5)


def test_data_is_the_next_token_at_every_position():
    mod = _module("configs")
    cfg = dict(_config(), seq_len=16, vocab_size=50)
    (ids,), y = mod.make_data(cfg, 2 ** 31 + 5, 6)
    assert ids.shape == y.shape == (6, 16) and ids.dtype == np.int32
    assert (ids[:, 1:] == y[:, :-1]).all()
    assert ids.min() >= 0 and ids.max() < 50
    (again,), _ = mod.make_data(cfg, 2 ** 31 + 5, 3)
    assert (again == ids[:3]).all()
