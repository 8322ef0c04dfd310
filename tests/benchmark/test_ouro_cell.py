"""The ``ouro-2.6b`` cell's own files, rehearsed on the CPU at a tiny size: a
tiny looped decoder added to a copy of the benchmark as ``tiny.py`` adds its
cells, driven through ``run.measure`` by the ``fit_tokens`` driver, traced and
untraced; ``work`` and the attention kernels' count against hand counts; and
the control and the faults, the two readings of the mechanism among them (one
pass fewer, only the last pass's gradient), coming out not correct by the
comparison's own judgement."""

import json
import os
import sys
import time

import numpy as np
import pytest

import tiny

# the readers and the limits script import ``harness`` as ``run.py`` does
if os.path.join(tiny.REPO, "benchmark") not in sys.path:
    sys.path.insert(0, os.path.join(tiny.REPO, "benchmark"))

CELL = "ouro-tiny-fit"
# float32 program against float32 reference: rounding only
LIMITS = dict(loss=1e-4, grad_norm=2e-3, delta_norm=5e-3)

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


@pytest.fixture(scope="module")
def ouro_tree(tmp_path_factory):
    """``tiny.make_tree`` plus a tiny looped decoder: new files and new
    entries."""
    root = tiny.make_tree(str(tmp_path_factory.mktemp("ouro_checkout")))
    bench_dir = os.path.join(root, "benchmark")
    with open(os.path.join(bench_dir, "configs", "ouro-2.6b.json")) as f:
        cfg = json.load(f)
    cfg.update(name="ouro-tiny", module="ouro-2.6b", hidden_size=64,
               num_attention_heads=2, num_key_value_heads=2, head_dim=32,
               num_hidden_layers=3, layer_types=["full_attention"] * 3,
               intermediate_size=96, vocab_size=128, seq_len=32)
    cfg["deployment"].update(batch_per_chip=4, compute_dtype="float32")
    cfg["deployment"]["optimizer"]["lr"] = 1e-3
    _dump(cfg, os.path.join(bench_dir, "configs", "ouro-tiny.json"))
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
        name="ouro-tiny", source="a test's cut of the published one",
        file="benchmark/configs/ouro-tiny.json", reduced=[], why="test"))
    bench["workloads"].append(dict(
        name=CELL, config="ouro-tiny", traffic="fit-host-packed-tiny",
        chips=1, why="test"))
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root


@pytest.fixture(scope="module")
def ouro_harness(ouro_tree):
    return tiny.harness_of(ouro_tree)


def _measure(harness, tree, monkeypatch, seed, trace):
    bench_run, spec = harness
    bench_run.ROOT = tree
    cell = spec.load_cell(tree, CELL)
    tiny.only_chips(monkeypatch, 1)
    return cell, bench_run.measure(cell, seed, 0.3, trace,
                                   time.perf_counter())


def _bench():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _entry(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, (name, [e["name"] for e in entries])
    return found[0]


def test_benchmark_json_differs_by_appended_entries():
    """What PR 29 wrote is there under its names, wherever later entries
    put it in the lists: the configuration, the cell and the kernels'
    metric, which lists that cell alone."""
    bench = _bench()
    config = _entry(bench["configs"], "ouro-2.6b")
    assert config["file"] == "benchmark/configs/ouro-2.6b.json"
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                                "blob/main/config.json")
    cell = _entry(bench["workloads"], "ouro-2.6b-fit-packed4k")
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        "ouro-2.6b", 1, "fit-host-packed4k")
    flash = _entry(bench["per_layer"], "flash_attn_roofline_pct.fit")
    assert flash == dict(
        name="flash_attn_roofline_pct.fit", unit="%", better="higher",
        source="device_trace", layer="kernels", moves="fit_samples_per_s",
        workloads=["ouro-2.6b-fit-packed4k"])


@pytest.mark.parametrize("name", ["stack_ms.fit", "head_loss_ms.fit"])
def test_scope_metrics_list_the_cell_whose_step_has_the_scopes(name):
    """The looped decoder's two scopes as milliseconds a step: a share has
    no better direction."""
    assert _entry(_bench()["per_layer"], name) == dict(
        name=name, unit="ms", better="lower", source="device_trace",
        layer="model step", moves="fit_samples_per_s",
        workloads=["ouro-2.6b-fit-packed4k"])


@pytest.mark.parametrize("name", [w["name"] for w in _bench()["workloads"]])
def test_each_cell_finds_its_files_by_name(name):
    """What ``test_work_and_peaks``'s test of the same purpose checks, and
    that the driver's file is there, and that a cell reads exactly the
    metrics without a list of cells plus those that list it, in the file's
    order, whatever their number."""
    from harness import spec

    bench = _bench()
    c = spec.load_cell(tiny.REPO, name)
    for fn in ("build", "make_data", "work", "to_program", "from_program"):
        assert callable(getattr(c.config_mod, fn))
    assert callable(c.reference.loss_fn) and callable(c.reference.init_params)
    assert c.limits["limits"], "a cell compares at least one number"
    assert set(c.limits["limits"]) <= {
        "loss", "grad_norm", "delta_norm", "grad_norm_median",
        "delta_norm_median"}
    assert os.path.isfile(os.path.join(
        c.bench_dir, "harness", "drivers", c.traffic["driver"] + ".py"))
    assert callable(c.driver().run)
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    everywhere = [m["name"] for m in bench["per_layer"]
                  if "workloads" not in m and m["moves"] in reported]
    listed = [m["name"] for m in bench["per_layer"]
              if name in m.get("workloads", [])]
    mine = [m["name"] for m in bench["per_layer"]
            if m["name"] in everywhere + listed]
    assert [m["name"] for m in c.per_layer] == mine and everywhere
    assert ("flash_attn_roofline_pct.fit" in mine) == (
        name == "ouro-2.6b-fit-packed4k")
    for m in bench["per_layer"]:
        # a metric lists cells that are there, each of which reports the
        # end-to-end metric it moves
        for w in m.get("workloads", []):
            other = spec.load_cell(tiny.REPO, w)
            assert m["moves"] in {e["name"] for e in other.end_to_end}
    for m in c.per_layer:
        assert callable(c.layer_metric_reader(m["name"]))
    for m in c.end_to_end:
        assert callable(c.end_to_end_reader(m["name"]))
    assert c.traffic["rows"] >= c.traffic["pool_rows"] >= 3 * 32


def test_configuration_keeps_the_published_keys():
    """Every key of the catalog row at its value, but the two reduced."""
    cfg = _config()
    published = dict(
        head_dim=128, hidden_act="silu", hidden_size=2048,
        intermediate_size=5632, max_position_embeddings=65536,
        max_window_layers=48, model_type="ouro", num_attention_heads=16,
        num_key_value_heads=16, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
        total_ut_steps=4, early_exit_threshold=1, use_sliding_window=False,
        vocab_size=49152)
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert cfg["num_hidden_layers"] == 6
    assert cfg["layer_types"] == ["full_attention"] * 6
    for item in ("sandwich placement", "norm between passes", "exit gate",
                 "exit_entropy_beta", "biases", "document mask",
                 "early_exit_threshold", "optimizer"):
        assert item in cfg["assumed"], item


def test_cell_runs_and_meets_its_reference(ouro_harness, ouro_tree,
                                           monkeypatch):
    cell, out = _measure(ouro_harness, ouro_tree, monkeypatch,
                         2 ** 31 + 1234, trace=False)
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
    # the three compared steps, the two that fill the pipeline, the window
    assert tokens and tokens[0] >= (3 + 2 + win["steps"]) * 4 * 32
    assert any(k.startswith("ops_kernel_selected_total")
               and "flash_attention" in k for k in counters)
    assert out["notes"]["attention kernels asked for a step"] == \
        cell.config_mod.attention_kernel_work(cell.config, 4)
    assert out["run"]["attention_kernel_work"] == \
        out["notes"]["attention kernels asked for a step"]
    # the accepted driver's own session is back in its place
    from harness.drivers import fit, fit_tokens
    assert fit.Session is not fit_tokens.Session
    assert issubclass(fit_tokens.Session, fit.Session)


def test_cell_traced_reads_what_a_cpu_trace_holds(ouro_harness, ouro_tree,
                                                  monkeypatch):
    """``--trace 1`` on the CPU: no device plane, so the readers of the
    device trace leave their metrics out; the program's counters are read."""
    cell, out = _measure(ouro_harness, ouro_tree, monkeypatch, 7, trace=True)
    assert out["correct"], out["checks"]
    names = {m["name"] for m in cell.per_layer}
    # a cell that no metric lists reads the metrics that list no cell
    assert names == {m["name"] for m in _bench()["per_layer"]
                     if "workloads" not in m}
    assert set(out["metrics"]) <= names
    assert "dispatch_ms.fit" in out["metrics"]
    assert not os.path.exists(os.path.join(ouro_tree, ".bench_trace", CELL))


def test_a_run_that_fails_leaves_the_accepted_driver_as_it_was(
        ouro_harness, ouro_tree, monkeypatch):
    from harness.drivers import fit, fit_tokens

    _, spec = ouro_harness
    cell = spec.load_cell(ouro_tree, CELL)
    theirs = fit.Session
    monkeypatch.setattr(fit_tokens.Session, "setup",
                        lambda self, pool_rows=None: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        fit_tokens.run(cell, 3, 0.1, False, time.perf_counter())
    assert fit.Session is theirs


def test_limits_readings_tell_the_faults_from_the_model(
        ouro_harness, ouro_tree, monkeypatch, tmp_path, capsys):
    """``limits_tokens.py`` as it is run on the chip, on one seed: by
    ``compare.judge`` under the cell's limits the program is correct, and
    the control in fp8, the half batch, the loop with one pass fewer and
    the loop whose early passes hand no gradient back each are not."""
    from harness import compare

    bench_run, spec = ouro_harness
    limits = spec.load_cell(ouro_tree, CELL).limits
    tiny.only_chips(monkeypatch, 1)
    import limits_tokens

    monkeypatch.setattr(limits_tokens, "ROOT", ouro_tree)
    monkeypatch.setattr(bench_run, "require_chips", lambda chips: None)
    monkeypatch.setattr(bench_run, "place_compile_cache", lambda: None)
    out = tmp_path / "readings.json"
    assert limits_tokens.main(["--workload", CELL, "--seeds", "1",
                               "--control-seeds", "1", "--first-seed",
                               str(2 ** 31 + 9), "--out", str(out)]) == 0
    capsys.readouterr()
    doc = json.loads(out.read_text())
    (seed,) = doc["program"]
    ok, checks = compare.judge(doc["program"][seed], limits)
    assert ok and set(checks) == set(LIMITS), checks
    assert set(doc["faults"]) == {"control_fp8", "half_batch",
                                  "one_pass_fewer", "last_pass_gradient_only"}
    for fault, readings in doc["faults"].items():
        ok, checks = compare.judge(readings[seed], limits)
        assert not ok, (fault, checks)


# ---------------------------------------------------------- hand counts ---

def _config_mod():
    from harness import spec

    return spec.load_module(os.path.join(tiny.REPO, "benchmark", "configs",
                                         "ouro-2.6b.py"))


def _config():
    with open(os.path.join(tiny.REPO, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        return json.load(f)


def test_work_against_a_hand_count():
    mod, cfg = _config_mod(), _config()
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    assert mod.layer_params(cfg) == layer + 4 * 2048
    params = 6 * (layer + 4 * 2048) + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert mod.param_count(cfg) == params == 509_661_185
    # a token's forward: 24 layer applications (the matrices and the causal
    # half of 4,096 x 4,096 scores, twice), four heads
    macs = 24 * (layer + 2 * 2048 * 2048) + 4 * 2048 * 49152
    assert mod.forward_macs_per_token(cfg) == macs == 1_837_105_152
    w = mod.work(cfg, 2)
    assert w["flops"] == 2 * 3 * macs * 8192
    assert w["flops"] == pytest.approx(90.3e12, rel=1e-3)
    assert w["bytes"] == 32 * params + 2 * 4 * 8192
    assert w["samples"] == 2


def test_attention_kernel_count_against_a_hand_count():
    mod, cfg = _config_mod(), _config()
    k = mod.attention_kernel_work(cfg, 2)
    scores = 4096 * 4097 // 2                   # a causal head's
    heads = 2 * 16 * 24                         # batch x heads x applications
    # forward twice (2 products each), backward once (5): 9 products of
    # 2 * 128 FLOPs a score
    assert k["flops"] == heads * scores * 2 * 128 * 9
    assert k["flops"] == pytest.approx(14.85e12, rel=1e-3)
    # 2 x 4 + 8 tensors of 4,096 x 128 bfloat16
    assert k["bytes"] == heads * 16 * 4096 * 128 * 2
    # well under the whole step's count of the same products
    assert k["flops"] < mod.work(cfg, 2)["flops"]


def _flash_run(work, ops, steps=2):
    return {"trace": {"steps": steps, "op_seconds": ops}, "chips": 1,
            "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e9},
            "attention_kernel_work": work, "window": {}}


FLASH_OPS = {"flash_attention_fwd.3": 0.2, "flash_attention_fwd.7": 0.1,
             "flash_attention_dq.3": 0.1, "flash_attention_dkv.3": 0.1,
             "fusion.9": 5.0, "while.2": 7.0}


@pytest.mark.parametrize("work,share", [
    # 50 GFLOP at 1 TFLOP/s: 50 ms a step (the bytes: 10 ms); the four
    # kernels ran 0.5 s in 2 steps, 250 ms a step: 20 %
    ({"flops": 50e9, "bytes": 10e6}, 20.0),
    # 100 MB at 1 GB/s: 100 ms a step binds (the FLOPs: 50 ms): 40 %
    ({"flops": 50e9, "bytes": 100e6}, 40.0),
])
def test_flash_kernels_share_of_their_roofline_against_a_hand_count(
        work, share):
    from harness import spec

    cell = spec.load_cell(tiny.REPO, "ouro-2.6b-fit-packed4k")
    read = cell.layer_metric_reader("flash_attn_roofline_pct.fit")
    assert read(_flash_run(work, FLASH_OPS)) == pytest.approx(share)
    # four chips share the step's work; the first device's trace is read
    assert read(dict(_flash_run(work, FLASH_OPS), chips=4)) == \
        pytest.approx(share / 4)


def test_flash_reader_reads_nothing_where_nothing_is_to_be_read():
    """No kernel work named by the driver, no operation of that name, no
    device trace, no peaks: nothing, never a 0."""
    from harness import spec

    cell = spec.load_cell(tiny.REPO, "ouro-2.6b-fit-packed4k")
    read = cell.layer_metric_reader("flash_attn_roofline_pct.fit")
    work = {"flops": 50e9, "bytes": 10e6}
    run = _flash_run(work, FLASH_OPS)
    assert read(run) is not None
    del run["attention_kernel_work"]
    assert read(run) is None
    assert read(_flash_run(None, FLASH_OPS)) is None
    assert read(_flash_run(work, {"fusion.9": 5.0})) is None
    assert read(_flash_run(work, FLASH_OPS, steps=0)) is None
    assert read(dict(_flash_run(work, FLASH_OPS), trace=None)) is None
    assert read(dict(_flash_run(work, FLASH_OPS), peaks=None)) is None
    # and no other cell's line asks for it
    for other in ("resnet50-fit-host", "bert-base-fit-host"):
        assert "flash_attn_roofline_pct.fit" not in {
            m["name"] for m in spec.load_cell(tiny.REPO, other).per_layer}


def test_data_is_the_next_token_at_every_position():
    mod = _config_mod()
    cfg = dict(_config(), seq_len=16, vocab_size=50)
    (ids,), y = mod.make_data(cfg, 2 ** 31 + 5, 6)
    assert ids.shape == y.shape == (6, 16) and ids.dtype == np.int32
    assert (ids[:, 1:] == y[:, :-1]).all()
    assert ids.min() >= 0 and ids.max() < 50
    (again,), _ = mod.make_data(cfg, 2 ** 31 + 5, 3)
    assert (again == ids[:3]).all()
