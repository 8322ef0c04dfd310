"""The arithmetic kept with the benchmark, against hand counts; the peaks
table; the contract's static limits on ``BENCHMARK.json``; and ``run.py``
without a chip."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import tiny

_, spec = tiny.harness_of(tiny.REPO)
from harness import peaks  # noqa: E402

with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def _config(name):
    with open(os.path.join(tiny.REPO, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    mod = spec.load_module(os.path.join(tiny.REPO, "benchmark", "configs",
                                        name + ".py"))
    return cfg, mod


def test_resnet50_forward_is_the_papers_3_8_g_multiply_adds():
    cfg, mod = _config("resnet50-imagenet")
    # the stem by hand: 112 x 112 outputs, 7 x 7 x 3 inputs, 64 filters
    assert mod._convs(cfg)[0] == (112, 7, 7, 3, 64)
    assert 112 * 112 * 7 * 7 * 3 * 64 == 118_013_952
    # stage 0, block 0 by hand at 56 x 56: proj 64->256, a 64->64,
    # b 3x3 64->64, c 64->256
    s0b0 = 56 * 56 * (64 * 256 + 64 * 64 + 9 * 64 * 64 + 64 * 256)
    assert sum(h * h * kh * kw * ci * co
               for h, kh, kw, ci, co in mod._convs(cfg)[1:5]) == s0b0
    assert len(mod._convs(cfg)) == 53
    # He et al. table 1 gives 3.8e9 for the 50-layer net, whose stride
    # sits on each stage's first 1x1 convolution, as the program's does
    # (the ~4.1e9 often quoted is the variant with the stride on the 3x3)
    assert mod.forward_macs(cfg) == 3_857_973_248
    assert mod.param_count(cfg) == 25_557_032        # the published count


def test_resnet50_step_work():
    cfg, mod = _config("resnet50-imagenet")
    w = mod.work(cfg, 256)
    per_image = w["flops"] / 256
    assert 22.8e9 < per_image < 23.2e9               # ~22.9 GFLOP an image
    stem = 118_013_952
    assert w["flops"] == 2.0 * 256 * (3 * mod.forward_macs(cfg) - stem)
    # 25.6 M parameters x (weights + gradients + momentum) x 8 bytes, and
    # 256 float32 images
    assert w["bytes"] == 24.0 * 25_557_032 + 256 * (224 * 224 * 3 * 4 + 4)


def test_bert_base_counts():
    cfg, mod = _config("bert-base-uncased")
    # one block by hand: four 768 x 768 matrices and two 768 x 3072, their
    # biases, two layer norms
    block = (4 * (768 * 768 + 768) + 2 * 768 * 3072 + 3072 + 768
             + 2 * 2 * 768)
    assert mod.block_params(cfg) == block == 7_087_872
    assert 12 * block == 85_054_464                  # ~85 M non-embedding
    # with the pooler and a head of 14 classes (109,482,240 without a head)
    assert mod.param_count(cfg) == 109_482_240 + 14 * 769 == 109_493_006
    per_token = 12 * (4 * 768 * 768 + 2 * 768 * 3072)
    attention = 12 * 2 * 512 * 512 * 768
    assert mod.forward_macs(cfg) == (512 * per_token + attention
                                     + 768 * 768 + 768 * 14)
    w = mod.work(cfg, 32)
    assert w["flops"] == 6.0 * 32 * mod.forward_macs(cfg)
    # ~85 M x 6 per token plus attention's products: ~292 GFLOP a sequence
    assert 285e9 < w["flops"] / 32 < 300e9


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_run_py_without_a_chip_exits_nonzero_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(tiny.REPO, "benchmark", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tiny.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode not in (0, None)
    assert "metrics" not in done.stdout and done.stdout.strip() == ""
    assert "TPU" in done.stderr


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert all(len(w["why"]) <= 200 for w in BENCH["workloads"])
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(tiny.REPO, c["file"]))
        assert c["file"].startswith(BENCH["paths"][0] + "/")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_file_a_cell_names_is_there(cell):
    """The harness finds the configuration, its builder and reference, the
    traffic, the limits and every per-layer reader by name alone."""
    c = spec.load_cell(tiny.REPO, cell)
    for fn in ("build", "make_data", "work", "to_program", "from_program"):
        assert callable(getattr(c.config_mod, fn))
    assert callable(c.reference.loss_fn) and callable(c.reference.init_params)
    assert set(c.limits["limits"]) <= {
        "loss", "grad_norm", "delta_norm", "grad_norm_median",
        "delta_norm_median"}
    assert c.limits["limits"], "a cell compares at least one number"
    assert callable(c.driver().run)
    # every cell reports the set-up time and another end-to-end metric: the
    # ones that list no cell and the ones that list this one
    reported = {m["name"] for m in c.end_to_end}
    assert reported == {m["name"] for m in BENCH["end_to_end"]
                        if "workloads" not in m or cell in m["workloads"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert {m["name"] for m in c.per_layer} == {
        m["name"] for m in BENCH["per_layer"]
        if ("workloads" not in m or cell in m["workloads"])
        and m["moves"] in reported}
    assert c.per_layer, "a cell reports at least one per-layer metric"
    for m in c.per_layer:
        assert callable(c.layer_metric_reader(m["name"]))
    for m in c.end_to_end:
        assert callable(c.end_to_end_reader(m["name"]))
    assert c.traffic["rows"] >= c.traffic["pool_rows"] >= 3 * 32


def test_rows_stand_for_a_data_set_larger_than_the_pool_and_note_what_is_asked():
    from harness.drivers.fit import Rows

    pool = np.arange(12, dtype=np.float32).reshape(4, 3)
    asked = []
    rows = Rows(pool, 10, asked)
    assert rows.shape == (10, 3) and len(rows) == 10 and rows.ndim == 2
    got = rows[np.asarray([9, 0, 5])]
    assert got.tolist() == [pool[1].tolist(), pool[0].tolist(),
                            pool[1].tolist()]
    assert rows[2:4].tolist() == pool[2:4].tolist()
    assert [a.tolist() for a in asked] == [[9, 0, 5], [2, 3]]
    assert Rows(pool, 10)[np.asarray([7])].tolist() == [pool[3].tolist()]


@pytest.mark.parametrize("asked,twice", [
    ([[0, 1], [2, 3], [4, 5]], 0),          # three batches of two
    ([[0, 1, 2, 3], [4, 5]], 0),            # two steps asked for at once
    ([[0, 1], [2, 1], [4, 5]], 1),          # a row asked for twice
    ([[0, 1], [2, 3]], 2),                  # a batch missing
    ([], 6),                                # nothing asked
])
def test_batches_asked_splits_in_order_and_counts_what_a_shuffle_may_not_do(
        asked, twice):
    from harness import compare

    out = compare.batches_asked([np.asarray(a) for a in asked], 2)
    assert out["twice"] == twice and len(out["batches"]) == compare.STEPS
    assert all(len(b) == 2 for b in out["batches"])
    if not twice:
        assert np.concatenate(out["batches"]).tolist() == [0, 1, 2, 3, 4, 5]
