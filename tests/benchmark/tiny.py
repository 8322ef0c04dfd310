"""Builds, in a directory of the test's own, a copy of the benchmark with
tiny cells ADDED to it as a later PR would add them: new configuration,
traffic and limits files and new entries in ``BENCHMARK.json``, and no edit
to a file that is there."""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The tiny cells compute in float32.  BERT's program and reference then
# agree to rounding.  ResNet-50 at 32 x 32 with 8 images normalises over as
# few as 8 values a channel, and ReLU and max-pool masks flip on float32
# rounding: step 1's loss agrees to 1e-5 and its gradient norms to a
# percent, and the gap grows over steps 2 and 3, so its limits are loose
# (test_rehearsal holds its first gradient tighter).
TINY_LIMITS = {
    "bert-tiny-fit": dict(loss=1e-3, grad_norm=5e-3, delta_norm=5e-3),
    "bert-tiny-dp4": dict(loss=1e-3, grad_norm=5e-3, delta_norm=5e-3),
    "resnet50-tiny-fit": dict(loss=0.1, grad_norm=0.03,
                              grad_norm_median=0.01, delta_norm_median=0.2),
}


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_tree(dst: str) -> str:
    """``dst`` becomes a checkout that holds ``BENCHMARK.json`` and
    ``benchmark/`` with the tiny cells beside the real ones."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg_dir = os.path.join(dst, "benchmark", "configs")

    with open(os.path.join(cfg_dir, "resnet50-imagenet.json")) as f:
        resnet = json.load(f)
    resnet.update(name="resnet50-tiny", module="resnet50-imagenet",
                  class_num=10, image=32)
    resnet["deployment"].update(batch_per_chip=8, compute_dtype="float32")
    resnet["deployment"]["optimizer"]["lr"] = 0.001
    _dump(resnet, os.path.join(cfg_dir, "resnet50-tiny.json"))

    with open(os.path.join(cfg_dir, "bert-base-uncased.json")) as f:
        bert = json.load(f)
    bert.update(name="bert-tiny", module="bert-base-uncased", vocab_size=100,
                hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
                intermediate_size=64, max_position_embeddings=16, seq_len=16)
    bert["deployment"].update(batch_per_chip=8, compute_dtype="float32")
    bert["deployment"]["optimizer"]["lr"] = 1e-3
    _dump(bert, os.path.join(cfg_dir, "bert-tiny.json"))

    tdir = os.path.join(dst, "benchmark", "traffic")
    with open(os.path.join(tdir, "fit-host-imagenet1k.json")) as f:
        traffic = json.load(f)
    # a million rows of which 96 are held: a short window stays inside
    # the first epoch, as the real cells' does
    traffic.update(rows=1000000, pool_rows=96, open_after_steps=2)
    _dump(traffic, os.path.join(tdir, "fit-host-tiny.json"))

    for name in ("resnet50-tiny", "bert-tiny"):
        bench["configs"].append(dict(
            name=name, source="a test's cut of the published one",
            file=f"benchmark/configs/{name}.json", reduced=[], why="test"))
    cells = [("resnet50-tiny-fit", "resnet50-tiny", 1),
             ("bert-tiny-fit", "bert-tiny", 1),
             ("bert-tiny-dp4", "bert-tiny", 4)]
    for cell, config, chips in cells:
        bench["workloads"].append(dict(
            name=cell, config=config, traffic="fit-host-tiny", chips=chips,
            why="test"))
        _dump({"limits": TINY_LIMITS[cell]},
              os.path.join(dst, "benchmark", "limits", cell + ".json"))
    _dump(bench, os.path.join(dst, "BENCHMARK.json"))
    return dst


def harness_of(root: str):
    """Import the repo's ``run.py`` and ``harness`` with the tree as the
    checkout: the files they find by name are the tree's."""
    bench_dir = os.path.join(REPO, "benchmark")
    for p in (REPO, bench_dir):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as bench_run
    from harness import spec

    bench_run.ROOT = root
    return bench_run, spec


def only_chips(monkeypatch, chips: int) -> None:
    """The test process has more virtual devices than a cell has chips, and
    the program's context takes every device JAX finds: let JAX find the
    first ``chips`` only, as a machine with that many would."""
    import jax

    found = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: found[:chips])


if __name__ == "__main__":        # needs as many devices as the cell has chips
    import time

    t0 = time.perf_counter()
    root = make_tree(sys.argv[1]) if not os.path.exists(
        os.path.join(sys.argv[1], "BENCHMARK.json")) else sys.argv[1]
    bench_run, spec = harness_of(root)
    from harness import result

    cell = spec.load_cell(root, sys.argv[2])
    out = bench_run.measure(cell, int(sys.argv[3]), float(sys.argv[4]),
                            bool(int(sys.argv[5])), t0)
    result.print_checks(out["checks"], out["notes"])
    print(json.dumps({k: out[k] for k in
                      ("correct", "metrics", "device", "breakdown")})[:3000])
