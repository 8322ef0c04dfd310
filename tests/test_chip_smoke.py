"""chip_smoke.py on the CPU tier: the phase functions the chip run drives,
at tiny widths on the 8-device virtual mesh with the kernels forced to
``interpret``; the off-TPU exit; and the compile-cache placement helper.

What only the chip can show (Mosaic accepting the kernels, the published
widths fitting, the times) is ``python chip_smoke.py`` on the chip machine.
"""

import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _tiny_mobilenet(class_num, input_shape):
    from analytics_zoo_tpu.models.image.imageclassification import mobilenet

    return mobilenet(class_num=class_num, input_shape=input_shape,
                     alpha=0.125)


TINY = {
    "train/ncf": dict(user_count=64, item_count=48, ratings_per_user=40,
                      batch=256, steps_per_execution=2, held_out=512),
    "train/resnet50": dict(net=_tiny_mobilenet, image=32, classes=10,
                           batch=8, steps=2),
    "serve": dict(net=_tiny_mobilenet, image=32, classes=10,
                  buckets=(1, 4), burst=8),
    "kernels": dict(
        flash=dict(B=1, H=1, L=256, D=128),
        scan=dict(B=1, L=32, H=4, P=64, G=2, N=16, chunk=16),
        bag_model=[dict(name="narrow", V=4100, D=20, B=16, N=1)],
        # the smallest shape the compiled kernel accepts: one 128-lane
        # row per id, one 8-bag block
        bag_kernel=dict(name="smallest", V=4096, D=128, B=8, N=2),
        dequant=dict(K=256, N=256, Ms=(8,)),
        interpret=True),
    "multichip": dict(dryrun=False, bag=dict(V=4096, D=128, B=16, N=2),
                      ring=dict(B=1, H=1, L=1024, D=128), interpret=True),
}


@pytest.fixture(autouse=True)
def _default_context_after():
    yield
    from analytics_zoo_tpu import init_zoo_context

    init_zoo_context()


@pytest.fixture
def restore_cache_dir():
    """main() and the helper point jax's persistent cache at the checkout;
    put the session's setting back before anything compiles (jax binds the
    directory at the first compile after it is set)."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_main_exits_nonzero_off_tpu_and_names_the_phase(
        capsys, restore_cache_dir):
    assert jax.devices()[0].platform == "cpu"
    rc = chip_smoke.main()
    out, err = capsys.readouterr()
    assert rc != 0
    assert "FAILED phase=device" in err
    assert "platform=cpu" in out and "native.available()" in out
    # no result line: nothing on stdout parses as the ok object
    assert '"ok"' not in out


def test_phase_train_ncf_tiny():
    res = chip_smoke.phase_train_ncf(TINY["train/ncf"])
    assert res["param_span"] == len(jax.devices()) == 8
    assert any('path="reference"' in s for s in res["selected"])


def test_phase_train_image_tiny():
    chip_smoke.phase_train_resnet50(TINY["train/resnet50"])


def test_phase_serve_tiny():
    chip_smoke.phase_serve(TINY["serve"])


def test_phase_kernels_interpret_tiny():
    chip_smoke.phase_kernels(TINY["kernels"])


def test_phase_multichip_tiny():
    res = chip_smoke.phase_multichip(TINY["multichip"])
    assert res == {"devices": 8}


@pytest.mark.slow
def test_phase_multichip_with_fit_regimes():
    chip_smoke.phase_multichip(dict(TINY["multichip"], dryrun=True))


@pytest.mark.usefixtures("restore_cache_dir")
class TestCompileCachePlacement:
    def test_env_set_means_no_directory_set_in_code(self, monkeypatch):
        from analytics_zoo_tpu.core.context import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jax.config.update("jax_compilation_cache_dir", "/sentinel")
        assert enable_compile_cache() == "/sentinel"
        assert jax.config.jax_compilation_cache_dir == "/sentinel"

    def test_env_unset_means_checkout_jax_cache(self, monkeypatch):
        from analytics_zoo_tpu.core.context import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
