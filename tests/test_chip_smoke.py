"""chip_smoke.py's phases on the CPU: the reduced AlexNet config through the
same functions the chip run composes, Pallas kernels in interpret mode.

What only the chip can show (platform ``tpu``, ``tpu_custom_call`` in each
bucket's compiled forward) is checked here in its refusal: a CPU run must
exit non-zero without printing the result line.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402


@pytest.fixture(scope="module")
def served(monkeypatch_module):
    """Reduced AlexNet on the Pallas route, buckets 1 and 2, served in
    waves that fill full and partial buckets."""
    monkeypatch_module.setattr(chip_smoke, "MAX_BATCH", 2)
    cfg = chip_smoke.pallas_config(get_config("alexnet").reduced())
    eng = chip_smoke.build_engine(cfg)
    images = chip_smoke.seeded_images(cfg, 6)
    logits, seconds = chip_smoke.serve(eng, images, waves=(2, 1, 3))
    return cfg, eng, images, logits


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


def test_phases_serve_reduced_config_in_interpret_mode(served):
    cfg, eng, images, logits = served
    assert set(eng.compile_seconds) == {1, 2}
    assert logits.shape == (6, cfg.num_classes)
    chip_smoke.check_serving(eng)
    # waves of 2, 1 and 3 images: groups of 2 | 1 | 2 + 1
    assert eng.bucket_counts == {1: 2, 2: 2}
    ref = chip_smoke.reference_logits(cfg, eng.params, images, batch=2)
    rel = chip_smoke.compare(logits, ref, chip_smoke.LOGIT_TOL, "cpu")
    assert rel < 1e-5           # float32 throughout on the CPU


def test_features_match_reference_in_interpret_mode(served):
    """The conv stack alone against the highest-precision lax route.  On
    the CPU every precision is float32, so the control cannot exceed the
    bound and check_features must refuse it."""
    cfg, eng, images, _ = served
    err, control = chip_smoke.feature_errors(cfg, eng.params, images,
                                             batch=2)
    assert err < 1e-5 and control < 1e-5
    with pytest.raises(chip_smoke.SmokeFailure, match="bfloat16 control"):
        chip_smoke.check_features(err, control)


def test_check_features_bounds():
    chip_smoke.check_features(1e-5, 5e-3, tol=1e-3)
    with pytest.raises(chip_smoke.SmokeFailure, match="conv features"):
        chip_smoke.check_features(2e-3, 5e-3, tol=1e-3)


def test_interpret_mode_has_no_tpu_kernels(served):
    _, eng, _, _ = served
    counts = chip_smoke.kernel_counts(eng)
    assert counts == {1: 0, 2: 0}
    with pytest.raises(chip_smoke.SmokeFailure, match="fewer than 5"):
        chip_smoke.check_kernels(counts, 5)


def test_compare_fails_on_error_and_top1_flip():
    ref = np.array([[3.0, 1.0, 0.0], [0.0, 2.0, 5.0]], np.float32)
    chip_smoke.compare(ref + 1e-3, ref, 1e-2, "close")
    with pytest.raises(chip_smoke.SmokeFailure, match="relative logit"):
        chip_smoke.compare(ref + 0.5, ref, 1e-2, "far")
    flipped = ref.copy()
    flipped[0] = [1.0, 3.0, 0.0]
    with pytest.raises(chip_smoke.SmokeFailure, match="top-1"):
        chip_smoke.compare(flipped, ref, 1.0, "flipped")


def test_routes_off_pallas_fail(monkeypatch):
    import dataclasses

    from repro.launch import serve
    monkeypatch.setattr(serve, "apply_cnn_route", lambda c, r: (
        dataclasses.replace(c, use_winograd=False, use_pallas=False)))
    with pytest.raises(chip_smoke.SmokeFailure, match="off the Pallas"):
        chip_smoke.pallas_config(get_config("alexnet").reduced())


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_placement(env_dir, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` where set (JAX's own reading of it,
    nothing else set); otherwise the fixed ``.jax_cache/`` of the
    checkout."""
    code = ("import jax\n"
            "from repro.serving.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(ROOT, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [want, want]


def _run_alone(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    return r


def test_refuses_ok_without_tpu():
    """On the CPU the script exits non-zero and prints no result line."""
    assert "no TPU" in _run_alone(ROOT).stderr


def test_refuses_ok_without_the_program(tmp_path):
    """Copied out of the checkout, with none of the program beside it, the
    script fails the same way."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    assert "ModuleNotFoundError" in _run_alone(tmp_path).stderr
