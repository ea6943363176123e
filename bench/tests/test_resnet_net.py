"""CPU tests of the net ``resnet`` (ResNet-50 v1.5): its work count at
full size, the refusal of a file that is another network than the
program, its seeded batch norm, a whole run at the CPU-test size
(``data/resnet50_tiny.json``, Pallas kernels in interpret mode) held to
the full-size limit, and the reader of ``conv.residual_roofline``.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402
import trace as tr  # noqa: E402
import work  # noqa: E402

SEED = 2**31 + 17


def _tiny_cfg():
    with open(os.path.join(BENCH, "tests", "data", "resnet50_tiny.json")) as f:
        return json.load(f)


def test_resnet50_layer_counts():
    """He et al. 2015, Table 1, 50-layer, v1.5: 53 convs and the FC, 25.53
    M parameters (each conv's bias is its folded batch norm), 4.09 G
    multiply-adds an image direct and 3.02 G least (F(4,3) on the thirteen
    3x3 stride-1 convs)."""
    cfg = run.config_file("resnet50")
    layers = run.net(cfg["net"]).work.layer_work(cfg)
    assert sum(w.op == "conv" for w in layers) == 53 and len(layers) == 54
    assert sum(w.weight_bytes for w in layers) // 4 == 25_530_472
    assert sum(w.direct_macs for w in layers) == 4_089_184_256
    assert sum(w.least_macs for w in layers) == 3_023_962_112
    by = {w.name: w for w in layers}
    assert by["stem"].direct_macs == 112 * 112 * 147 * 64     # 7x7 s2, 3->64
    # s3b1_c3 reads its 28x28x128 input and adds its 28x28x512 shortcut
    assert by["s3b1_c3"].in_bytes == (28 * 28 * 128 + 28 * 28 * 512) * 4
    assert by["s3b1_c2"].least_macs == by["s3b1_c2"].direct_macs  # stride 2
    assert by["fc"].direct_macs == 2048 * 1000


def test_a_file_that_differs_from_the_program_is_refused():
    """lax SAME's (2, 3) stem padding is another network than the
    published (3, 3): the program refuses to run it."""
    import serve  # noqa: F401  (puts the program on the path)

    cfg = run.config_file("resnet50")
    program = run.net("resnet").program
    cfg["layers"][0]["padding"] = [2, 3]
    with pytest.raises(program.ConfigMismatch):
        program.program_config(cfg)


def test_branches_stay_a_real_part_of_each_block():
    """The seeded batch-norm statistics keep every bottleneck's residual
    branch (batch norm of its last conv) at 10% or more of its shortcut's
    RMS, and the activations of order one."""
    import jax.numpy as jnp

    import reference

    cfg = _tiny_cfg()
    ref = run.net("resnet").reference
    params = ref.make_params(cfg, SEED)
    acts = {}
    ref.forward(cfg, params, jnp.asarray(reference.make_images(cfg, SEED, 4)),
                acts=acts)
    eps = cfg["bn"]["eps"]
    adds = [l for l in cfg["layers"] if l["op"] == "conv" and l["add"]]
    assert len(adds) == 5
    for layer in adds:
        p = params[layer["name"]]
        branch = ref._batch_norm(ref._conv(acts[layer["input"]], p["w"],
                                           layer, "highest"), p["bn"], eps)
        rms = float(jnp.sqrt(jnp.mean(branch ** 2)))
        short = float(jnp.sqrt(jnp.mean(acts[layer["add"]] ** 2)))
        assert rms >= 0.1 * short, layer["name"]
        assert 0.1 < short < 10, layer["name"]


def test_sound_run_passes_and_control_fails():
    """The tiny configuration through the whole run, held to the full-size
    configuration's limit: the program passes ``logit_err``; the
    reference computed in three bfloat16 passes, put in its place, does
    not."""
    cfg = _tiny_cfg()
    cfg["check"]["logit_err"] = run.config_file("resnet50")["check"][
        "logit_err"]
    closed = run.cell("resnet50.closed")
    traffic = {"kind": "closed", "clients": 2 * cfg["max_batch"], "pool": 8,
               "buckets": [cfg["max_batch"]]}
    cell = run.Cell(closed.name, 1, cfg, run.net(cfg["net"]), traffic,
                    closed.end_to_end, closed.per_layer)
    res = run.run_cell(cell, SEED, 2.0, False, require_chip=False,
                       controls=("bf16_3x",))
    limit = res["checks"]["logit_err"]["limit"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["controls"]["bf16_3x"] > limit, res


def _measured(ops):
    """Ten batches of 8 over two residual-fused layers, each 8 ms of
    compute at the peaks below (memory 1.7 ms), one plain conv and an FC;
    ``ops`` the trace's device ops."""
    w = work.LayerWork
    layers = [w("b1_c1", "conv", 1, 1, 1, 1, 1),
              w("b1_c3", "conv", 5 * 10**8, 5 * 10**8, 10**5, 10**5, 10**5),
              w("b2_c3", "conv", 5 * 10**8, 5 * 10**8, 10**5, 10**5, 10**5),
              w("fc", "fc", 1, 1, 1, 1, 1)]
    red = tr.Reduction(window_s=1.0, busy_s=0.5, kernel_s=0.4, xla_s=0.1,
                       ops=ops)
    return run.Measured(1, None, {8: 10}, 80, red, layers, 0,
                        {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})


def test_residual_roofline_reads_the_fused_kernels_only():
    reader = run.metric_reader("conv.residual_roofline")
    m = _measured({"pallas:b1_c3_direct_add.1": 0.16,
                   "pallas:b2_c3_direct_add": 0.16,
                   "pallas:b1_c1_direct.1": 5.0, "xla:fusion.3": 1.0})
    # 2 layers x 10 batches x 8 ms / 0.32 s of their kernels
    assert reader.read(m) == pytest.approx(50.0)
    assert reader.read(_measured({"pallas:b1_c1_direct.1": 5.0,
                                  "pallas:b1_c3_winograd.1": 1.0})) is None
