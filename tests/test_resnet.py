"""ResNet-50 v1.5 through ``CnnEngine`` against its plain reference.

At the CPU-test size (``ResNetConfig.reduced()``: 32 px, widths / 16, the
stem, a stride-1 and a stride-2 projection block and an identity block),
on the Pallas route in interpret mode, with seeded He-normal weights and
seeded batch-norm statistics: served logits against
``models/resnet_ref.py``; batch norm folded into a conv against conv then
batch norm; the direct kernel's residual epilogue against conv + b +
shortcut, ReLU; symmetric padding of strided convs against lax's explicit
padding; and the same comparison failing once the shortcut is left out.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.conv.direct import conv2d_direct
from repro.kernels.conv.ref import conv2d_ref
from repro.models import model_for, resnet, resnet_ref
from repro.nn.conv import ConvSpec, conv_pads, dispatch_conv
from repro.serving import CnnEngine, CnnServeConfig, ImageRequest

CFG = dataclasses.replace(get_config("resnet50").reduced(), use_pallas=True)

# Served logits against the reference, as the widest |served - reference|
# of a row over its largest |reference logit|.  Both are float32; they
# differ in summation order (the Winograd transform, the folded batch
# norm, the kernels' channel blocks), and twenty convs amplify that
# rounding: the lax route with folded batch norm reads about 1e-5 at this
# size.  A row computed without one residual add is off by order one.
LOGIT_TOL = 1e-4


def _bn(key, c):
    kg, kb, km, kv = jax.random.split(key, 4)
    return {"gamma": jax.random.uniform(kg, (c,), minval=0.5, maxval=1.5),
            "beta": 0.1 * jax.random.normal(kb, (c,)),
            "mean": 0.1 * jax.random.normal(km, (c,)),
            "var": jax.random.uniform(kv, (c,), minval=0.5, maxval=1.5)}


def _params(cfg, seed=0):
    """``resnet.init``'s weights with seeded batch-norm statistics."""
    p = resnet.init(jax.random.PRNGKey(seed), cfg)
    for i, l in enumerate(resnet.layers(cfg)):
        p[l.name]["bn"] = _bn(jax.random.PRNGKey(1000 + i), l.c_out)
    return p


def _images(cfg, n, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (n, cfg.image_size, cfg.image_size, cfg.in_channels)
    ).astype(np.float32)


def _row_err(got, ref):
    got, ref = np.asarray(got), np.asarray(ref)
    return max(float(np.abs(g - r).max() / np.abs(r).max())
               for g, r in zip(got, ref))


@pytest.fixture(scope="module")
def tiny():
    params = _params(CFG)
    imgs = _images(CFG, 2)
    ref = jax.jit(lambda p, x: resnet_ref.forward(p, CFG, x))(
        params, jnp.asarray(imgs))
    return params, imgs, np.asarray(ref)


def test_layer_table_routes_every_conv_to_a_pallas_kernel():
    full = dataclasses.replace(get_config("resnet50"), use_pallas=True)
    routes = dict(resnet.layer_routes(full))
    assert len(routes) == 53
    assert sum(r == "pallas-winograd" for r in routes.values()) == 13
    assert sum(r == "pallas-direct" for r in routes.values()) == 40
    adds = [l for l in resnet.layers(full) if l.add]
    assert len(adds) == 16 and all(l.name.endswith("_c3") for l in adds)
    assert model_for(full) is resnet


def test_engine_serves_the_reference_logits(tiny):
    """(a) ``CnnEngine`` on the Pallas route against the plain reference."""
    params, imgs, ref = tiny
    eng = CnnEngine(CFG, CnnServeConfig(max_batch=2), params=params)
    reqs = [ImageRequest(image=im) for im in imgs]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    assert eng.bucket_counts == {2: 1}
    got = np.stack([r.logits for r in reqs])
    assert _row_err(got, ref) <= LOGIT_TOL


def test_folded_batch_norm_is_conv_then_batch_norm():
    """(b) filters and bias with batch norm folded in == conv, then BN."""
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (2, 9, 9, 6))
    w = jax.random.normal(kw, (3, 3, 6, 5)) * 0.3
    bn = _bn(kb, 5)
    wf, bf = resnet.fold_bn(w, bn, 1e-5)
    got = conv2d_ref(x, wf, bf, stride=2, padding=(1, 1))
    y = conv2d_ref(x, w, stride=2, padding=(1, 1))
    want = ((y - bn["mean"]) / jnp.sqrt(bn["var"] + 1e-5) * bn["gamma"]
            + bn["beta"])
    # float32: one product per output against a scale after the sum
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_direct_kernel_adds_the_residual_before_relu():
    """(c) the residual epilogue, 1x1 stride 1, K = 256 in two K blocks and
    C = 256 in two channel blocks: conv -> +b -> +shortcut -> ReLU."""
    kx, kw, kb, kr = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(kx, (2, 8, 8, 256))
    w = jax.random.normal(kw, (1, 1, 256, 256)) * 0.06
    b = jax.random.normal(kb, (256,))
    res = jax.random.normal(kr, (2, 8, 8, 256))
    got = conv2d_direct(x, w, b, None, res, relu=True, k_block=128,
                        c_block=128, interpret=True, name="c3_direct_add")
    want = jnp.maximum(conv2d_ref(x, w, b) + res, 0)
    # float32 sums in another order (two channel blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.mean(got == 0)) > 0.2      # the ReLU cut something
    spec = ConvSpec(kernel=1, relu=True, route="pallas")
    jaxpr = str(jax.make_jaxpr(lambda x, w, b, r: dispatch_conv(
        spec, x, w, b, name="s2b1_c3", residual=r, interpret=True))(
            x, w, b, res))
    assert "s2b1_c3_direct_add" in jaxpr


@pytest.mark.parametrize("kernel,stride,pad,same", [
    (7, 2, 3, (2, 3)),          # the stem on an even extent
    (3, 2, 1, (0, 1)),          # a v1.5 down-sampling 3x3
])
def test_strided_convs_pad_symmetrically(kernel, stride, pad, same):
    """(d) explicit (k-1)//2 pads on every side, as lax's explicit
    padding, and not lax SAME (which pads ``same``)."""
    kx, kw = jax.random.split(jax.random.PRNGKey(kernel))
    x = jax.random.normal(kx, (2, 16, 16, 4))
    w = jax.random.normal(kw, (kernel, kernel, 4, 8)) * 0.2
    spec = ConvSpec(kernel=kernel, stride=stride, padding=(pad, pad),
                    route="pallas")
    got = np.asarray(dispatch_conv(spec, x, w, interpret=True))

    def lax(padding):
        return np.asarray(jax.lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST))

    want = lax(((pad, pad), (pad, pad)))
    assert got.shape == want.shape == (2, 8, 8, 8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert conv_pads(16, kernel, stride, "SAME") == same
    assert lax("SAME").shape == want.shape
    assert np.abs(lax("SAME") - want).max() > 0.1
    assert resnet.conv_out_hw(16, kernel, stride, (pad, pad)) == 8


def test_leaving_out_the_shortcut_fails_the_comparison(tiny, monkeypatch):
    """(e) the program without its residual adds: (a)'s comparison fails."""
    params, imgs, ref = tiny
    dispatch = resnet.dispatch_conv

    def no_shortcut(*args, residual=None, **kw):
        return dispatch(*args, **kw)

    monkeypatch.setattr(resnet, "dispatch_conv", no_shortcut)
    mod = model_for(CFG)
    packed = mod.pack_serving_slabs(params, CFG, 2)
    got = jax.jit(lambda p, s, x: mod.apply(p, CFG, x, packed=s))(
        params, packed, jnp.asarray(imgs))
    assert _row_err(got, ref) > LOGIT_TOL


def test_direct_route_with_every_switch_serves_the_reference(tiny):
    """The engine's degraded route (lax convs, the shortcut added after
    them) and the model's switches: ABFT verdicts, BFP filter slabs and
    FC stream."""
    params, imgs, ref = tiny
    direct = dataclasses.replace(CFG, use_pallas=False, use_winograd=False)
    fwd = jax.jit(lambda p, s, x, cfg: resnet.apply(p, cfg, x, packed=s),
                  static_argnums=3)
    x = jnp.asarray(imgs)
    got = fwd(params, resnet.pack_serving_slabs(params, direct, 2), x,
              direct)
    assert _row_err(got, ref) <= LOGIT_TOL
    armed = dataclasses.replace(direct, sdc_abft=True, conv_bfp=True,
                                fc_bfp=True)
    got, sdc = fwd(params, resnet.pack_serving_slabs(params, armed, 2), x,
                   armed)
    assert int(sdc) == 0
    # int8 block floating point on every weight: about 2**-7 of each
    # weight block's largest magnitude, through twenty convs and the FC
    assert LOGIT_TOL < _row_err(got, ref) <= 0.1

