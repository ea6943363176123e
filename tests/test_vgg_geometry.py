"""VGG-16-class geometry sweep: `auto_c_block` / `auto_pool_rows` off
AlexNet (paper-adjacent: the DLA's stream buffers are sized for AlexNet
planes; VGG's 224px maps are the case where whole-plane residency stops
fitting and the channel-block reduction has to earn its keep).

Two layers of validation:

* the *choices*: over the real VGG-16 conv table, the auto-sized blocks
  must respect the VMEM slab budget, keep every AlexNet-scale plane fully
  resident, and split channels on the big 224/112px planes (the re-fetch
  trade `conv2d_hbm_bytes` models);
* the *kernels*: VGG-proportioned geometries whose auto plan really does
  pick ``ncb > 1`` (several channel blocks) and partial pooled-row blocks
  must still be bit-faithful to the lax reference on both Pallas kernels.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.winograd import LANES, auto_c_block, auto_pool_rows, \
    vmem_bytes
from repro.kernels.conv import direct as dk
from repro.kernels.conv import winograd as wk
from repro.kernels.conv.ref import conv2d_ref
from repro.nn.pooling import apply_epilogue

# the VGG-16 conv layers: (input extent, C_in, C_out); all 3x3 stride 1
VGG16_LAYERS = [
    (224, 3, 64), (224, 64, 64),
    (112, 64, 128), (112, 128, 128),
    (56, 128, 256), (56, 256, 256), (56, 256, 256),
    (28, 256, 512), (28, 512, 512), (28, 512, 512),
    (14, 512, 512), (14, 512, 512), (14, 512, 512),
]
# layers followed by the 2x2 s2 max-pool: (conv-out extent, C_out)
VGG16_POOLED = [(224, 64), (112, 128), (56, 256), (28, 512), (14, 512)]

SLAB_BUDGET = 8 * 2 ** 20
EPILOGUE_BUDGET = 4 * 2 ** 20


@pytest.mark.parametrize("batch", [1, 8])
def test_auto_c_block_respects_budget_over_vgg_table(batch):
    """Every auto-sized channel block is lane-legal (all of C, or a
    multiple of 128 lanes) and keeps the whole resident (batch, Hp, Wp, Cb)
    input block — padded to the VMEM tile, double-buffered — within the
    slab budget (or full C when it fits; one lane tile can never be
    shrunk further)."""
    for h, c_in, _ in VGG16_LAYERS:
        hp = wp = h + 2                         # SAME halo for r=3
        cb = auto_c_block(hp, wp, c_in, batch=batch)
        assert 1 <= cb <= c_in, (h, c_in, cb)
        assert cb == c_in or cb % LANES == 0, (h, c_in, cb)
        if cb < c_in:
            assert cb == LANES or 2 * vmem_bytes(
                (batch, hp, wp, cb)) <= SLAB_BUDGET, (h, c_in, cb)


def test_auto_c_block_splits_vgg_but_not_alexnet():
    """At the filter-cache depth (batch=8) the big VGG planes must split
    channels while every AlexNet plane stays fully resident — the exact
    trade DESIGN.md documents.  A plane of at most 128 channels cannot
    split below one lane tile: VGG's 224px and 112px stages keep all of C
    (they need row-blocked residency instead, an open item)."""
    # VGG 56px plane: whole-plane residency can't fit 8 deep
    assert auto_c_block(58, 58, 256, batch=8) < 256
    assert auto_c_block(226, 226, 64, batch=8) == 64
    assert auto_c_block(114, 114, 128, batch=8) == 128
    # AlexNet planes (Hp x Wp x C at the five layers) all stay resident
    for hp, c in ((227, 3), (31, 48), (15, 256), (13, 192), (13, 192)):
        assert auto_c_block(hp, hp, c, batch=8) == c, (hp, c)


@pytest.mark.parametrize("batch", [1, 8])
def test_auto_pool_rows_respects_budget_over_vgg_table(batch):
    """The pooled-row block keeps the full-channel epilogue scratch within
    its budget (or owns the whole pooled extent when that fits)."""
    for out_h, k in VGG16_POOLED:
        ph = out_h // 2
        Pb = auto_pool_rows(ph, 2, 2, cols=out_h, kfull=k, batch=batch)
        assert 1 <= Pb <= ph
        rows = 2 * (Pb - 1) + 2
        if Pb < ph:
            assert Pb == 1 or batch * rows * out_h * k * 4 <= \
                EPILOGUE_BUDGET, (out_h, k, Pb)


def _vgg_case(H, C, K, B, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, H, H, C)) * 0.5, jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, C, K)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.standard_normal((K,)), jnp.float32)
    return x, w, b


def test_winograd_kernel_auto_c_block_splits_on_vgg_plane():
    """A VGG-proportioned plane (72px, C=256, batch 8) where the auto plan
    genuinely picks several channel blocks: the in-kernel channel-block
    reduction + DMA weight stream must be invisible in the output."""
    x, w, b = _vgg_case(72, 256, 8, 8, seed=0)
    p = wk.plan(x.shape, w.shape)
    assert p.ncb > 1, "geometry must force a multi-c-block plan"
    out = wk.conv2d_winograd(x, w, b, relu=True, interpret=True)
    ref = conv2d_ref(x, w, b, relu=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_winograd_fused_pool_auto_blocks_on_vgg_plane():
    """Same multi-c-block regime with the fused 2x2 s2 VGG pool epilogue
    (pool_row_block=None grows to the budgeted pooled-row block); C=192
    also pads up to the two 128-lane blocks."""
    x, w, b = _vgg_case(72, 192, 8, 8, seed=1)
    p = wk.plan(x.shape, w.shape, pool=(2, 2))
    assert p.ncb > 1, "geometry must force a multi-c-block plan"
    out = wk.conv2d_winograd(x, w, b, relu=True, pool=(2, 2),
                             interpret=True)
    ref = apply_epilogue(conv2d_ref(x, w, b, relu=True), None, (2, 2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_direct_kernel_auto_c_block_splits_on_vgg_plane():
    """The strided direct kernel under the same auto multi-c-block regime
    (3x3 s1 runs on it too when routed explicitly).  K is a whole lane
    block: the auto plan keeps every channel of a narrower K resident,
    since a multi-tile filter stream of K < 128 lanes does not compile
    for the TPU."""
    x, w, b = _vgg_case(72, 256, 128, 8, seed=2)
    p = dk.plan(x.shape, w.shape)
    assert p.ncb > 1, "geometry must force a multi-c-block plan"
    out = dk.conv2d_direct(x, w, b, relu=True, interpret=True)
    ref = conv2d_ref(x, w, b, relu=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("pool_row_block", [1, 3, None])
def test_pool_row_block_sweep_vgg_pool(pool_row_block):
    """pool_row_block sweep on the VGG 2x2 s2 pool: single-row blocks,
    a non-dividing partial block, and the auto (whole-extent) block must
    all agree with the reference on both kernels."""
    x, w, b = _vgg_case(28, 24, 12, 3, seed=3)
    ref = apply_epilogue(conv2d_ref(x, w, b, relu=True), None, (2, 2))
    out_w = wk.conv2d_winograd(x, w, b, relu=True, pool=(2, 2),
                               pool_row_block=pool_row_block,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    out_d = dk.conv2d_direct(x, w, b, relu=True, pool=(2, 2),
                             pool_row_block=pool_row_block, interpret=True)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
