"""ResNet-50 v1.5 — bottleneck residual network, served through the same
conv datapath as AlexNet and VGG-16.

He et al. 2015 ("Deep Residual Learning for Image Recognition",
arXiv:1512.03385, Table 1, 50-layer column), in the v1.5 layout of
torchvision's ``resnet50`` and of MLPerf Inference: a 7x7 stride-2 stem
(pad 3) with batch norm and ReLU, a 3x3 stride-2 max-pool (pad 1), four
stages of 3/4/6/3 bottlenecks (1x1 reduce, 3x3, 1x1 expand by 4, each
conv with batch norm) whose first block carries a 1x1 projection shortcut
and, in stages 3-5, the stride — on the 3x3 conv (v1.5), not on the first
1x1 — then a global average pool and FC 2048 -> 1000.

Every conv is one :class:`~repro.nn.conv.ConvSpec` through
``dispatch_conv``: on the ``pallas`` route the thirteen 3x3 stride-1 convs
run the Winograd kernel and the other forty (stem, 1x1s, 3x3 stride 2)
the direct kernel.  Strided convs pad symmetrically, ``(k - 1) // 2`` on
every side before striding, as the published model does (lax ``SAME``
would pad (2, 3) on the stem); stride-1 convs pad ``SAME``, which is the
same there.  The last 1x1 conv of each bottleneck takes the shortcut as
its residual operand: conv -> +b -> +shortcut -> ReLU in the direct
kernel's epilogue (kernel ``<layer>_direct_add``).

Inference batch norm is folded into each conv's filters and bias before
packing (:func:`fold_bn`); the model's parameters keep it unfolded, as
trained.  The max-pool and the average pool run in XLA.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.bfp_matmul.ops import bfp_linear, fc_block, quantize_weights
from ..kernels.conv.epilogue import F32_DOT
from ..nn.conv import ConvSpec, conv_out_hw, dispatch_conv, \
    pack_conv_weights, resolve_kernel
from ..nn.module import split


@dataclass(frozen=True)
class ResNetConfig:
    """Bottleneck ResNet.  The defaults are ResNet-50 v1.5 at 224 px."""
    name: str = "resnet50"
    family: str = "cnn"
    arch: str = "resnet"
    image_size: int = 224
    in_channels: int = 3
    stem_channels: int = 64
    stage_blocks: Tuple[int, ...] = (3, 4, 6, 3)
    stage_widths: Tuple[int, ...] = (64, 128, 256, 512)
    expansion: int = 4              # a bottleneck's output / its width
    num_classes: int = 1000
    bn_eps: float = 1e-5
    use_winograd: bool = True      # F(4,3) on the 3x3 stride-1 convs
    use_pallas: bool = False       # route convs through the Pallas kernels
    fc_bfp: bool = False           # shared-exponent BFP FC weight stream
    conv_bfp: bool = False         # BFP on the staged conv filter slabs
    weight_prefetch: bool = True   # double-buffered in-kernel DMA stream
    sdc_abft: bool = False         # ABFT checksum row on the filter stream;
                                   # forward returns (logits, sdc_verdict)
    dtype: str = "float32"

    def reduced(self) -> "ResNetConfig":
        """CPU-test size: 32 px, widths / 16; the stem, a stride-1 and a
        stride-2 projection block and an identity block (stage 3)."""
        return replace(self, image_size=32, stem_channels=4,
                       stage_blocks=(1, 2, 1, 1),
                       stage_widths=(4, 8, 16, 32), num_classes=10)


@dataclass(frozen=True)
class ConvLayer:
    """One conv of the network: what it reads, how, and what it adds."""
    name: str               # "s3b1_c2"; its kernel is "<name>_<kernel>"
    block: str              # its named scope: "stem", "s3b1", ..
    input: str              # the tensor it reads: "image", "pool", a layer
    spec: ConvSpec
    in_hw: int
    c_in: int
    c_out: int
    add: Optional[str] = None   # the shortcut added before its ReLU


def _pads(kernel: int, stride: int):
    """Symmetric (k-1)//2 on every side for strided convs; stride-1 convs
    pad SAME, which is the same there."""
    return "SAME" if stride == 1 else ((kernel - 1) // 2,) * 2


# the max-pool after the stem: (window, stride, (lo, hi) pads)
POOL = (3, 2, (1, 1))


def pool_hw(h: int) -> int:
    """The max-pool's output extent."""
    window, stride, (lo, hi) = POOL
    return (h + lo + hi - window) // stride + 1


def layers(cfg: ResNetConfig) -> List[ConvLayer]:
    """Every conv in the order the forward runs them: the stem, then each
    bottleneck's c1, c2, its projection (first block of a stage) and c3."""
    def conv(name, block, src, k, stride, c_out, relu, add=None):
        spec = ConvSpec(kernel=k, stride=stride, padding=_pads(k, stride),
                        relu=relu)
        out.append(ConvLayer(name, block, src, spec, hw[src], ch[src],
                             c_out, add))
        hw[name] = conv_out_hw(hw[src], k, stride, spec.padding)
        ch[name] = c_out
        return name

    out: List[ConvLayer] = []
    hw, ch = {"image": cfg.image_size}, {"image": cfg.in_channels}
    conv("stem", "stem", "image", 7, 2, cfg.stem_channels, True)
    hw["pool"], ch["pool"] = pool_hw(hw["stem"]), ch["stem"]
    x = "pool"
    for si, (n, width) in enumerate(zip(cfg.stage_blocks, cfg.stage_widths)):
        for bi in range(n):
            blk = f"s{si + 2}b{bi + 1}"
            stride = 2 if bi == 0 and si > 0 else 1
            c1 = conv(f"{blk}_c1", blk, x, 1, 1, width, True)
            c2 = conv(f"{blk}_c2", blk, c1, 3, stride, width, True)
            short = (conv(f"{blk}_proj", blk, x, 1, stride,
                          width * cfg.expansion, False) if bi == 0 else x)
            x = conv(f"{blk}_c3", blk, c2, 1, 1, width * cfg.expansion, True,
                     add=short)
    return out


def _route(cfg: ResNetConfig) -> str:
    if not cfg.use_winograd:
        return "direct"
    return "pallas" if cfg.use_pallas else "winograd"


def layer_routes(cfg: ResNetConfig) -> List[Tuple[str, str]]:
    """(layer name, fully resolved datapath) per conv, as dispatch_conv
    resolves it."""
    route = _route(cfg)
    return [(l.name, resolve_kernel(l.spec.with_route(route), in_hw=l.in_hw))
            for l in layers(cfg)]


def init(key, cfg: ResNetConfig):
    """He-normal convs, identity batch norm (gamma 1, beta 0, mean 0, var
    1) and a 1/sqrt(fan_in) FC: ``{layer: {"w", "bn": {"gamma", "beta",
    "mean", "var"}}, "fc": {"w", "b"}}``."""
    dtype = jnp.dtype(cfg.dtype)
    convs = layers(cfg)
    keys = split(key, len(convs) + 1)
    p = {}
    for k, l in zip(keys, convs):
        shape = (l.spec.kernel, l.spec.kernel, l.c_in, l.c_out)
        fan_in = int(np.prod(shape[:-1]))
        ones, zeros = jnp.ones((l.c_out,), dtype), jnp.zeros((l.c_out,), dtype)
        p[l.name] = {
            "w": (jax.random.normal(k, shape, jnp.float32)
                  * np.sqrt(2.0 / fan_in)).astype(dtype),
            "bn": {"gamma": ones, "beta": zeros, "mean": zeros, "var": ones}}
    d = cfg.stage_widths[-1] * cfg.expansion
    p["fc"] = {"w": (jax.random.normal(keys[-1], (d, cfg.num_classes),
                                       jnp.float32) * d ** -0.5).astype(dtype),
               "b": jnp.zeros((cfg.num_classes,), dtype)}
    return p


def fold_bn(w, bn, eps: float):
    """Inference batch norm after a conv without bias, folded into it:
    filters w * gamma / sqrt(var + eps) (per output channel) and bias
    beta - mean * gamma / sqrt(var + eps), in float32, cast back."""
    f32 = jnp.float32
    scale = bn["gamma"].astype(f32) * jax.lax.rsqrt(bn["var"].astype(f32)
                                                    + eps)
    return ((w.astype(f32) * scale).astype(w.dtype),
            (bn["beta"].astype(f32)
             - bn["mean"].astype(f32) * scale).astype(w.dtype))


def max_pool(x):
    """The stem's max-pool (:data:`POOL`); its pads never win (-inf)."""
    window, stride, pads = POOL
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, window, window, 1),
                                 (1, stride, stride, 1),
                                 ((0, 0), pads, pads, (0, 0)))


def pack_serving_slabs(params, cfg: ResNetConfig, batch: int, *,
                       plans=None, fingerprint: bool = False) -> dict:
    """Pack-once serving slabs for one batch shape: each conv's filters
    with its batch norm folded in, packed for its datapath
    (:func:`~repro.nn.conv.pack_conv_weights`), its folded bias under
    ``"bias"``, and the FC's quantized stream under ``cfg.fc_bfp``."""
    plans = plans or {}
    route = _route(cfg)
    packed, bias = {}, {}
    for l in layers(cfg):
        p = params[l.name]
        w, bias[l.name] = fold_bn(p["w"], p["bn"], cfg.bn_eps)
        packed[l.name] = pack_conv_weights(
            l.spec.with_route(route), (batch, l.in_hw, l.in_hw, l.c_in), w,
            bfp_pack=cfg.conv_bfp, abft=cfg.sdc_abft,
            fingerprint=fingerprint, plan=plans.get(l.name))
    packed["bias"] = bias
    if cfg.fc_bfp:
        w = params["fc"]["w"]
        packed["fc"] = quantize_weights(w, block=fc_block(w.shape[0]))
    return packed


def apply(params, cfg: ResNetConfig, images, *, plans=None, packed=None):
    """images (B, H, W, C) -> logits (B, classes); with ``cfg.sdc_abft``
    ``(logits, sdc)``, the summed ABFT verdict of every conv.

    ``packed`` is a :func:`pack_serving_slabs` dict hoisted across the jit
    boundary: each conv streams its staged slab and takes its folded bias
    from it (the filters folded here are then unused).  Without it each
    conv folds and packs in-trace.  ``plans`` maps layer names to tuned
    :class:`~repro.nn.conv.ConvPlan`s."""
    plans = plans or {}
    route = _route(cfg)
    abft = cfg.sdc_abft
    sdc = jnp.zeros((), jnp.int32)
    acts = {"image": images.astype(jnp.dtype(cfg.dtype))}
    for l in layers(cfg):
        p = params[l.name]
        w, b = fold_bn(p["w"], p["bn"], cfg.bn_eps)
        plan = plans.get(l.name)
        kw = ({"plan": plan} if plan is not None
              else {"weight_prefetch": cfg.weight_prefetch})
        if packed is not None:
            kw["w_packed"] = packed.get(l.name)
            b = packed["bias"][l.name]
        with jax.named_scope(l.block):
            y = dispatch_conv(l.spec.with_route(route), acts[l.input], w, b,
                              abft=abft, name=l.name,
                              residual=acts[l.add] if l.add else None, **kw)
            if abft:
                y, v = y
                sdc = sdc + v
            if l.name == "stem":
                acts["pool"] = max_pool(y)
        acts[l.name] = y
    with jax.named_scope("head"):
        feats = jnp.mean(acts[l.name].astype(jnp.float32), axis=(1, 2))
        fc = params["fc"]
        if cfg.fc_bfp:
            q = packed.get("fc") if packed is not None else None
            logits = bfp_linear(feats, fc["w"], quantized=q)
        else:
            logits = jnp.dot(feats, fc["w"].astype(jnp.float32),
                             precision=F32_DOT)
        logits = (logits + fc["b"].astype(jnp.float32)).astype(
            jnp.dtype(cfg.dtype))
    return (logits, sdc) if abft else logits
