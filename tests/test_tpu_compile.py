"""AlexNet's five conv layers, compiled for a TPU v5e at published width.

Interpret mode cannot see what only the chip's compiler refuses: blocks
that break the (8, 128) tiling, strided value slices, scoped VMEM overuse.
So each conv layer of the full 227 px config is lowered and compiled on
the Pallas route for one chip of a described ``v5e:2x2`` topology (no
chip attached), at batch 1 and at batch 8, and must contain its Pallas
kernel, named by its layer (``conv3_winograd``).  The topology is described inside a fixture, in this one file:
only one process at a time may load the TPU compiler's library.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import alexnet
from repro.nn.conv import dispatch_conv

CFG = dataclasses.replace(get_config("alexnet"), use_pallas=True)


def _layers():
    """(name, spec, input shape without batch, filter shape) per layer."""
    out, h, c = [], CFG.image_size, CFG.in_channels
    for i, (spec, k) in enumerate(zip(alexnet.layer_specs(CFG),
                                      CFG.conv_channels)):
        out.append((f"conv{i + 1}", spec.with_route("pallas"), (h, h, c),
                    (spec.kernel, spec.kernel, c // spec.groups, k)))
        h, c = spec.out_hw(h), k
    return out


LAYERS = _layers()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("layer", range(len(LAYERS)),
                         ids=[name for name, *_ in LAYERS])
def test_conv_layer_compiles_for_v5e(layer, batch, one_chip,
                                     no_compile_cache):
    name, spec, in_shape, w_shape = LAYERS[layer]

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    fwd = jax.jit(lambda x, w, b: dispatch_conv(spec, x, w, b,
                                                interpret=False, name=name))
    compiled = fwd.lower(sds((batch, *in_shape)), sds(w_shape),
                         sds((w_shape[-1],))).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
    # the kernel's instruction, and so its device op, is named by its layer
    assert re.search(rf'%{name}_(direct|winograd)(\.\d+)? = [^\n]*'
                     r'custom_call_target="tpu_custom_call"',
                     compiled.as_text()), name
