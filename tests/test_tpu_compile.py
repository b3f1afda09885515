"""Mosaic compiles of the arena kernels for a described TPU v5e.

No chip is needed: libtpu's compiler runs here against a v5e *described* by
topology, so everything Mosaic would refuse on the chip (unaligned slices,
unsupported relayouts, over-budget VMEM) is refused here too. The
topology is described only inside a fixture of this one file — never at
import time — and the persistent compilation cache is off around these
compiles (their entries could not be read back without a chip).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.core import exec as X
from repro.core import zoo
from repro.core.exec.pallas_backend import PallasExecutor
from repro.core.pipeline import compile as compile_graph
from repro.kernels import arena_ops, runtime

_V5E = "TPU v5 lite"
_FLAGSHIP = "mobilenet_v1_0.25_128_8bit"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _weight_shapes(spec):
    """(shape, dtype) of each weight operand a spec consumes."""
    dt = jnp.int8 if spec.dtype == "i8" else jnp.float32
    if spec.kind == "fused":
        return [w for st in spec.stages for w in _weight_shapes(st)]
    if spec.kind in ("conv2d", "depthwise_conv2d"):
        kh, kw = spec.meta[:2]
        last = (spec.meta[8] if spec.kind == "depthwise_conv2d"
                else spec.out_shape[-1])
        return [((kh, kw, spec.in_shape[0][-1], last), dt)]
    if spec.kind == "fully_connected":
        return [((spec.in_shape[0][-1], spec.out_shape[-1]), dt)]
    return []


def _lowered(name: str, route: str, graph=None):
    """(specs, arena shape, arena dtype) of a zoo model's arena program
    (``graph``, where given, in place of the zoo's own)."""
    cp = compile_graph(graph or zoo.TABLE3_MODELS[name][0](), verify="off")
    bp = cp.legalised()
    quant = None
    if X.needs_quant(cp.graph):
        quant = X.calibrate(cp.graph, 0, X.synth_weights(cp.graph, 0))
    be = PallasExecutor(mode="streaming" if route == "stream" else
                        "compiled")
    specs = (be.lower_stream(bp, quant) if route == "stream"
             else be.lower_blocks(bp, quant))
    dt = jnp.int8 if bp.dtype_bytes == 1 else jnp.float32
    return specs, (bp.total_rows, bp.arena_rowlen), dt


def _compile(fn, arena_shape, dt, specs, sharding):
    args = [jax.ShapeDtypeStruct(arena_shape, dt, sharding=sharding)]
    for spec in specs:
        args += [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                 for s, d in _weight_shapes(spec)]
    return fn.lower(*args).compile()


@pytest.mark.parametrize("route", ["blocks", "stream"])
def test_flagship_program_compiles_for_v5e(one_chip, route):
    """Every launch of the paper's flagship (int8, packed arena) compiles
    through Mosaic on the VMEM-resident and on the streaming route: one
    TPU kernel per launch in the compiled program."""
    specs, shape, dt = _lowered(_FLAGSHIP, route)
    assert len(specs) == 29
    fn = arena_ops.lower_program(specs, interpret=False,
                                 vmem_limit=runtime.vmem_limit(_V5E))
    compiled = _compile(fn, shape, dt, specs, one_chip)
    assert compiled.as_text().count("tpu_custom_call") >= len(specs)


@pytest.mark.parametrize("kind", ["conv2d", "depthwise_conv2d"])
def test_full_width_f32_kernel_compiles_for_v5e(one_chip, kind):
    """The widest conv and depthwise launches of mobilenet_v1_1.0_224 (f32,
    full width) compile on their own."""
    specs, shape, dt = _lowered("mobilenet_v1_1.0_224", "blocks")
    spec = max((s for s in specs if s.kind == kind),
               key=lambda s: arena_ops._elems(s.out_shape))
    fn = jax.jit(lambda arena, *w: arena_ops.apply_op(
        arena, spec, w, interpret=False,
        vmem_limit=runtime.vmem_limit(_V5E)))
    compiled = _compile(fn, shape, dt, [spec], one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_small_inception_resnet_v2_program_compiles_for_v5e(one_chip):
    """Every launch of Inception-ResNet-v2 at 75 px with one block of each
    kind, at the published widths (f32: convs of 1x1 to 5x5 and 1x7 / 7x1,
    max and average pools, residual adds, channel concats), compiles
    through Mosaic: one TPU kernel per launch."""
    specs, shape, dt = _lowered(
        "inception_resnet_v2", "blocks",
        zoo.inception_resnet_v2(75, 4, (1, 1, 1)))
    assert len(specs) == 58
    fn = arena_ops.lower_program(specs, interpret=False,
                                 vmem_limit=runtime.vmem_limit(_V5E))
    compiled = _compile(fn, shape, dt, specs, one_chip)
    assert compiled.as_text().count("tpu_custom_call") >= len(specs)


@pytest.fixture(scope="module")
def irv2_published():
    """(specs, arena shape, arena dtype, block plan) of the published
    Inception-ResNet-v2 program at 299 px, planned once for the module."""
    cp = compile_graph(zoo.inception_resnet_v2(299, 4), verify="off")
    bp = cp.legalised()
    specs = PallasExecutor(mode="compiled").lower_blocks(bp)
    return specs, (bp.total_rows, bp.arena_rowlen), jnp.float32, bp


def test_published_inception_resnet_v2_joins_stream_rows(irv2_published):
    """The published plan passes the row-granular no-clobber check, and
    every residual add and channel concat of it runs one row at a time."""
    specs, _, _, bp = irv2_published
    bp.validate()
    joins = [s for s in specs if s.kind in ("elementwise", "concat")]
    assert len(joins) == 83
    assert all(arena_ops._row_streamable(arena_ops._BlockMem(None, s), s)
               for s in joins)


@pytest.mark.parametrize("name", ["m35_0_add", "m5b_cat"])
def test_published_join_kernel_compiles_for_v5e(one_chip, irv2_published,
                                                name):
    """The published 35x35x320 residual add and the 4-way concat to
    35x35x320 compile on their own."""
    specs, shape, dt, _ = irv2_published
    spec = next(s for s in specs if s.name == name)
    assert spec.out_shape == (35, 35, 320)
    fn = jax.jit(lambda arena: arena_ops.apply_op(
        arena, spec, (), interpret=False,
        vmem_limit=runtime.vmem_limit(_V5E)))
    compiled = _compile(fn, shape, dt, [spec], one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["stem_c1", "m17_0_b2c"])
def test_published_conv_kernel_compiles_for_v5e(one_chip, irv2_published,
                                                name):
    """The published stem's first conv (3 input channels, 299 px) and a
    17x17 block-B 7x1 conv compile on their own, each staging only its
    operands' rows of the 7.4 MB arena."""
    specs, shape, dt, _ = irv2_published
    spec = next(s for s in specs if s.name == name)
    spans, out = arena_ops.block_stage_spans(spec, shape[0])
    assert sum(n for _, n in spans) < shape[0] and out[1] < shape[0]
    fn = jax.jit(lambda arena, *w: arena_ops.apply_op(
        arena, spec, w, interpret=False,
        vmem_limit=runtime.vmem_limit(_V5E)))
    compiled = _compile(fn, shape, dt, [spec], one_chip)
    assert "tpu_custom_call" in compiled.as_text()
