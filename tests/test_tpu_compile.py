"""Compile rehearsal: the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles one kernel for a v5e chip that
is described, not attached, and checks that the compiler put the Pallas
kernel in (``tpu_custom_call``) and that the program fits one chip's 16 GB.
The kernels are compiled at the size ``chip_smoke.py`` drives (the paper's
SPAMBASE geometry, d=57, C=10, K=4, at N=10^6 nodes), and the d-tiled
receive kernel at the paper's Reuters geometry (d=9947) on the 6144 nodes of
the benchmark's Reuters configuration. This catches tiling and VMEM
refusals, which interpret mode cannot see, at no chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, D, C, K = 1_000_000, 57, 10, 4        # chip_smoke's protocol phase
N_REUTERS, D_REUTERS = 6144, 9947        # configs/reuters-f32-6k.json
M = 4096                                 # chip_smoke's serving batch
HBM_BYTES = 16 * 10 ** 9                 # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no topology"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes, one_chip, **kw_shapes):
    spec = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    compiled = fn.lower(*[spec(*a) for a in shapes],
                        **{k: spec(*a) for k, a in kw_shapes.items()}
                        ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= HBM_BYTES, used


def _payload(wire):
    from repro.core.wire_codec import get_codec
    codec = get_codec(wire)
    return (K, N, codec.payload_cols(D)), codec.payload_dtype


@pytest.mark.parametrize("wire", ["f32", "int8", "int4"])
def test_fused_receive_apply_compiles(wire, one_chip):
    from repro.core.wire_codec import get_codec
    from repro.kernels.gossip_cycle import fused_receive_apply

    codec = get_codec(wire)
    meta = {}
    if codec.quantized:
        meta["msg_scale"] = ((K, N), jnp.float16)
    if codec.has_zp:
        meta["msg_zp"] = ((K, N), jnp.float16)

    def receive(*args, **kw):
        return fused_receive_apply(*args, wire=wire, variant="mu", lam=1e-3,
                                   **kw)

    f32, i32 = jnp.float32, jnp.int32
    _compile(jax.jit(receive),
             ((N, D), f32), ((N,), i32), ((N, D), f32), ((N,), i32),
             ((N, C, D), f32), ((N, C), i32), ((N,), i32), ((N,), i32),
             _payload(wire), ((K, N), i32), ((K, N), i32), ((N, D), f32),
             ((N,), f32), one_chip=one_chip, **meta)


def test_fused_receive_apply_dtiled_compiles(one_chip):
    from repro.kernels.gossip_cycle import (fused_receive_apply_dtiled,
                                            receive_d_tile)

    n, d = N_REUTERS, D_REUTERS
    tile = receive_d_tile(n, d, C, K)
    assert tile < d                           # the engine's choice here

    def receive(*args):
        return fused_receive_apply_dtiled(*args, variant="mu", lam=1e-4,
                                          d_tile=tile)

    f32, i32 = jnp.float32, jnp.int32
    _compile(jax.jit(receive),
             ((n, d), f32), ((n,), i32), ((n, d), f32), ((n,), i32),
             ((n, C, d), f32), ((n, C), i32), ((n,), i32), ((n,), i32),
             ((K, n, d), f32), ((K, n), i32), ((K, n), i32), ((n, d), f32),
             ((n,), f32), one_chip=one_chip)


@pytest.mark.parametrize("wire", ["int8", "int8_sr", "int4_ef"])
def test_quantize_send_compiles(wire, one_chip):
    from repro.kernels.gossip_cycle import quantize_send

    if wire == "int8_sr":
        fn = jax.jit(lambda w, kd: quantize_send(w, wire, key_data=kd))
        shapes = [((N, D), jnp.float32), ((2,), jnp.uint32)]
    elif wire == "int4_ef":
        fn = jax.jit(lambda w, ef: quantize_send(w, wire, ef=ef))
        shapes = [((N, D), jnp.float32), ((N, D), jnp.float32)]
    else:
        fn = jax.jit(lambda w: quantize_send(w, wire))
        shapes = [((N, D), jnp.float32)]
    _compile(fn, *shapes, one_chip=one_chip)


def test_voted_predict_batched_compiles(one_chip):
    from repro.kernels.voted_predict import voted_predict_batched

    _compile(jax.jit(voted_predict_batched),
             ((M, C, D), jnp.float32), ((M,), jnp.int32),
             ((M, D), jnp.float32), one_chip=one_chip)
