"""Fused send-side quantization kernel: bitwise parity with the jnp path.

``quantize_send`` must reproduce ``quantize_wire`` bit for bit — codes,
f16 scale and zero-point — including the "int8_sr" stochastic-rounding
uniform, which the kernel regenerates *in kernel* with an op-exact
threefry-2x32 (the engines' parity contract rules out the TPU-native PRNG,
whose stream differs from ``jax.random.uniform``'s)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gossip_optimizer import quantize_wire
from repro.core.wire_codec import uniform_at
from repro.kernels.gossip_cycle import quantize_send


def rand_w(n, d, seed=0, spread=True):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(n, d))
    if spread:                      # heterogeneous per-message ranges
        w *= rng.uniform(1e-3, 30.0, size=(n, 1))
    return jnp.asarray(w, jnp.float32)


@pytest.mark.parametrize("n,d", [(64, 10), (33, 7), (1, 1), (128, 57),
                                 (40, 128), (7, 130)])
def test_quantize_send_matches_quantize_wire_int8(n, d):
    w = rand_w(n, d, seed=n)
    q0, s0, z0 = quantize_wire(w, "int8")
    q1, s1, z1 = quantize_send(w, "int8", interpret=True)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(z0), np.asarray(z1))


@pytest.mark.parametrize("n,d", [(64, 10), (33, 7), (5, 3), (96, 57),
                                 (1100, 57)])
def test_quantize_send_matches_quantize_wire_int8_sr(n, d):
    """Stochastic rounding: the in-kernel threefry draw must equal the
    ``jax.random.uniform(k_recv, (n, d))`` draw of the jnp path, for even
    and odd sizes and a population that spans several node blocks."""
    w = rand_w(n, d, seed=n + 1)
    key = jax.random.split(jax.random.key(42), 4)[0]     # a k_recv slot
    q0, s0, z0 = quantize_wire(w, "int8_sr", key=key)
    q1, s1, z1 = quantize_send(w, "int8_sr",
                               key_data=jax.random.key_data(key),
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(z0), np.asarray(z1))


@pytest.mark.parametrize("size", [8, 9, 2048, 2049])
def test_uniform_at_matches_jax_random_uniform(size):
    """The kernel's threefry helper IS jax.random.uniform, elementwise,
    under the partitionable counter scheme of the installed JAX."""
    assert jax.config.jax_threefry_partitionable
    key = jax.random.key(123)
    ref = jax.random.uniform(key, (size,))
    k0, k1 = (jnp.uint32(x) for x in np.asarray(jax.random.key_data(key)))
    got = uniform_at(k0, k1, jnp.arange(size, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_in_kernel_f16_rounding_matches_cast():
    """The send kernels round scales to f16 with integer ops (no f16
    conversion in Mosaic on v5e): equal to the saturating f16 cast on
    normals, subnormals, exact ties and out-of-range values."""
    from repro.core.wire_codec import _sat_f16
    from repro.kernels.gossip_cycle import _sat_f16 as kernel_sat_f16

    rng = np.random.default_rng(4)
    ties = (np.arange(1, 2049, dtype=np.float32) + 0.5) * 2.0 ** -24
    v = np.concatenate([
        rng.normal(size=4096) * 10.0 ** rng.uniform(-9, 5, size=4096),
        ties, -ties, 1.0 + np.arange(64) * 2.0 ** -11 + 2.0 ** -12,
        [0.0, -0.0, 65504.0, 65519.0, 65520.0, 1e9, -1e9, 2.0 ** -14,
         2.0 ** -25, 3 * 2.0 ** -26]]).astype(np.float32)
    want = np.asarray(_sat_f16(jnp.asarray(v)).astype(jnp.float32))
    got = np.asarray(jax.jit(kernel_sat_f16)(jnp.asarray(v)))
    np.testing.assert_array_equal(want.view(np.uint32), got.view(np.uint32))


def test_constant_and_degenerate_rows():
    """Constant rows (scale 0) and huge-range rows (f16 saturation) take
    the same guarded paths as quantize_wire."""
    w = jnp.stack([jnp.full((16,), 3.25), jnp.zeros((16,)),
                   jnp.linspace(-7e4, 7e4, 16)]).astype(jnp.float32)
    q0, s0, z0 = quantize_wire(w, "int8")
    q1, s1, z1 = quantize_send(w, "int8", interpret=True)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(z0), np.asarray(z1))


def test_sharded_engine_send_kernel_bitwise():
    """Engine-level: use_send_kernel routes every send through the kernel
    and the run still reproduces the reference engine bitwise."""
    from repro.configs.gossip_linear import GossipLinearConfig
    from repro.core.simulation import run_simulation
    from repro.data.synthetic import make_linear_dataset

    rng = np.random.default_rng(0)
    n, d = 64, 16
    X, y = make_linear_dataset(rng, n + 48, d, noise=0.05, separation=3.0)
    cfg = GossipLinearConfig(name="sendk", dim=d, n_nodes=n, n_test=48,
                             class_ratio=(1, 1), lam=1e-3, variant="mu",
                             drop_prob=0.5, delay_max_cycles=6,
                             online_fraction=0.8, wire_dtype="int8_sr")
    kw = dict(cycles=18, eval_every=6, seed=9)
    ref = run_simulation(cfg, X[:n], y[:n], X[n:], y[n:], **kw)
    sh = run_simulation(cfg, X[:n], y[:n], X[n:], y[n:], engine="sharded",
                        use_send_kernel=True, interpret=True, **kw)
    assert ref.cycles == sh.cycles
    assert ref.err_fresh == sh.err_fresh
    assert ref.err_voted == sh.err_voted
    assert ref.sent_total == sh.sent_total


def test_send_kernel_argument_validation():
    from repro.configs.gossip_linear import GossipLinearConfig
    from repro.core.simulation import run_simulation
    from repro.data.synthetic import make_linear_dataset

    rng = np.random.default_rng(0)
    X, y = make_linear_dataset(rng, 32 + 16, 8, noise=0.05)
    cfg = GossipLinearConfig(name="v", dim=8, n_nodes=32, n_test=16,
                             class_ratio=(1, 1))
    with pytest.raises(ValueError, match="quantized"):
        run_simulation(cfg, X[:32], y[:32], X[32:], y[32:], cycles=2,
                       engine="sharded", use_send_kernel=True)
    with pytest.raises(ValueError, match="needs key_data"):
        quantize_send(jnp.zeros((4, 4)), "int8_sr", interpret=True)
    with pytest.raises(ValueError, match="quantized wire codec"):
        quantize_send(jnp.zeros((4, 4)), "bf16", interpret=True)


@pytest.mark.parametrize("wire", ["int4", "ternary"])
@pytest.mark.parametrize("n,d", [(64, 10), (33, 7), (1, 1), (96, 57),
                                 (40, 128), (7, 130)])
def test_quantize_send_matches_codec_encode_packed(wire, n, d):
    """The packed sub-4-bit codecs: in-kernel symmetric scale, code pack
    and (with ``ef``) the fused EF residual must all equal the jnp codec
    chain bit for bit — including odd d (half-filled final byte) and the
    d > 128 multi-lane-tile case."""
    from repro.core.wire_codec import get_codec

    codec = get_codec(wire)
    w = rand_w(n, d, seed=n + d)
    p0, s0, _ = codec.encode(w)
    p1, s1 = quantize_send(w, wire, interpret=True)
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))

    # fused EF pass: encode(w + ef) + residual, vs the jnp chain
    ef = rand_w(n, d, seed=n + d + 1) * 0.1
    x = w + ef
    p2, s2, _ = codec.encode(x)
    resid = x - codec.decode(p2, s2, None, d)
    p3, s3, r3 = quantize_send(w, wire, ef=ef, interpret=True)
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(p3))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s3))
    np.testing.assert_array_equal(np.asarray(resid), np.asarray(r3))


def test_quantize_send_packed_degenerate_rows():
    """Constant, zero and f16-saturating rows take the same guarded paths
    as the jnp codec."""
    from repro.core.wire_codec import get_codec

    w = jnp.stack([jnp.full((16,), 3.25), jnp.zeros((16,)),
                   jnp.linspace(-7e4, 7e4, 16)]).astype(jnp.float32)
    for wire in ("int4", "ternary"):
        codec = get_codec(wire)
        p0, s0, _ = codec.encode(w)
        p1, s1 = quantize_send(w, wire, interpret=True)
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
