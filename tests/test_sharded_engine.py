"""Sharded mega-population engine: parity with the reference engine.

The sharded engine consumes the same host RNG stream and per-cycle key
sequence as the reference driver and shares its cycle math, so for a given
seed the error curves must reproduce the reference engine's (the acceptance
bar is 0.02 at every eval point; in practice they are bitwise-equal)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gossip_linear import GossipLinearConfig
from repro.core.sharded_engine import (dense_table, key_schedule,
                                       pack_compact_rounds)
from repro.core.simulation import message_wire_bytes, run_simulation
from repro.data.synthetic import make_linear_dataset


def small_cfg(n_nodes=128, **kw):
    base = dict(name="toy", dim=16, n_nodes=n_nodes, n_test=64,
                class_ratio=(1, 1), lam=1e-3, variant="mu")
    base.update(kw)
    return GossipLinearConfig(**base)


def toy(n=128, d=16, seed=0):
    rng = np.random.default_rng(seed)
    X, y = make_linear_dataset(rng, n + 64, d, noise=0.05, separation=3.0)
    return X[:n], y[:n], X[n:], y[n:]


def assert_curves_close(a, b, tol=0.02):
    assert a.cycles == b.cycles
    for xa, xb in zip(a.err_fresh, b.err_fresh):
        assert abs(xa - xb) <= tol, (a.err_fresh, b.err_fresh)
    for xa, xb in zip(a.err_voted, b.err_voted):
        assert abs(xa - xb) <= tol, (a.err_voted, b.err_voted)


def test_key_schedule_matches_host_split_loop():
    keys = key_schedule(7, 5)
    k = jax.random.key(7)
    for c in range(5):
        k, sub = jax.random.split(k)
        assert jnp.all(jax.random.key_data(keys[c]) == jax.random.key_data(sub))


def test_sharded_matches_reference_clean_scenario():
    X, y, Xt, yt = toy()
    kw = dict(cycles=30, eval_every=10, seed=1)
    ref = run_simulation(small_cfg(), X, y, Xt, yt, **kw)
    sh = run_simulation(small_cfg(), X, y, Xt, yt, engine="sharded", **kw)
    assert_curves_close(ref, sh)
    assert (ref.sent_total, ref.delivered_total, ref.lost_total,
            ref.overflow_total) == (sh.sent_total, sh.delivered_total,
                                    sh.lost_total, sh.overflow_total)


def test_sharded_matches_reference_failure_scenario():
    """Drop 0.5 + 10Δ delay + churn — the paper's extreme setting."""
    X, y, Xt, yt = toy()
    cfg = small_cfg(drop_prob=0.5, delay_max_cycles=10, online_fraction=0.9)
    kw = dict(cycles=40, eval_every=20, seed=3)
    ref = run_simulation(cfg, X, y, Xt, yt, **kw)
    sh = run_simulation(cfg, X, y, Xt, yt, engine="sharded", **kw)
    assert_curves_close(ref, sh)
    assert ref.lost_total == sh.lost_total > 0  # churn actually loses messages


@pytest.mark.parametrize("wire", [None, "bf16", "int8", "int8_sr"])
@pytest.mark.parametrize("variant", ["mu", "um", "rw"])
def test_sharded_pallas_kernel_matches_reference(variant, wire):
    """The fused gossip_cycle kernel path (interpret mode on CPU), including
    bf16 wire message operands (the widened 16-sublane node block) and
    affine-int8 operands (32-sublane block, in-kernel dequant from the
    per-message f16 scale/zero-point)."""
    X, y, Xt, yt = toy(n=64)
    cfg = small_cfg(n_nodes=64, variant=variant, drop_prob=0.2,
                    delay_max_cycles=3, wire_dtype=wire)
    kw = dict(cycles=20, eval_every=10, seed=5)
    ref = run_simulation(cfg, X, y, Xt, yt, **kw)
    sh = run_simulation(cfg, X, y, Xt, yt, engine="sharded",
                        use_pallas=True, interpret=True, **kw)
    assert_curves_close(ref, sh)


def test_pallas_kernel_refuses_other_learners():
    """The receive kernel implements the Pegasos update only: asking for it
    with another learner is an error, not a quiet switch to the vector
    apply."""
    X, y, Xt, yt = toy(n=32)
    cfg = small_cfg(n_nodes=32, learner="adaline")
    with pytest.raises(ValueError, match="Pegasos"):
        run_simulation(cfg, X, y, Xt, yt, cycles=2, engine="sharded",
                       use_pallas=True, interpret=True)


@pytest.mark.parametrize("sampler", ["uniform", "matching"])
@pytest.mark.parametrize("n", [32, 33])
def test_sharded_engine_odd_and_even_populations(sampler, n):
    """Both engines handle odd N — incl. the matching sampler's idle node."""
    X, y, Xt, yt = toy(n=n)
    kw = dict(cycles=16, eval_every=8, seed=2, sampler=sampler)
    ref = run_simulation(small_cfg(n_nodes=n), X, y, Xt, yt, **kw)
    sh = run_simulation(small_cfg(n_nodes=n), X, y, Xt, yt,
                        engine="sharded", **kw)
    assert_curves_close(ref, sh)
    if sampler == "matching" and n % 2 == 1:
        # one node idles per cycle: at most (n-1) sends per cycle
        assert ref.sent_total <= (n - 1) * 16


def test_sharded_engine_multirecord_nodes():
    """(N, k, d) multi-record nodes stream through the scan path too."""
    rng = np.random.default_rng(0)
    X, y = make_linear_dataset(rng, 64 * 3 + 32, 8, noise=0.05)
    Xtr = X[:192].reshape(64, 3, 8)
    ytr = y[:192].reshape(64, 3)
    Xt, yt = X[192:], y[192:]
    cfg = small_cfg(n_nodes=64, dim=8)
    kw = dict(cycles=12, eval_every=6, seed=4)
    ref = run_simulation(cfg, Xtr, ytr, Xt, yt, **kw)
    sh = run_simulation(cfg, Xtr, ytr, Xt, yt, engine="sharded", **kw)
    assert_curves_close(ref, sh)


def test_compact_and_dense_rounds_agree():
    """compact_rounds gathers/applies/scatters only multi-receivers; the
    curves and the message economy must equal the dense K-round apply."""
    X, y, Xt, yt = toy()
    cfg = small_cfg(drop_prob=0.5, delay_max_cycles=10, online_fraction=0.9)
    kw = dict(cycles=40, eval_every=20, seed=3, engine="sharded")
    dense = run_simulation(cfg, X, y, Xt, yt, compact_rounds=False, **kw)
    comp = run_simulation(cfg, X, y, Xt, yt, compact_rounds=True, **kw)
    assert dense.err_fresh == comp.err_fresh
    assert dense.err_voted == comp.err_voted
    assert (dense.sent_total, dense.delivered_total, dense.lost_total,
            dense.overflow_total) == (comp.sent_total, comp.delivered_total,
                                      comp.lost_total, comp.overflow_total)


def test_pack_compact_rounds_covers_every_multi_receive():
    """The compacted tables must encode exactly the dense table's rounds:
    round 1 dense, every round >= 2 receive present at the receiver's
    compact position, padding inert (-1)."""
    rng = np.random.default_rng(0)
    T, K, n = 3, 4, 32
    src_slot = np.full((T, K, n), -1, np.int32)
    for t in range(T):
        recv = rng.choice(n, size=12, replace=False)
        for j, node in enumerate(recv):
            depth = 1 + (j % K)              # winner rounds fill in order
            src_slot[t, :depth, node] = rng.integers(0, 64, size=depth)
    multi = [np.flatnonzero(src_slot[t, 1] >= 0).astype(np.int32)
             for t in range(T)]
    # the winner-tuple form the router emits (ascending t)
    t_w, r_w, dst_w = (a.astype(np.int32) for a in np.nonzero(src_slot >= 0))
    win = (t_w, r_w, dst_w, src_slot[t_w, r_w, dst_w])
    assert np.array_equal(dense_table(win, T, K, n), src_slot)
    width = max(r.size for r in multi) + 3   # over-wide: padding must be inert
    src0, ridx, rslot = pack_compact_rounds(win, multi, T, K, n, width)
    assert np.array_equal(src0, src_slot[:, 0])
    for t in range(T):
        m = multi[t]
        assert np.array_equal(ridx[t, :m.size], m)
        assert np.all(ridx[t, m.size:] == -1)
        assert np.all(rslot[t, :, m.size:] == -1)
        for k in range(1, K):
            assert np.array_equal(rslot[t, k - 1, :m.size], src_slot[t, k, m])


def test_compact_dense_fallback_mid_run(monkeypatch):
    """A chunk whose receiver subsets are near-full (> N/2) must fall back
    to the dense table without disturbing the compact chunks around it.
    Forced by making the router report full receiver lists for one chunk —
    the src_slot table stays truthful, so the dense path must reproduce the
    reference curves while the run mixes compact and dense chunk fns."""
    from repro.core import sharded_engine as se

    X, y, Xt, yt = toy()
    cfg = small_cfg(drop_prob=0.5, delay_max_cycles=10, online_fraction=0.9)
    kw = dict(cycles=30, eval_every=10, seed=3)
    ref = run_simulation(cfg, X, y, Xt, yt, **kw)

    orig = se._HostRouter.route_chunk
    calls = []

    def fake(self, dsts, arrivals, online_rows, clock0, k_rounds,
             per_cycle_stats=False):
        src_slot, stats, multi, recv = orig(self, dsts, arrivals,
                                            online_rows, clock0, k_rounds,
                                            per_cycle_stats=per_cycle_stats)
        if len(calls) == 1:           # middle chunk: claim near-full rounds
            full = [np.arange(self.n, dtype=np.int32)] * len(multi)
            multi, recv = full, full
        calls.append(max(r.size for r in multi))
        return src_slot, stats, multi, recv

    monkeypatch.setattr(se._HostRouter, "route_chunk", fake)
    sh = run_simulation(cfg, X, y, Xt, yt, engine="sharded",
                        compact_rounds=True, **kw)
    assert len(calls) == 3 and calls[1] == 128  # fallback chunk was forced
    assert sh.compaction["chunk_modes"]["dense"] == 1  # ... and ran dense
    assert_curves_close(ref, sh)
    assert ref.sent_total == sh.sent_total


def test_sharded_wire_bf16_matches_reference_wire_bf16():
    """Both engines quantize at send time with the same semantics, so the
    bf16-wire curves must agree across engines like the f32 curves do."""
    X, y, Xt, yt = toy()
    cfg = small_cfg(drop_prob=0.5, delay_max_cycles=10, online_fraction=0.9,
                    wire_dtype="bf16")
    kw = dict(cycles=40, eval_every=20, seed=3)
    ref = run_simulation(cfg, X, y, Xt, yt, **kw)
    sh = run_simulation(cfg, X, y, Xt, yt, engine="sharded", **kw)
    assert_curves_close(ref, sh)
    assert (ref.sent_total, ref.delivered_total) == (sh.sent_total,
                                                     sh.delivered_total)


def test_wire_bf16_curves_close_to_f32():
    """Documented tolerance: bf16 wire quantization moves the error curves
    by at most 0.05 at any eval point on the toy problem."""
    X, y, Xt, yt = toy()
    kw = dict(cycles=30, eval_every=10, seed=1, engine="sharded")
    f32 = run_simulation(small_cfg(), X, y, Xt, yt, **kw)
    bf16 = run_simulation(small_cfg(wire_dtype="bf16"), X, y, Xt, yt, **kw)
    assert_curves_close(f32, bf16, tol=0.05)


def test_wire_quantization_matches_gossip_merge_exchange():
    """The simulator's wire semantics — quantize the transmitted model,
    merge in f32 — must equal gossip_merge's exchange_dtype semantics."""
    from repro.core.gossip_optimizer import gossip_merge
    from repro.core.learners import LinearModel
    from repro.core.merge import merge

    w = jnp.asarray(np.random.default_rng(0).normal(size=(2, 64)), jnp.float32)
    out = gossip_merge({"w": w}, np.array([1, 0]),
                       exchange_dtype=jnp.bfloat16)["w"]
    # simulator: peer 1's model is quantized at send time, peer 0 merges it
    # (in f32) with its own full-precision model
    msg = w[1].astype(jnp.bfloat16).astype(jnp.float32)
    t = jnp.zeros((), jnp.int32)
    mine = merge(LinearModel(msg, t), LinearModel(w[0], t)).w
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(mine))


@pytest.mark.parametrize("engine", ["reference", "sharded"])
def test_wire_and_buffer_accounting(engine):
    """wire_bytes_total and buf_payload_bytes follow the wire dtype exactly;
    bf16 halves both the per-coefficient wire cost and the dominant
    in-flight buffer."""
    X, y, Xt, yt = toy(n=32)
    kw = dict(cycles=10, eval_every=10, seed=0, engine=engine)
    d, D = 16, 4
    f32 = run_simulation(small_cfg(n_nodes=32, delay_max_cycles=D),
                         X, y, Xt, yt, **kw)
    bf16 = run_simulation(small_cfg(n_nodes=32, delay_max_cycles=D,
                                    wire_dtype="bf16"), X, y, Xt, yt, **kw)
    assert message_wire_bytes(d, None) == d * 4 + 4
    assert message_wire_bytes(d, "bf16") == d * 2 + 4
    assert f32.wire_bytes_total == f32.sent_total * (d * 4 + 4)
    assert bf16.wire_bytes_total == bf16.sent_total * (d * 2 + 4)
    assert f32.buf_payload_bytes == D * 32 * d * 4
    assert bf16.buf_payload_bytes == D * 32 * d * 2
    assert bf16.sent_total == f32.sent_total  # routing is payload-blind


def test_sharded_engine_rejects_unknown_engine():
    X, y, Xt, yt = toy(n=16)
    with pytest.raises(ValueError, match="unknown engine"):
        run_simulation(small_cfg(n_nodes=16), X, y, Xt, yt, cycles=2,
                       engine="bogus")


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs.gossip_linear import GossipLinearConfig
    from repro.core.simulation import run_simulation
    from repro.data.synthetic import make_linear_dataset

    assert len(jax.devices()) == 4
    rng = np.random.default_rng(0)
    X, y = make_linear_dataset(rng, 128 + 64, 16, noise=0.05, separation=3.0)
    Xtr, ytr, Xt, yt = X[:128], y[:128], X[128:], y[128:]
    cfg = GossipLinearConfig(name="toy", dim=16, n_nodes=128, n_test=64,
                             class_ratio=(1, 1), lam=1e-3, variant="mu",
                             drop_prob=0.3, delay_max_cycles=4)
    kw = dict(cycles=20, eval_every=10, seed=6)
    ref = run_simulation(cfg, Xtr, ytr, Xt, yt, **kw)
    mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("nodes",))
    sh = run_simulation(cfg, Xtr, ytr, Xt, yt, engine="sharded",
                        mesh=mesh, **kw)
    for a, b in zip(ref.err_fresh, sh.err_fresh):
        assert abs(a - b) <= 0.02, (ref.err_fresh, sh.err_fresh)
    assert ref.sent_total == sh.sent_total
    # compacted rounds now run UNDER the node mesh (per-shard packed
    # tables): the default run must have used a compact packing
    assert sh.compaction["shards"] == 4, sh.compaction
    cm = sh.compaction["chunk_modes"]
    assert cm["compact"] + cm["compact_all"] > 0, cm

    # forced shard-local compact_all parity under the mesh
    sha = run_simulation(cfg, Xtr, ytr, Xt, yt, engine="sharded",
                         mesh=mesh, compact_mode="compact_all", **kw)
    assert sha.err_fresh == sh.err_fresh
    assert sha.sent_total == sh.sent_total

    # int8 wire dtype under node sharding: the (D, N) scale/zero-point
    # lanes shard with the buffer and parity still holds
    import dataclasses
    cfg8 = dataclasses.replace(cfg, wire_dtype="int8_sr")
    ref8 = run_simulation(cfg8, Xtr, ytr, Xt, yt, **kw)
    sh8 = run_simulation(cfg8, Xtr, ytr, Xt, yt, engine="sharded",
                         mesh=mesh, **kw)
    for a, b in zip(ref8.err_fresh, sh8.err_fresh):
        assert abs(a - b) <= 0.02, (ref8.err_fresh, sh8.err_fresh)
    assert ref8.sent_total == sh8.sent_total

    # packed int4 + error feedback under node sharding: the packed (D, N,
    # ceil(d/2)) payload, the scale lane AND the (N, d) EF residual all
    # shard over the node axis; the residual telemetry matches the
    # reference engine exactly
    cfg4 = dataclasses.replace(cfg, wire_dtype="int4_ef")
    ref4 = run_simulation(cfg4, Xtr, ytr, Xt, yt, **kw)
    sh4 = run_simulation(cfg4, Xtr, ytr, Xt, yt, engine="sharded",
                         mesh=mesh, **kw)
    for a, b in zip(ref4.err_fresh, sh4.err_fresh):
        assert abs(a - b) <= 0.02, (ref4.err_fresh, sh4.err_fresh)
    assert ref4.sent_total == sh4.sent_total
    assert abs(ref4.ef_residual_norm - sh4.ef_residual_norm) \\
        <= 1e-6 * max(ref4.ef_residual_norm, 1.0)
    print("MESH_PARITY_OK")
""")


def test_sharded_engine_multidevice_mesh_parity():
    """shard_map node-axis path on a 4-device (forced host) mesh.

    Runs in a subprocess because device count must be fixed before JAX
    initializes (tests/conftest.py pins the main process to one device)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "MESH_PARITY_OK" in out.stdout
