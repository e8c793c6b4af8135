"""The d-tiled receive kernel (``gossip_cycle.fused_receive_apply_dtiled``)
and the rule by which the engine picks it, in interpret mode on the CPU.

The tiled kernel sums each round's Pegasos margin tile by tile and then
over the tiles, where the whole-d kernel and the jnp path sum over all of d
at once. f32 addition is not associative, so the models may differ by a
few ulps of the margin's terms: model comparisons use the tolerance of the
other kernel tests (rtol 2e-5, atol 1e-5). Every integer output (update
counters, ring pointers, valid-slot counts, screen counts) is exact.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gossip_linear import GossipLinearConfig
from repro.core.cache import ModelCache, freshest
from repro.core.learners import make_update
from repro.core.simulation import apply_receives, run_simulation
from repro.data.synthetic import make_linear_dataset
from repro.kernels import gossip_cycle as gc

RTOL, ATOL = 2e-5, 1e-5          # the margin's summation order (module doc)
C, K = 10, 4


def _operands(n, d, seed=0):
    """Random receive operands whose margins fall on both sides of 1."""
    ks = jax.random.split(jax.random.key(seed), 8)
    s = 1.0 / np.sqrt(d)
    ptr = jax.random.randint(ks[4], (n,), 1, 3 * C)
    cache = ModelCache(jax.random.normal(ks[2], (n, C, d)) * s,
                       jax.random.randint(ks[3], (n, C), 0, 30), ptr,
                       jnp.minimum(ptr, C))
    return dict(
        last_w=jax.random.normal(ks[0], (n, d)) * s,
        last_t=jax.random.randint(ks[1], (n,), 0, 30), cache=cache,
        msg_w=jax.random.normal(ks[5], (K, n, d)) * s,
        msg_t=jax.random.randint(ks[6], (K, n), 0, 30),
        valid=jax.random.bernoulli(ks[7], 0.7, (K, n)),
        x=2.0 * jax.random.normal(ks[1], (n, d)),
        y=jnp.sign(jax.random.normal(ks[0], (n,)) + 0.1))


def _kernel_args(o):
    fw, ft = freshest(o["cache"])
    c = o["cache"]
    return (o["last_w"], o["last_t"], fw, ft, c.w, c.t, c.ptr, c.count,
            o["msg_w"], o["msg_t"], o["valid"].astype(jnp.int32), o["x"],
            o["y"])


@pytest.mark.parametrize("oracle", ["whole_d_kernel", "jnp_apply_receives"])
def test_dtiled_kernel_matches(oracle):
    """N=1024 (two node blocks), d=1000 in tiles of 256 (the last one
    ragged: 232 rows), against the whole-d kernel and the jnp path."""
    n, d, lam = 1024, 1000, 0.01
    o = _operands(n, d)
    args = _kernel_args(o)
    got = gc.fused_receive_apply_dtiled(*args, variant="mu", lam=lam,
                                        interpret=True, d_tile=256)
    if oracle == "whole_d_kernel":
        exp = gc.fused_receive_apply(*args, variant="mu", lam=lam,
                                     interpret=True)
    else:
        lw, lt, cache, gated, clipped = apply_receives(
            o["last_w"], o["last_t"], o["cache"], o["msg_w"], o["msg_t"],
            o["valid"], o["x"], o["y"], variant="mu",
            update=make_update("pegasos", lam=lam))
        exp = (lw, lt, *freshest(cache), cache.w, cache.t, cache.ptr,
               cache.count, gated, clipped)
    names = ("last_w", "last_t", "fresh_w", "fresh_t", "cache_w", "cache_t",
             "ptr", "count", "gated", "clipped")
    margins = o["y"] * jnp.sum((o["msg_w"][0] + o["last_w"]) / 2 * o["x"], 1)
    assert 0.05 < float(jnp.mean(margins < 1.0)) < 0.95  # both branches
    for name, g, e in zip(names, got, exp):
        g, e = np.asarray(g), np.asarray(e)
        assert g.shape == e.shape, name
        if g.dtype.kind == "f":
            np.testing.assert_allclose(g, e, rtol=RTOL, atol=ATOL,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(g, e.astype(g.dtype), name)


def _tiled_run_inputs():
    """A population and width at which the fit rule picks the tiled kernel
    (N=512, one node block; d=160 -> tiles of 96 and a ragged 64)."""
    n, d = 512, 160
    rng = np.random.default_rng(4)
    X, y = make_linear_dataset(rng, n + 64, d, noise=0.05, separation=3.0)
    cfg = GossipLinearConfig(name="wide", dim=d, n_nodes=n, n_test=64,
                             class_ratio=(1, 1), lam=1e-3, variant="mu",
                             drop_prob=0.3, delay_max_cycles=3,
                             online_fraction=0.9)
    return cfg, (X[:n], y[:n], X[n:], y[n:])


def test_sharded_run_takes_tiled_kernel_and_matches_reference():
    cfg, data = _tiled_run_inputs()
    n, d = cfg.n_nodes, cfg.dim
    tile = gc.receive_d_tile(n, d, cfg.cache_size, K)
    assert tile < d
    kw = dict(cycles=12, eval_every=6, seed=7, k_rounds=K)
    snaps = {}

    def hook(name):
        def keep(cycle, snap):
            snaps[name] = [np.asarray(a) for a in (snap.w, snap.t,
                                                   snap.count)]
        return keep
    ref = run_simulation(cfg, *data, serve_hook=hook("reference"), **kw)
    sh = run_simulation(cfg, *data, engine="sharded", use_pallas=True,
                        interpret=True, serve_hook=hook("sharded"), **kw)
    economy = lambda r: (r.sent_total, r.delivered_total, r.lost_total,
                         r.overflow_total, list(r.delivered_per_cycle))
    assert economy(sh) == economy(ref)
    assert sh.delivered_total > 0
    (w_s, t_s, c_s), (w_r, t_r, c_r) = snaps["sharded"], snaps["reference"]
    np.testing.assert_array_equal(t_s, t_r)
    np.testing.assert_array_equal(c_s, c_r)
    np.testing.assert_allclose(w_s, w_r, rtol=RTOL, atol=ATOL)
    for a, b in ((sh.err_fresh, ref.err_fresh), (sh.err_voted,
                                                 ref.err_voted)):
        np.testing.assert_allclose(a, b, atol=0.02)
    # the span record: the engine's choice, and the bytes its calls move
    assert sh.trace.named("setup")[0].counters["receive_d_tile"] == tile
    per_call = gc.receive_call_bytes(n, d, cfg.cache_size, K, None, tile)
    assert [s.counters["receive_bytes"] for s in sh.trace.named("dispatch")] \
        == [6 * per_call, 6 * per_call]


@pytest.mark.parametrize("change", [dict(wire_dtype="int8"),
                                    dict(variant="um"),
                                    dict(defense="norm_clip")])
def test_tiled_path_refuses_what_it_does_not_implement(change):
    """A quantized wire, another CREATEMODEL variant or a defense at a
    width only the tiled kernel holds is an error, not a quiet fallback."""
    cfg, data = _tiled_run_inputs()
    cfg = dataclasses.replace(cfg, **change)
    with pytest.raises(ValueError, match="d-tiled receive kernel"):
        run_simulation(cfg, *data, cycles=2, eval_every=2, engine="sharded",
                       use_pallas=True, interpret=True)


@pytest.mark.parametrize("cell,n_local,d,whole", [
    ("spambase-1m.extreme.serve", 10 ** 6, 57, True),
    ("spambase-1m.sparse", 10 ** 6, 57, True),
    ("spambase-1m.clean", 10 ** 6, 57, True),
    ("spambase-4m.extreme.mesh", 4 * 10 ** 6 // 4, 57, True),   # a shard
    ("reuters-6k.extreme", 6144, 9947, False),
])
def test_fit_rule_at_the_cells_shapes(cell, n_local, d, whole):
    tile = gc.receive_d_tile(n_local, d, C, K)
    if whole:
        assert tile == d, cell
    else:
        assert tile < d and tile % 8 == 0, (cell, tile)


@pytest.mark.parametrize("n,d,tile", [(1000, 57, 57), (1100, 300, 96)])
def test_receive_call_bytes_counts_every_block_move(n, d, tile):
    """Per node: the whole-d kernel reads K messages, K counters, K valid
    flags, the example (d + 1 words), the last and freshest models (d + 1
    each), the cache (C·d + C) and two counters, and writes back the
    state it read from the last model on, plus two screen counts. The
    tiled kernel reads the messages, the last model and the example once
    more, in its margin pass."""
    whole = (K * d + 2 * K + 3 * (d + 1) + (C * d + C) + 2) \
        + (2 * (d + 1) + (C * d + C) + 4)
    margin_pass = (K + 2) * d if tile != d else 0
    assert gc.receive_call_bytes(n, d, C, K, None, tile) \
        == 4 * n * (whole + margin_pass)
