"""Adversarial-fault subsystem: registry properties, corruption/defense
unit semantics, the k_fault no-consumption contract (fault-free runs stay
bitwise identical), and the bitwise cross-engine parity of every fault
model across the reference engine, all three sharded packings and the
Pallas interpret path — with the defense screen active.

The fault contract (``repro.core.faults``): a seed-chosen Byzantine subset
corrupts every model it sends (model-kind faults rewrite the transmitted
weights before the wire encode; the wire-kind ``bitflip`` flips one bit of
the encoded payload after it), and the receive path may screen each
incoming payload per merge round against the receiver's current lastModel.
Fault draws ride ``fault_key = fold_in(cycle_key, FAULT_FOLD)`` — derived,
never consumed from the pinned ``split(key, 4)`` sequence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gossip_linear import (FAILURE_SCENARIOS,
                                         GossipLinearConfig,
                                         with_failure_scenario)
from repro.core.cache import ModelCache, cache_oldest, voted_predict
from repro.core.faults import (AMPLIFY_GAMMA, DEFENSES, FAULT_MODELS,
                               NORM_CLIP_FLOOR, NORM_CLIP_MULT,
                               SIGN_FLIP_GAMMA, apply_defense,
                               bitflip_payload, byzantine_mask,
                               check_defense, corrupt_model, fault_key,
                               get_fault)
from repro.core.simulation import run_simulation
from repro.data.synthetic import make_linear_dataset

MODEL_FAULTS = [n for n, f in FAULT_MODELS.items() if f.kind == "model"]


@pytest.fixture(scope="module", autouse=True)
def _release_compile_caches():
    """The parity-matrix tests below compile a few hundred distinct engine
    signatures (fault x codec x packing); drop them at module teardown so
    the single-process tier-1 run stays within the box's native compile
    budget (the accumulated executables crash XLA's compiler late in the
    suite otherwise)."""
    yield
    jax.clear_caches()


def small_cfg(n_nodes=128, **kw):
    base = dict(name="toy", dim=16, n_nodes=n_nodes, n_test=64,
                class_ratio=(1, 1), lam=1e-3, variant="mu")
    base.update(kw)
    return GossipLinearConfig(**base)


def toy(n=128, d=16, seed=0):
    rng = np.random.default_rng(seed)
    X, y = make_linear_dataset(rng, n + 64, d, noise=0.05, separation=3.0)
    return X[:n], y[:n], X[n:], y[n:]


# ---------------------------------------------------------------------------
# registry + config plumbing
# ---------------------------------------------------------------------------


def test_registry_names_and_kinds():
    assert set(FAULT_MODELS) == {"sign_flip", "amplify", "zero",
                                 "random_payload", "stale_replay", "bitflip"}
    kinds = {n: f.kind for n, f in FAULT_MODELS.items()}
    assert kinds == {"sign_flip": "model", "amplify": "model",
                     "zero": "model", "random_payload": "model",
                     "stale_replay": "model", "bitflip": "wire"}
    assert get_fault(None) is None and get_fault("") is None
    with pytest.raises(ValueError, match="unknown fault model"):
        get_fault("gaussian")
    assert DEFENSES == ("none", "norm_clip", "cosine_gate")
    assert check_defense("norm_clip") == "norm_clip"
    with pytest.raises(ValueError, match="unknown defense"):
        check_defense("median")


def test_config_fails_fast_on_bad_fault_knobs():
    X, y, Xt, yt = toy(n=32)
    kw = dict(cycles=2, eval_every=2, seed=0)
    with pytest.raises(ValueError, match="unknown fault model"):
        run_simulation(small_cfg(n_nodes=32, fault_model="nope",
                                 byzantine_frac=0.1), X, y, Xt, yt, **kw)
    with pytest.raises(ValueError, match="unknown defense"):
        run_simulation(small_cfg(n_nodes=32, defense="median"),
                       X, y, Xt, yt, **kw)
    with pytest.raises(ValueError, match="byzantine_frac"):
        run_simulation(small_cfg(n_nodes=32, fault_model="zero",
                                 byzantine_frac=1.5), X, y, Xt, yt, **kw)


def test_with_failure_scenario_validates_override_keys(monkeypatch):
    """Regression: a typo'd key in a scenario dict used to surface only as
    dataclasses.replace's generic TypeError — now it fails loudly naming
    the offending keys before any replace happens."""
    cfg = small_cfg()
    with pytest.raises(ValueError, match="unknown failure scenario"):
        with_failure_scenario(cfg, "extreme-typo")
    monkeypatch.setitem(FAILURE_SCENARIOS, "bad-scenario",
                        dict(drop_prob=0.5, drop_probz=0.9))
    with pytest.raises(ValueError, match="drop_probz"):
        with_failure_scenario(cfg, "bad-scenario")
    # every registered scenario applies cleanly (the validation is not
    # rejecting legitimate keys)
    for name in ("clean", "extreme", "sparse-d0.8-o0.1"):
        assert with_failure_scenario(cfg, name).name == cfg.name


def test_byzantine_mask_properties():
    m = byzantine_mask(seed=5, n=1000, frac=0.1)
    assert m.dtype == bool and m.shape == (1000,) and m.sum() == 100
    np.testing.assert_array_equal(m, byzantine_mask(5, 1000, 0.1))
    assert not np.array_equal(m, byzantine_mask(6, 1000, 0.1))
    assert byzantine_mask(5, 1000, 0.0).sum() == 0
    assert byzantine_mask(5, 64, 1.0).sum() == 64
    with pytest.raises(ValueError, match="byzantine_frac"):
        byzantine_mask(5, 10, -0.1)


def test_fault_key_derives_without_consuming():
    """The k_fault contract: fold_in gives a deterministic side key and
    leaves the parent key's split sequence untouched."""
    key = jax.random.key(42)
    before = jax.random.key_data(jax.random.split(key, 4))
    kf = fault_key(key)
    assert not np.array_equal(jax.random.key_data(kf),
                              jax.random.key_data(key))
    np.testing.assert_array_equal(
        jax.random.key_data(kf), jax.random.key_data(fault_key(key)))
    after = jax.random.key_data(jax.random.split(key, 4))
    np.testing.assert_array_equal(before, after)


# ---------------------------------------------------------------------------
# corruption semantics (unit level)
# ---------------------------------------------------------------------------


def test_corrupt_model_touches_only_byzantine_rows():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(8, 6)), jnp.float32)
    t = jnp.arange(8, dtype=jnp.int32) + 10
    byz = jnp.asarray([True, False] * 4)
    key = fault_key(jax.random.key(0))
    old_w, old_t = 0.5 * w, t - 7
    for name in MODEL_FAULTS:
        cw, ct = corrupt_model(get_fault(name), byz, key, w, t,
                               old_w=old_w, old_t=old_t)
        np.testing.assert_array_equal(np.asarray(cw)[1::2],
                                      np.asarray(w)[1::2])
        np.testing.assert_array_equal(np.asarray(ct)[1::2],
                                      np.asarray(t)[1::2])
    cw, _ = corrupt_model(get_fault("sign_flip"), byz, key, w, t)
    np.testing.assert_allclose(np.asarray(cw)[0],
                               -SIGN_FLIP_GAMMA * np.asarray(w)[0])
    cw, _ = corrupt_model(get_fault("amplify"), byz, key, w, t)
    np.testing.assert_allclose(np.asarray(cw)[0],
                               AMPLIFY_GAMMA * np.asarray(w)[0])
    cw, _ = corrupt_model(get_fault("zero"), byz, key, w, t)
    assert np.all(np.asarray(cw)[0] == 0.0)
    cw, ct = corrupt_model(get_fault("stale_replay"), byz, key, w, t,
                           old_w=old_w, old_t=old_t)
    np.testing.assert_array_equal(np.asarray(cw)[0], np.asarray(old_w)[0])
    assert int(ct[0]) == int(old_t[0])
    cw, _ = corrupt_model(get_fault("random_payload"), byz, key, w, t)
    scale = np.abs(np.asarray(w)[0]).max()
    assert np.all(np.abs(np.asarray(cw)[0]) <= scale + 1e-6)
    with pytest.raises(ValueError, match="not a model-kind"):
        corrupt_model(get_fault("bitflip"), byz, key, w, t)


def test_corrupt_model_subset_matches_dense_gather():
    """compact_all parity mechanism: random_payload on a sender subset
    regenerates bitwise the dense draw at those global rows."""
    n, d = 32, 9
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    t = jnp.zeros((n,), jnp.int32)
    byz = jnp.asarray(rng.random(n) < 0.5)
    key = fault_key(jax.random.key(7))
    fault = get_fault("random_payload")
    dense, _ = corrupt_model(fault, byz, key, w, t)
    rows = jnp.asarray([3, 0, 31, 17, 8])
    sub, _ = corrupt_model(fault, byz[rows], key, w[rows], t[rows],
                           rows=rows)
    np.testing.assert_array_equal(np.asarray(dense)[np.asarray(rows)],
                                  np.asarray(sub))


@pytest.mark.parametrize("dtype,cols", [(jnp.float32, 6), (jnp.uint8, 5),
                                        (jnp.float16, 4)])
def test_bitflip_flips_exactly_one_bit(dtype, cols):
    rng = np.random.default_rng(2)
    if dtype == jnp.uint8:
        payload = jnp.asarray(rng.integers(0, 255, size=(10, cols)), dtype)
    else:
        payload = jnp.asarray(rng.normal(size=(10, cols)), dtype)
    byz = jnp.asarray(rng.random(10) < 0.5)
    out = bitflip_payload(byz, fault_key(jax.random.key(3)), payload)
    assert out.dtype == payload.dtype
    itemsize = np.dtype(payload.dtype).itemsize
    uint = {1: np.uint8, 2: np.uint16, 4: np.uint32}[itemsize]
    a = np.asarray(jax.lax.bitcast_convert_type(payload, uint))
    b = np.asarray(jax.lax.bitcast_convert_type(out, uint))
    diffbits = np.array([bin(int(x)).count("1")
                         for x in (a ^ b).astype(np.uint64).ravel()]
                        ).reshape(a.shape).sum(axis=-1)
    np.testing.assert_array_equal(diffbits, np.asarray(byz).astype(int))


def test_bitflip_subset_matches_dense_gather():
    n, cols = 24, 5
    rng = np.random.default_rng(3)
    payload = jnp.asarray(rng.integers(0, 255, size=(n, cols)), jnp.uint8)
    byz = jnp.asarray(rng.random(n) < 0.6)
    key = fault_key(jax.random.key(11))
    dense = bitflip_payload(byz, key, payload)
    rows = jnp.asarray([23, 1, 12, 0, 7, 19])
    sub = bitflip_payload(byz[rows], key, payload[rows], rows=rows)
    np.testing.assert_array_equal(np.asarray(dense)[np.asarray(rows)],
                                  np.asarray(sub))


def test_cache_oldest_picks_slot_ptr_minus_count():
    w = jnp.arange(2 * 3 * 2, dtype=jnp.float32).reshape(2, 3, 2)
    cache = ModelCache(w, jnp.asarray([[5, 6, 7], [8, 9, 10]], jnp.int32),
                       ptr=jnp.asarray([4, 1], jnp.int32),
                       count=jnp.asarray([3, 1], jnp.int32))
    ow, ot = cache_oldest(cache)
    # node 0: slot (4-3)%3 = 1; node 1: slot (1-1)%3 = 0
    np.testing.assert_array_equal(np.asarray(ow),
                                  np.asarray(w)[[0, 1], [1, 0]])
    np.testing.assert_array_equal(np.asarray(ot), [6, 8])


# ---------------------------------------------------------------------------
# defense semantics (unit level)
# ---------------------------------------------------------------------------


def test_defense_none_is_structural_noop():
    w = jnp.full((4, 3), 1e6, jnp.float32)
    valid = jnp.asarray([True, True, False, True])
    mw, vm, gated, clipped = apply_defense("none", w, valid, jnp.zeros_like(w))
    assert mw is w and np.array_equal(np.asarray(vm), np.asarray(valid))
    assert not gated.any() and not clipped.any()


def test_norm_clip_bounds_and_preserves():
    rng = np.random.default_rng(4)
    recv = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
    msg = jnp.concatenate([recv[:3] * 100.0, recv[3:] * 0.5])
    valid = jnp.ones(6, bool)
    mw, vm, gated, clipped = apply_defense("norm_clip", msg, valid, recv)
    assert np.array_equal(np.asarray(clipped), [True] * 3 + [False] * 3)
    assert not gated.any() and vm.all()
    thr = np.maximum(NORM_CLIP_MULT * np.linalg.norm(np.asarray(recv),
                                                     axis=-1),
                     NORM_CLIP_FLOOR)
    norms = np.linalg.norm(np.asarray(mw), axis=-1)
    np.testing.assert_allclose(norms[:3], thr[:3], rtol=1e-5)
    # in-bound messages pass through bitwise untouched
    np.testing.assert_array_equal(np.asarray(mw)[3:], np.asarray(msg)[3:])


def test_norm_clip_floor_lets_early_messages_flow():
    """Zero-init receivers (||recv|| = 0) must still accept honest small
    messages — the FLOOR keeps the warm-up phase alive."""
    msg = jnp.full((2, 4), 0.3, jnp.float32)
    mw, vm, gated, clipped = apply_defense(
        "norm_clip", msg, jnp.ones(2, bool), jnp.zeros_like(msg))
    assert vm.all() and not clipped.any()
    np.testing.assert_array_equal(np.asarray(mw), np.asarray(msg))


def test_defenses_gate_non_finite_payloads():
    msg = jnp.asarray([[1.0, jnp.nan], [jnp.inf, 0.0], [1.0, 1.0]],
                      jnp.float32)
    recv = jnp.ones_like(msg)
    valid = jnp.ones(3, bool)
    for defense in ("norm_clip", "cosine_gate"):
        _, vm, gated, _ = apply_defense(defense, msg, valid, recv)
        assert np.array_equal(np.asarray(vm)[:2], [False, False]), defense
        assert np.asarray(vm)[2] and np.array_equal(
            np.asarray(gated)[:2], [True, True]), defense


def test_cosine_gate_rejects_anti_aligned_only():
    recv = jnp.asarray(np.random.default_rng(5).normal(size=(3, 16)),
                       jnp.float32)
    msg = jnp.stack([-4.0 * recv[0], recv[1], recv[2]
                     + 0.01 * jnp.ones(16)])
    _, vm, gated, clipped = apply_defense("cosine_gate", msg,
                                          jnp.ones(3, bool), recv)
    assert np.array_equal(np.asarray(vm), [False, True, True])
    assert np.array_equal(np.asarray(gated), [True, False, False])
    assert not clipped.any()


def test_defense_real_mask_ignores_pad_lanes():
    """The Pallas kernel's node-minor layout: payloads as (d, m) columns
    screened with ``axis=0`` give the same decisions and rescales as the
    (m, d) rows, and a garbage column (the ragged last node block) does
    not change any other node's result."""
    msg = jnp.asarray([[3.0, 4.0], [0.5, -0.2], [1e30, jnp.nan]],
                      jnp.float32)
    recv = jnp.asarray([[1.0, 0.0], [-0.5, 0.2], [1e30, 7.0]], jnp.float32)
    valid = jnp.ones(3, bool)
    for defense in ("norm_clip", "cosine_gate"):
        rows = apply_defense(defense, msg, valid, recv)
        cols = apply_defense(defense, msg.T, valid[None, :], recv.T, axis=0)
        for r, c in zip(rows[1:], cols[1:]):
            np.testing.assert_array_equal(np.asarray(r), np.asarray(c)[0])
        np.testing.assert_array_equal(np.asarray(rows[0])[:2],
                                      np.asarray(cols[0]).T[:2])
        clean = apply_defense(defense, msg[:2], valid[:2], recv[:2])
        for r, c in zip(rows, clean):
            np.testing.assert_array_equal(np.asarray(r)[:2], np.asarray(c))


# ---------------------------------------------------------------------------
# fault-free bitwise identity (the k_fault no-consumption contract, end
# to end) + cross-engine parity for every fault model
# ---------------------------------------------------------------------------


ENGINES = dict(
    dense=dict(engine="sharded", compact_mode="dense"),
    compact=dict(engine="sharded", compact_mode="compact"),
    compact_all=dict(engine="sharded", compact_mode="compact_all"),
    pallas=dict(engine="sharded", use_pallas=True, interpret=True),
)


@pytest.mark.parametrize("wire", [None, "int8_sr", "ternary_ef"])
def test_fault_machinery_at_zero_frac_is_bitwise_invisible(wire):
    """Pin the acceptance bar: enabling the fault code path with an empty
    Byzantine set must reproduce the fault-free run BIT FOR BIT on every
    engine path — i.e. fault draws never consume from the pinned
    ``split(key, 4)`` sequence and all injection sites are no-ops."""
    X, y, Xt, yt = toy()
    kw = dict(cycles=20, eval_every=10, seed=3)
    base = small_cfg(drop_prob=0.5, delay_max_cycles=10,
                     online_fraction=0.9, wire_dtype=wire)
    armed = dataclasses.replace(base, fault_model="sign_flip",
                                byzantine_frac=0.0, defense="none")
    for name, ekw in [("ref", {})] + list(ENGINES.items()):
        off = run_simulation(base, X, y, Xt, yt, **kw, **ekw)
        on = run_simulation(armed, X, y, Xt, yt, **kw, **ekw)
        assert off.err_fresh == on.err_fresh, (wire, name)
        assert off.err_voted == on.err_voted, (wire, name)
        assert off.ef_residual_norm == on.ef_residual_norm, (wire, name)
        assert on.fault_stats == {"corrupted": 0, "gated": 0, "clipped": 0}
        assert off.fault_stats == on.fault_stats


@pytest.mark.parametrize("wire", [None, "int8", "int4"])
@pytest.mark.parametrize("fault", sorted(FAULT_MODELS))
def test_fault_bitwise_parity_all_engines(fault, wire):
    """Acceptance bar for every fault model: for a fixed seed the error
    curves AND the fault counters agree bitwise across the reference
    engine, all three sharded packings and the Pallas interpret path —
    with the norm_clip screen active (the defended merge is part of the
    parity contract, not an afterthought)."""
    X, y, Xt, yt = toy()
    cfg = small_cfg(drop_prob=0.5, delay_max_cycles=10, online_fraction=0.9,
                    wire_dtype=wire, fault_model=fault, byzantine_frac=0.1,
                    defense="norm_clip")
    kw = dict(cycles=20, eval_every=10, seed=3)
    ref = run_simulation(cfg, X, y, Xt, yt, **kw)
    assert ref.fault_stats["corrupted"] > 0
    for name, ekw in ENGINES.items():
        r = run_simulation(cfg, X, y, Xt, yt, **kw, **ekw)
        assert ref.err_fresh == r.err_fresh, (fault, wire, name)
        assert ref.err_voted == r.err_voted, (fault, wire, name)
        assert ref.ef_residual_norm == r.ef_residual_norm, (fault, wire,
                                                           name)
        assert ref.fault_stats == r.fault_stats, (fault, wire, name)


def test_cosine_gate_parity_and_counters():
    X, y, Xt, yt = toy()
    cfg = small_cfg(drop_prob=0.5, delay_max_cycles=10, online_fraction=0.9,
                    fault_model="sign_flip", byzantine_frac=0.2,
                    defense="cosine_gate")
    kw = dict(cycles=20, eval_every=10, seed=3)
    ref = run_simulation(cfg, X, y, Xt, yt, **kw)
    assert ref.fault_stats["gated"] > 0 and ref.fault_stats["clipped"] == 0
    for name, ekw in ENGINES.items():
        r = run_simulation(cfg, X, y, Xt, yt, **kw, **ekw)
        assert ref.err_fresh == r.err_fresh, name
        assert ref.fault_stats == r.fault_stats, name


def test_faulty_run_is_reproducible():
    X, y, Xt, yt = toy(n=64)
    cfg = small_cfg(n_nodes=64, drop_prob=0.3, delay_max_cycles=4,
                    fault_model="random_payload", byzantine_frac=0.25,
                    defense="norm_clip")
    kw = dict(cycles=16, eval_every=8, seed=9, engine="sharded")
    a = run_simulation(cfg, X, y, Xt, yt, **kw)
    b = run_simulation(cfg, X, y, Xt, yt, **kw)
    assert a.err_fresh == b.err_fresh and a.fault_stats == b.fault_stats


# ---------------------------------------------------------------------------
# the defense actually defends (poisoned-cache property)
# ---------------------------------------------------------------------------


def test_norm_clip_bounds_poisoned_cache_votes():
    """VOTEDPREDICT poisoned-cache property, at the real defense site
    (``apply_receives``): one merge round carries a huge anti-aligned
    payload. Undefended, that payload dominates every later merge — the
    cache fills with sign-reversed models and the majority vote flips.
    With the per-round norm_clip screen the poison enters norm-bounded,
    the honest rounds re-dominate, and the voted predictions track the
    poison-free chain far more closely."""
    from repro.core.cache import init_cache
    from repro.core.learners import make_update
    from repro.core.simulation import apply_receives

    rng = np.random.default_rng(6)
    n, d, m, K = 8, 8, 64, 4
    w_star = rng.normal(size=d).astype(np.float32)
    X = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.sign(x @ jnp.asarray(w_star))
    last_w = jnp.asarray(w_star + 0.05 * rng.normal(size=(n, d)),
                         jnp.float32)
    last_t = jnp.full((n,), 20, jnp.int32)
    honest = jnp.asarray(
        w_star + 0.05 * rng.normal(size=(K, n, d)), jnp.float32)
    dirty = honest.at[1].set(-200.0 * honest[1])   # round-2 poison
    msg_t = jnp.full((K, n), 20, jnp.int32)
    valid = jnp.ones((K, n), bool)
    upd = make_update("pegasos", lam=0.01)

    def chain(msg_w, defense):
        lw, lt, cache, gated, clipped = apply_receives(
            last_w, last_t, init_cache(n, K, d), msg_w, msg_t, valid, x, y,
            variant="mu", update=upd, defense=defense)
        return np.asarray(voted_predict(cache, X)), clipped

    votes_clean, _ = chain(honest, "none")
    votes_dirty, cl_none = chain(dirty, "none")
    votes_defended, cl_clip = chain(dirty, "norm_clip")
    assert not np.asarray(cl_none).any() and np.asarray(cl_clip).any()
    agree_dirty = (votes_dirty == votes_clean).mean()
    agree_defended = (votes_defended == votes_clean).mean()
    # voting itself absorbs part of the attack (the poison touches 2 of K
    # cache slots: its own round's merge and the next round's lastModel),
    # so the defended gain is a margin, not a rescue from zero — measured
    # 0.78 vs 0.54 agreement on this fixed seed, pinned with slack
    assert agree_defended > agree_dirty + 0.1, (agree_dirty, agree_defended)


def test_norm_clip_recovers_voted_error_end_to_end():
    """The acceptance property at toy scale (the N=10^4 version lives in
    BENCH_robustness.json): under a 30% sign-flip attack on the extreme
    scenario, the undefended voted error collapses while norm_clip holds
    it near the fault-free level."""
    X, y, Xt, yt = toy()
    kw = dict(cycles=40, eval_every=20, seed=3)
    base = small_cfg(drop_prob=0.5, delay_max_cycles=10,
                     online_fraction=0.9, fault_model="sign_flip",
                     byzantine_frac=0.3)
    none = run_simulation(base, X, y, Xt, yt, **kw)
    clip = run_simulation(dataclasses.replace(base, defense="norm_clip"),
                          X, y, Xt, yt, **kw)
    # measured on this seed: voted 0.334 undefended vs 0.153 defended
    assert clip.err_voted[-1] + 0.1 < none.err_voted[-1], (
        none.err_voted[-1], clip.err_voted[-1])
    assert clip.err_fresh[-1] + 0.1 < none.err_fresh[-1]


def test_fault_stats_scale_with_byzantine_frac():
    X, y, Xt, yt = toy(n=64)
    kw = dict(cycles=10, eval_every=10, seed=1, engine="sharded")
    lo = run_simulation(small_cfg(n_nodes=64, fault_model="amplify",
                                  byzantine_frac=0.1, defense="norm_clip"),
                        X, y, Xt, yt, **kw)
    hi = run_simulation(small_cfg(n_nodes=64, fault_model="amplify",
                                  byzantine_frac=0.4, defense="norm_clip"),
                        X, y, Xt, yt, **kw)
    assert hi.fault_stats["corrupted"] > lo.fault_stats["corrupted"] > 0
    assert hi.fault_stats["clipped"] >= lo.fault_stats["clipped"] > 0
