"""Layer-B gossip optimizer: semantics + convergence + mesh runtime."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import GossipConfig
from repro.core.gossip_optimizer import (GossipState, gossip_merge,
                                         linear_gossip_mesh_step,
                                         make_gossip_train_step,
                                         peer_disagreement, perms_for_step,
                                         stack_for_peers, unstack_mean)
from repro.optim import constant, make_optimizer


def quad_loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    l = jnp.mean((pred - batch["y"]) ** 2)
    return l, {}


def _run(merge, steps=60, n_peers=8, schedule="hypercube", lr=0.1, seed=0):
    key = jax.random.key(seed)
    w_true = jax.random.normal(key, (12,))
    params = {"w": jnp.zeros((12,)), "b": jnp.zeros(())}
    sp = stack_for_peers(params, n_peers)
    opt = make_optimizer("sgd", constant(lr), grad_clip=0)
    gc = GossipConfig(schedule=schedule, merge=merge)
    # perm is a static (compile-time) partner schedule — see gossip_merge
    fn = jax.jit(make_gossip_train_step(quad_loss, opt, n_peers, gc),
                 static_argnums=(2, 3))
    state = GossipState(sp, opt.init(sp), jnp.zeros((), jnp.int32))
    loss = None
    for s in range(steps):
        k = jax.random.fold_in(key, s)
        x = jax.random.normal(k, (n_peers, 16, 12))
        batch = {"x": x, "y": x @ w_true}
        perm, _ = perms_for_step(gc, s, n_peers)
        state, loss, _ = fn(state, batch, tuple(int(x) for x in perm))
    return state, float(loss), w_true


@pytest.mark.parametrize("merge", ["mu", "um"])
def test_gossip_converges_with_low_disagreement(merge):
    state, loss, w_true = _run(merge)
    assert loss < 1e-3
    assert float(peer_disagreement(state.params)) < 1e-2
    err = float(jnp.linalg.norm(unstack_mean(state.params)["w"] - w_true))
    assert err < 0.05


def test_rw_diverges_across_peers_more_than_mu():
    """No merging (RW) leaves peers on independent SGD paths — disagreement
    must exceed the gossiped run's (the paper's merging argument)."""
    st_mu, _, _ = _run("mu", steps=30)
    st_rw, _, _ = _run("rw", steps=30)
    assert float(peer_disagreement(st_rw.params)) > \
        float(peer_disagreement(st_mu.params))


def test_gossip_merge_is_pairwise_average():
    params = {"w": jnp.arange(8.0)[:, None] * jnp.ones((8, 3))}
    perm = (1, 0, 3, 2, 5, 4, 7, 6)
    merged = gossip_merge(params, perm)
    expect0 = (0.0 + 1.0) / 2
    np.testing.assert_allclose(np.asarray(merged["w"][0]), expect0, rtol=1e-6)
    # total mass conserved (pairwise averaging is doubly stochastic)
    np.testing.assert_allclose(float(merged["w"].sum()),
                               float(params["w"].sum()), rtol=1e-6)


def test_perms_for_step_pod_schedule():
    gc = GossipConfig(pod_every=2)
    perm, pod = perms_for_step(gc, 0, 8, n_pods=2)
    assert pod is None                        # step 0: (0+1) % 2 != 0
    perm, pod = perms_for_step(gc, 1, 8, n_pods=2)
    assert pod is not None
    pod = np.asarray(pod)
    assert np.all(pod[pod] == np.arange(8))   # cross-pod pairing is involutive
    assert np.all((pod >= 4) == (np.arange(8) < 4))  # pairs across pods


def test_linear_gossip_mesh_step_shard_map():
    """The paper's protocol with peers = mesh devices via shard_map."""
    from jax.sharding import Mesh, PartitionSpec as PS
    from jax import shard_map
    import functools

    mesh = jax.make_mesh((1,), ("data",))
    d = 8
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(1, 4, d)), jnp.float32)
    y = jnp.ones((1, 4), jnp.float32)
    w0 = jnp.zeros((1, d))
    t0 = jnp.zeros((1,), jnp.int32)

    def per_device(w, t, X_l, y_l):
        # strip the local peer dim of size 1, run the protocol step, restore
        w2, t2 = linear_gossip_mesh_step(w[0], t[0], X_l[0], y_l[0],
                                         [(0, 0)], lam=1e-2, variant="mu",
                                         axis="data")
        return w2[None], t2[None]

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(PS("data"), PS("data"), PS("data"), PS("data")),
                   out_specs=(PS("data"), PS("data")))
    w, t = fn(w0, t0, X, y)
    assert w.shape == (1, d)
    assert int(t[0]) == 1
    assert bool(jnp.isfinite(w).all())
