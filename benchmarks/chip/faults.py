"""Faults planted underneath the timed path, to show that the check refuses
them: each patches one function of the program under test in this process,
before the program builds its compiled steps, and ``undo`` puts it back.

* ``frozen`` — the receive step returns its state unchanged;
* ``half`` — the receive step leaves out the second half of the population
  (its messages are taken as never received);
* ``answer`` — the served vote flips the answer of the first query of
  every batch where it is produced;
* ``eval`` — the eval points score the test rows in reverse order against
  their labels (the wrong rows);
* ``ring`` — on a node mesh, the message gather's ring leaves out the
  exchange between chips: each block stays on the chip that made it.
"""
from __future__ import annotations

FAULTS = ("frozen", "half", "answer", "eval", "ring")


def plant(name: str):
    """Patch the program for fault ``name``; returns ``undo``."""
    import jax
    import jax.numpy as jnp

    from repro.core import cache, serving, sharded_engine, simulation
    from repro.kernels import gossip_cycle

    def rebuild():
        # the one-chip eval is jitted on its own and keeps its first trace
        sharded_engine._build_chunk_fn.cache_clear()
        simulation._eval.clear_cache()

    def patch(owner, attrs):
        """Set ``attrs`` on ``owner`` until undone; the compiled steps are
        built anew on both sides."""
        saved = {a: getattr(owner, a) for a in attrs}
        for a, fn in attrs.items():
            setattr(owner, a, fn)
        rebuild()

        def undo():
            for a, fn in saved.items():
                setattr(owner, a, fn)
            rebuild()
        return undo

    if name == "eval":
        fresh, voted = cache.predict_fresh, cache.voted_predict
        return patch(cache, dict(
            predict_fresh=lambda c, X: fresh(c, X[::-1]),
            voted_predict=lambda c, X: voted(c, X[::-1])))
    if name == "ring":
        return patch(jax.lax, dict(ppermute=lambda x, axis_name, perm: x))
    if name in ("frozen", "half"):
        real = gossip_cycle.fused_receive_apply

        def receive(last_w, last_t, fresh_w, fresh_t, cache_w, cache_t, ptr,
                    count, msg_w, msg_t, valid, x, y, **kw):
            if name == "frozen":
                z = jnp.zeros_like(last_t)
                return (last_w, last_t, fresh_w, fresh_t, cache_w, cache_t,
                        ptr, count, z, z)
            keep = jnp.arange(valid.shape[1]) < valid.shape[1] // 2
            return real(last_w, last_t, fresh_w, fresh_t, cache_w, cache_t,
                        ptr, count, msg_w, msg_t, valid * keep[None, :], x,
                        y, **kw)

        return patch(gossip_cycle, dict(fused_receive_apply=receive))
    if name == "answer":
        real_vote = serving.serve_voted_kernel

        def vote(w, count, X, assign, **kw):
            out = real_vote(w, count, X, assign, **kw)
            return out.at[0].multiply(-1.0)

        return patch(serving, dict(serve_voted_kernel=vote))
    raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
