"""Faults planted under the timed path, to show that ``correct`` catches
them: each breaks one part of a built ``harness.Run`` and returns a
function that undoes it. Used by the CPU tests and by
``check_readings.py --faults``; a benchmark run plants none."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def swap(obj, attr, wrap):
    old = getattr(obj, attr)
    setattr(obj, attr, wrap(old))
    return lambda: setattr(obj, attr, old)


def alter_tokens(run):
    """Tier 1's decode step emits the next id instead of its pick."""
    rec = run.tiers[1].engine._decode
    vocab = run.tiers[1].spec["model"]["vocab_size"]
    return swap(rec, "fn", lambda fn: lambda *a: (
        lambda o: ((o[0] + 1) % vocab,) + tuple(o[1:]))(fn(*a)))


def freeze_kv(run):
    """Tier 1's decode step hands back its KV pools as they came in."""
    rec = run.tiers[1].engine._decode

    def wrap(fn):
        def step(*a):
            kp, vp = jnp.copy(a[1]), jnp.copy(a[2])
            o = fn(*a)
            return (o[0], o[1], kp, vp, o[4])
        return step
    return swap(rec, "fn", wrap)


def freeze_state(run):
    """Tier 0's decode step hands back its recurrent state as it came in."""
    rec = run.tiers[0].engine._decode

    def wrap(fn):
        def step(*a):
            keep = jax.tree_util.tree_map(jnp.copy, a[3])
            o = fn(*a)
            return tuple(o[:4]) + (keep,)
        return step
    return swap(rec, "fn", wrap)


def flip_routes(run):
    """The policy sends every request to the other tier."""
    return swap(run.policy, "decide", lambda fn: lambda *a: (
        lambda t, s: (1 - np.asarray(t), s))(*fn(*a)))


FAULTS = {f.__name__: f for f in (alter_tokens, freeze_kv, freeze_state,
                                  flip_routes)}
