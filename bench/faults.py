"""Faults planted in the timed path, for the check of the checks.

``faulty(kind)`` returns a service factory for ``run.run_cell`` whose round
is broken in one way a later change could break it:

* ``stale_state``       - a step that returns its state unchanged (the
                          metrics of the round are still computed and
                          returned);
* ``half_participants`` - half of the round's participants left out, Eq. 4
                          taking the mean over the rest;
* ``half_minibatch``    - local SGD on half of each minibatch, the loss the
                          mean over that half (the other half's samples are
                          replaced by the first half's, so the step's shapes
                          stay as they are);
* ``altered``           - an answer altered where it is produced: the round
                          time is returned 0.1% high.

A configuration without FL has no participants or minibatches, so only
``stale_state`` and ``altered`` apply to it (``kinds``). A cell on one chip
has no exchange between chips to leave out.
"""
from service import Service

KINDS = ("stale_state", "half_participants", "half_minibatch", "altered")
FL_KINDS = ("half_participants", "half_minibatch")


def kinds(cfg):
    """The faults that a cell of configuration ``cfg`` can have."""
    return tuple(k for k in KINDS if cfg["model"] is not None or k not in FL_KINDS)


def faulty(kind):
    if kind not in KINDS:
        raise ValueError(f"fault {kind!r}: one of {KINDS}")

    class Faulty(Service):
        def call(self, state, keys, plan):
            import jax
            import jax.numpy as jnp

            if kind == "half_participants":
                p = plan["valid"].shape[-1]
                plan = dict(plan, valid=plan["valid"] & (jnp.arange(p) < p // 2))
            if kind == "half_minibatch":
                b = plan["batch"]
                half = b[..., : b.shape[-1] // 2]
                plan = dict(plan, batch=jnp.concatenate([half, half], axis=-1))
            if kind == "stale_state":
                keep = jax.tree_util.tree_map(jnp.copy, state)
                _, metrics = super().call(state, keys, plan)
                return keep, metrics
            state, metrics = super().call(state, keys, plan)
            if kind == "altered":
                metrics = dict(metrics, round_time=metrics["round_time"] * 1.001)
            return state, metrics

    return Faulty
