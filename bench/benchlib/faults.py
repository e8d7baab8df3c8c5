"""Faults planted under the timed path, to show that ``correct`` reads
false when the served answers are wrong.  Each fault wraps the program's
step factories in ``repro.train.steps`` (the clip step and the slab step
the service jits); ``plant(name, setattr)`` installs one through a
``setattr`` such as pytest's ``monkeypatch.setattr``.  Only
``bench/readings.py --fault`` and the tests plant them.

- ``answer``: one answer altered where it is produced: the logits of the
  first row (slot) rolled by one class.
- ``state``: the slab step returns the state it was given.
- ``half``: half of the batch left out: the first half of the rows (slots)
  answered, and those answers given for the other half too.
"""
from __future__ import annotations


def _answer(step):
    import jax.numpy as jnp

    def broken(*a, **kw):
        out = step(*a, **kw)
        logits = out[1] if isinstance(out, tuple) else out
        logits = logits.at[0].set(jnp.roll(logits[0], 1))
        return (out[0], logits) if isinstance(out, tuple) else logits
    return broken


def _state(step):
    def broken(plans, slabs, *a, **kw):
        _, logits = step(plans, slabs, *a, **kw)
        return slabs, logits
    return broken


def _half_clip(step):
    import jax.numpy as jnp

    def broken(plans, x):
        half = step(plans, x[: x.shape[0] // 2])
        return jnp.concatenate([half, half])
    return broken


def _half_slab(step):
    import jax.numpy as jnp

    def broken(*a, **kw):
        slabs, logits = step(*a, **kw)
        h = logits.shape[0] // 2
        return slabs, jnp.concatenate([logits[:h], logits[:h],
                                       logits[2 * h:]])
    return broken


# fault -> {step factory name: wrapper}
FAULTS = {
    "answer": {"make_gcn_infer_step": _answer, "make_gcn_slab_step": _answer},
    "state": {"make_gcn_slab_step": _state},
    "half": {"make_gcn_infer_step": _half_clip,
             "make_gcn_slab_step": _half_slab},
}


def plant(name: str, setattr) -> None:
    """Wrap the step factories that fault ``name`` breaks."""
    import repro.train.steps as steps

    for factory, wrap in FAULTS[name].items():
        make = getattr(steps, factory)

        def broken_factory(cfg, make=make, wrap=wrap):
            return wrap(make(cfg))
        setattr(steps, factory, broken_factory)
