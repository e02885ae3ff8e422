"""Faults planted under the timed path, to show that the comparison fails
them. Each is a patch for ``harness.Run(patches=[...])``: called with the
run once the engine is built, it swaps one program function for a broken
wrapper and returns the callable that puts the original back.

* ``unchanged_state``: the flush returns x, x-hat and the momentum as it
  got them (its broadcast still goes out);
* ``half_batch``: the flush aggregates only the first half of the K
  uploads, their weights doubled, i.e. the mean over the rest;
* ``altered_answer``: every member's bucket norms are doubled where the
  client step produces them (the codes stay), so every checked upload
  carries a wrong answer;
* ``trains_from_version0``: clients train from the x-hat of version 0,
  whatever the version they fetched.

They are used by ``bench/selftest.py`` on the CPU and by
``bench/control.py`` on the chip; a benchmark run never plants one.
"""
from __future__ import annotations


def _swap(module, name, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    return lambda: setattr(module, name, orig)


def _flush_patch(make):
    from repro.kernels import ops
    return _swap(ops, "server_flush_step", make)


def _step_patch(make):
    from repro.kernels import ops
    return _swap(ops, "cohort_train_encode_step", make)


def unchanged_state(run):
    import jax.numpy as jnp

    def make(orig):
        def flush(x, h, m, *args, **kw):
            kept = tuple(jnp.array(v, copy=True) for v in (x, h, m))
            return kept + tuple(orig(x, h, m, *args, **kw)[3:])
        return flush
    return _flush_patch(make)


def half_batch(run):
    def make(orig):
        def flush(x, h, m, stack, norms, weights, *args, **kw):
            if stack is not None:
                half = stack.shape[0] // 2
                stack, norms = stack[:half], norms[:half]
                weights = weights[:half] * (weights.shape[0] / half)
            return orig(x, h, m, stack, norms, weights, *args, **kw)
        return flush
    return _flush_patch(make)


def altered_answer(run):
    def make(orig):
        def step(*args, **kw):
            out = dict(orig(*args, **kw))
            out["norms"] = out["norms"] * 2.0
            return out
        return step
    return _step_patch(make)


def trains_from_version0(run):
    import jax.numpy as jnp
    first = []

    def make(orig):
        def step(loss_fn, qcfg, spec, layout, hidden_flat, *args, **kw):
            if not first:
                first.append(jnp.array(hidden_flat, copy=True))
            return orig(loss_fn, qcfg, spec, layout, first[0], *args, **kw)
        return step
    return _step_patch(make)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_answer": altered_answer,
          "trains_from_version0": trains_from_version0}
