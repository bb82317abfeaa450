"""Faults planted under the timed path, to show that ``correct`` catches
them. Each wraps the program's step function before it is jitted; the
tests (``bench/tests``) and ``bench.calibrate`` pass them as hooks. The
benchmark's own runs never use them.

* ``state_unchanged``: the training step computes its loss but returns
  its parameters and optimizer state as they came in;
* ``half_batch``: the training step sees the first half of the batch
  twice, so its mean is taken over half the rows;
* ``token_altered``: the serving engine step adds one to the first token
  of every request it admits, in the state it returns.

The exchange between chips has no fault here: every cell runs on one
chip.
"""
from __future__ import annotations


def state_unchanged(step):
    def f(params, opt_state, tokens, labels):
        _, _, loss = step(params, opt_state, tokens, labels)
        return params, opt_state, loss

    return f


def half_batch(step):
    import jax.numpy as jnp

    def f(params, opt_state, tokens, labels):
        n = tokens.shape[0] // 2
        return step(params, opt_state,
                    jnp.concatenate([tokens[:n], tokens[:n]]),
                    jnp.concatenate([labels[:n], labels[:n]]))

    return f


def token_altered(vocab: int):
    import jax.numpy as jnp

    def wrap(step):
        def f(params, state, *arrivals):
            state, rep = step(params, state, *arrivals)
            buf = state.gen_buf
            bad = jnp.where(rep["admitted"], (buf[:, 0] + 1) % vocab,
                            buf[:, 0])
            return state._replace(gen_buf=buf.at[:, 0].set(bad)), rep

        return f

    return wrap


TRAIN = {"state_unchanged": state_unchanged, "half_batch": half_batch}
