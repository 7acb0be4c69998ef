"""Ways to break the timed path underneath a run, for the tests that see
``correct`` come out false. Each returns a stand-in for
``repro.kernels.ops.stencil_run``, the call both traffic loops reach."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference


def control(config: dict, real):
    """The reference one precision lower (bfloat16) in the program's
    place: the control the limits are set against."""
    st = config["stencil"]

    def one(x, src, n_steps):
        lo = jnp.bfloat16
        return reference.multistep(
            x.astype(lo), st, n_steps,
            None if src is None else src.astype(lo)).astype(x.dtype)

    def run(x, spec, n_steps, aux=None, **kw):
        src = None if not aux else next(iter(aux.values()))
        if x.ndim == st["dims"] + 1:
            return jax.vmap(lambda a, s: one(a, s, n_steps))(x, src)
        return one(x, src, n_steps)
    return run


def unchanged(config: dict, real):
    """A step that returns its state unchanged."""
    return lambda x, spec, n_steps, **kw: jnp.asarray(x)


def altered(config: dict, real):
    """One answer altered where it is produced: a cell off by 1%."""
    def run(x, spec, n_steps, **kw):
        y = real(x, spec, n_steps, **kw)
        return y.at[(0,) * y.ndim].multiply(1.01)
    return run


def half_batch(config: dict, real):
    """Half of each batch left out: the rows past the middle come back
    as they went in."""
    def run(x, spec, n_steps, **kw):
        y = real(x, spec, n_steps, **kw)
        if x.ndim != config["stencil"]["dims"] + 1 or x.shape[0] < 2:
            return y
        h = x.shape[0] // 2
        return jnp.concatenate([y[:h], jnp.asarray(x)[h:]])
    return run
