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


def no_exchange(config: dict, real):
    """The exchange between chips left out: each device runs the rows of
    a split grid that it holds alone, as a grid of its own, so no halo
    crosses from a neighbour. A grid on one device runs as it would."""
    def run(x, spec, n_steps, aux=None, n_devices=None, devices=None,
            **kw):
        if not n_devices or n_devices == 1:
            return real(x, spec, n_steps, aux=aux, **kw)

        def local(a):
            return {sh.device: sh.data for sh in a.addressable_shards}
        auxes = {k: local(v) for k, v in (aux or {}).items()}
        pieces = [jax.device_put(
            real(data, spec, n_steps,
                 aux={k: v[dev] for k, v in auxes.items()} or None, **kw),
            dev) for dev, data in local(x).items()]
        return jax.make_array_from_single_device_arrays(x.shape, x.sharding,
                                                        pieces)
    return run
