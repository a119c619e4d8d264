"""Random weights of a configuration's model, made on the device from the seed.

The configuration's family (``slobench/configs/<family>.py``) gives the table
of tensors to draw, ``shapes(conf)``: name -> (shape, kind, std). One jitted
call draws every tensor in bfloat16 (the type it is served in), each from
the seed's key folded in with its rank in the table's sorted names:

* ``"matrix"``: normal(0, std);
* ``"gain"``: an RMSNorm gain, 1 + normal(0, std) clipped to [0.5, 1.9],
  where ``g - 1`` is exact in bfloat16. The call also returns each gain's
  offset from one, which the engine holds in its place, so that both
  views compute with the same numbers.

``make`` returns two views of the same matrices: ``w``, the benchmark's own
naming, which the plain reference reads, and the tree the serving engine
loads, which the family's ``engine_params`` makes of ``w`` and the offsets.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from slobench import spec


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (wider than 32 bits
    too), by way of numpy's SeedSequence."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def make(conf: dict, seed: int):
    """``(w, params)`` for the configuration file ``conf`` (see the module
    docstring), made in one jitted call on the default device."""
    fam = spec.family(conf)
    table = fam.shapes(conf)

    @jax.jit
    def draw(key):
        w = {}
        for i, (name, (shape, kind, std)) in enumerate(sorted(table.items())):
            k = jax.random.fold_in(key, i)
            if kind == "matrix":
                w[name] = (jax.random.normal(k, shape, jnp.float32)
                           * std).astype(jnp.bfloat16)
            elif kind == "gain":
                g = 1.0 + std * jax.random.normal(k, shape, jnp.float32)
                w[name] = jnp.clip(g, 0.5, 1.9).astype(jnp.bfloat16)
            else:
                raise ValueError(f"{name}: no draw of kind {kind!r}")
        offsets = {n: w[n] - jnp.bfloat16(1.0)
                   for n, (_, kind, _) in table.items() if kind == "gain"}
        return w, offsets

    w, off = draw(seed_key(seed))
    return w, fam.engine_params(conf, w, off)


def check_layout(cfg, params) -> None:
    """Raise unless ``params`` has exactly the engine's parameter tree
    (names, shapes and dtypes of ``repro.models.model.init_params``)."""
    from repro.models.model import init_params
    want = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the engine's "
                         "parameter tree")
