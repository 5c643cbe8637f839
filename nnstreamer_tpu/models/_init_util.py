"""Compiled parameter initialization for zoo models.

Flax ``model.init`` run eagerly dispatches every RNG/reshape/conv op to the
default device one by one — hundreds of tiny programs for a MobileNet.
``host_init`` compiles the whole init as ONE program instead (and the
persistent compilation cache keeps it across processes).

The program runs on the process's default device: parameters are born
where models are served, on whichever backend this process has — there is
no second backend that has to be alive beside it.  An element that serves
from another device (``accelerator=`` pin, a mesh) moves them once with
``jax.device_put`` when it opens.
"""

from __future__ import annotations

from typing import Any


def host_init(init_fn, seed: int, *dummies: Any) -> Any:
    """Run a flax ``init`` as one compiled program.

    ``init_fn(rng, *dummies)`` is jitted with the PRNG key constructed
    *inside* the program (``jax.random.PRNGKey`` run eagerly is itself a
    device dispatch).  ``dummies`` are host values (numpy arrays /
    ShapeDtypeStructs); only their shapes matter.
    """
    import jax

    return jax.jit(
        lambda *xs: init_fn(jax.random.PRNGKey(seed), *xs)
    )(*dummies)
