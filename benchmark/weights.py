"""Reference weights from the seed, made without the program.

The program initialises its zoo models with flax: every parameter's random
key is folded from the root key by the parameter's PATH (module names, then
the parameter's name), and its values come from the layer's initializer.  A
reference states that recipe as data, a nested spec of ``name -> Leaf(shape,
init)`` in creation order, and ``init_params`` walks it with a flax module
that holds nothing but names.  Same seed, same paths, same initializers: the
same float32 weights, and nothing taken from the program (a test under
``benchmark/tests`` pins the equality at a tiny size).

The seed is an ARGUMENT of the one jitted program, so every seed hits the
same compile-cache entry.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    shape: Tuple[int, ...]
    init: str


INITS = {
    "zeros": nn.initializers.zeros,
    "ones": nn.initializers.ones,
    "lecun_normal": nn.initializers.lecun_normal(),          # Dense, Conv kernels
    "normal0.02": nn.initializers.normal(0.02),
    "embed": nn.initializers.variance_scaling(               # nn.Embed default
        1.0, "fan_in", "normal", out_axis=0),
}


class _Twin(nn.Module):
    spec: Any  # tuple of (name, Leaf | nested tuple)

    @nn.compact
    def __call__(self):
        for name, sub in self.spec:
            if isinstance(sub, Leaf):
                self.param(name, INITS[sub.init], sub.shape)
            else:
                _Twin(sub, name=name)()


def layer_norm(d):
    return (("scale", Leaf((d,), "ones")), ("bias", Leaf((d,), "zeros")))


def dense(d_in, d_out, bias=False):
    spec = (("kernel", Leaf((d_in, d_out), "lecun_normal")),)
    return spec + ((("bias", Leaf((d_out,), "zeros")),) if bias else ())


def init_params(spec, seed):
    """float32 parameter tree ``{"params": ...}`` on the default device."""
    twin = _Twin(spec)
    fn = jax.jit(lambda s: twin.init(jax.random.PRNGKey(s)))
    return fn(jnp.int32(seed))


# ---------------------------------------------------------------------------
# the control's arithmetic: fp8 (e4m3) in place of bf16
# ---------------------------------------------------------------------------
def fp8_round(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis`` (the
    dynamic per-row / per-channel scaling a quantized deployment uses)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(x, w, precision):
    """``x @ w`` in the reference's float32 ('f32') or with both operands
    rounded to fp8 first ('fp8': the control)."""
    if precision == "fp8":
        x, w = fp8_round(x, -1), fp8_round(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
