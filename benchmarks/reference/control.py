"""The control's precision: the step below the configurations' bfloat16.

``fake_quant(x, "fp8")`` rounds a tensor to float8 (e4m3) with one scale per
tensor and passes the gradient straight through, so a reference whose matrix
products and convolutions take their operands through it computes what an
8-bit path would. ``None`` leaves the tensor alone (the float32 reference).
"""

import jax
import jax.numpy as jnp


def fake_quant(x, kind):
    if kind is None:
        return x
    if kind != "fp8":
        raise ValueError("unknown control precision {!r}".format(kind))
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(rounded - x)
