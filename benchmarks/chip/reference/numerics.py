"""Plain float32 building blocks of the references, and the lower-precision
arithmetic of their controls.

``precision`` selects how every matrix product is computed:

- ``"f32"``: float32 at ``Precision.HIGHEST`` (the reference);
- ``"bf16"``: inputs rounded to bfloat16, float32 accumulation (the control
  of a float32 configuration);
- ``"fp8"``: inputs scaled per tensor into float8 e4m3's range and rounded
  to it, float32 accumulation (the control of a bfloat16 configuration).

Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def rounded(x: jnp.ndarray, precision: str) -> jnp.ndarray:
    """``x`` as the control's arithmetic would hold it, back in float32."""
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
        q = (x / scale).astype(jnp.float8_e4m3fn)
        return q.astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def einsum(spec: str, a, b, precision: str = "f32") -> jnp.ndarray:
    return jnp.einsum(spec, rounded(a, precision), rounded(b, precision),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def matmul(a, b, precision: str = "f32") -> jnp.ndarray:
    return jnp.matmul(rounded(a, precision), rounded(b, precision),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def masked_logits(h, head, vocab, precision="f32"):
    """``h @ head`` over the stored (padded) vocabulary, the pad rows set to
    -inf."""
    logits = matmul(h, head, precision)
    cols = jnp.arange(logits.shape[-1])
    return jnp.where(cols < vocab, logits, -jnp.inf)
