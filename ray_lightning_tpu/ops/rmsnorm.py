"""RMSNorm: pallas-fused forward on TPU (fp32 accumulation, one HBM
round-trip), reference-formula backward via recompute (XLA fuses it into the
surrounding backward matmuls). jnp fallback elsewhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rmsnorm_ref(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * weight.astype(jnp.float32)).astype(dtype)


def _rmsnorm_pallas(x, weight, eps: float, block_rows: int = 256):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    orig_shape = x.shape
    dim = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, dim)
    block_rows = min(block_rows, rows)

    def kernel(x_ref, w_ref, o_ref):
        xf = x_ref[:].astype(jnp.float32)
        scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        o_ref[:] = (xf * scale * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, dim), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((dim,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (block_rows, dim), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((rows, dim), x.dtype),
        name="rmsnorm",
    )(x2, weight)
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_tpu(x, weight, eps):
    return _rmsnorm_pallas(x, weight, eps)


def _rmsnorm_tpu_fwd(x, weight, eps):
    return _rmsnorm_pallas(x, weight, eps), (x, weight)


def _rmsnorm_tpu_bwd(eps, residuals, g):
    x, weight = residuals
    _, vjp = jax.vjp(lambda a, w: _rmsnorm_ref(a, w, eps), x, weight)
    return vjp(g)


_rmsnorm_tpu.defvjp(_rmsnorm_tpu_fwd, _rmsnorm_tpu_bwd)


def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    platform = jax.devices()[0].platform
    if platform == "tpu" and x.shape[-1] % 128 == 0:
        return _rmsnorm_tpu(x, weight, eps)
    return _rmsnorm_ref(x, weight, eps)
