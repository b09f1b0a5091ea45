"""Gradient compression: int8 quantization with error feedback.

For cross-pod gradient reduction (the slow DCN hop on multi-pod meshes),
gradients quantize to int8 with a per-tensor absmax scale before the
reduction; the quantization residual accumulates in a local error-feedback
buffer added to the next step's gradient (Seide et al. 1-bit SGD / EF-SGD
semantics, which keeps SGD/Adam convergence).

``compressed_psum`` runs inside shard_map over the reduction axis.  The
arithmetic is exact int8 semantics; on CPU/XLA the reduction itself is
carried in int32 (XLA has no int8 ring all-reduce), so the *wire-byte*
saving (4x) is reported analytically via ``wire_bytes`` — on TPU the int8
payload is what crosses the DCN.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def ef_compress(grad, error_buf):
    """Error-feedback compression of one tensor.

    Returns (int8 payload, scale, new_error_buf)."""
    g = grad.astype(jnp.float32) + error_buf
    q, scale = quantize_int8(g)
    new_err = g - dequantize(q, scale)
    return q, scale, new_err


def compressed_psum(grads, error_bufs, axis_name: str):
    """Inside shard_map: EF-int8 compress + reduce over ``axis_name``.

    Returns (reduced_f32_grads, new_error_bufs).
    """
    def one(g, e):
        q, scale, new_e = ef_compress(g, e)
        # int32 carrier for the reduction (int8 payload on real DCN)
        total = jax.lax.psum(q.astype(jnp.int32), axis_name)
        scale_sum = jax.lax.psum(scale, axis_name)
        n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
        # per-shard scales differ; use the mean scale (standard EF-SGD
        # approximation — the residual lands in the error buffer)
        return total.astype(jnp.float32) * (scale_sum / n) / n, new_e

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(error_bufs)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (tdef.unflatten([o[0] for o in out]),
            tdef.unflatten([o[1] for o in out]))


def wire_bytes(grads, compressed: bool) -> float:
    """Analytic per-reduction wire bytes (ring all-reduce, 2x payload)."""
    n = sum(g.size for g in jax.tree.leaves(grads))
    return 2.0 * n * (1 if compressed else 4)


def make_dp_train_grads(loss_fn, mesh, axis_name: str = "data",
                        compress: bool = True):
    """Pure-DP gradient computation with EF-int8 cross-shard reduction.

    Returns grads_fn(params, batch, error_bufs) -> (loss, grads, bufs):
    the batch shards over ``axis_name`` via shard_map, each shard
    backprops its microbatch, and the reduction runs compressed.  Used by
    the multi-pod example and tests; the pjit train path keeps XLA-native
    reductions (this is the explicit-collective alternative for the
    cross-pod DCN hop).
    """
    def local(params, batch, error_bufs):
        # error buffers carry a leading device axis (sharded over
        # axis_name): strip it inside, restore it on the way out
        ebufs = jax.tree.map(lambda x: x[0], error_bufs)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if compress:
            grads, ebufs = compressed_psum(grads, ebufs, axis_name)
        else:
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis_name), grads)
        return (jax.lax.pmean(loss, axis_name), grads,
                jax.tree.map(lambda x: x[None], ebufs))

    def apply(params, batch, error_bufs):
        sm = jax.shard_map(
            local, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params),
                      jax.tree.map(lambda _: P(axis_name), batch),
                      jax.tree.map(lambda _: P(axis_name), error_bufs)),
            out_specs=(P(),
                       jax.tree.map(lambda _: P(), params),
                       jax.tree.map(lambda _: P(axis_name), error_bufs)),
            check_vma=False)
        return sm(params, batch, error_bufs)

    return apply


def init_error_bufs(params, n_shards: int):
    """Per-shard error-feedback buffers, leading axis = n_shards."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_shards,) + p.shape, jnp.float32), params)
