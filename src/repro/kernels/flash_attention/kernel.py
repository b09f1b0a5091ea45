"""Pallas TPU flash attention (forward).

Canonical TPU tiling: grid = (batch*q_heads, q_blocks, kv_blocks) with the
kv axis innermost.  Each (bh, qi) output tile is revisited across kv steps
while online-softmax statistics (running max m, normalizer l) and the
accumulator live in VMEM scratch; the final kv step rescales and writes.

Block shapes are MXU-aligned (q_block x d and kv_block x d tiles with d a
multiple of 128 ideally; q/kv blocks multiples of the 8-sublane tile).
GQA is expressed through the k/v BlockSpec index maps (q-head h reads kv
head h // group) — no materialized head repetition, which is the memory
win vs the naive einsum on TPU.

Supports: causal masking (end-aligned), sliding window, Gemma-2 logit
softcap.  Sliding-window + causal skips fully-masked kv blocks by clamping
work to the masked band (the index maps still visit them; the @pl.when
guard makes them cheap).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int | None,
                  softcap: float, sq: int, skv: int, block_q: int,
                  block_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions of this tile's queries/keys, end-aligned on the
    # ORIGINAL (unpadded) lengths: real query i sits at i + (skv - sq);
    # padded queries land past the end (harmless, sliced off), padded keys
    # are masked by the validity test below.
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0) \
        + (skv - sq)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)

    needed = jnp.bool_(True)
    if causal:
        # tile participates iff some key <= some query
        needed &= (ki * block_kv) <= (qi * block_q + (skv - sq) + block_q - 1)
    if window is not None:
        first_valid = qi * block_q + (skv - sq) - window + 1
        needed &= (ki + 1) * block_kv - 1 >= first_valid
    needed &= (ki * block_kv) < skv  # tile of pure padding keys

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (block_kv, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = k_pos < skv  # padded keys are never valid
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                        # (block_q, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)        # fully-masked rows -> 0
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float = 0.0, scale: float | None = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool):
    """Flash attention forward.

    Args:
        q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D), Hq % Hkv == 0.
        block_q / block_kv: VMEM tile sizes (MXU-aligned multiples of 8/128).
        interpret: run the kernel body in Python on CPU (validation mode).

    Returns:
        (B, Hq, Sq, D), dtype of q.
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = d ** -0.5 if scale is None else scale

    # pad sequence dims to block multiples (end-aligned causal stays valid
    # because padding keys are masked by position comparisons)
    pq = -sq % block_q
    pkv = -skv % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0)))

    grid = (b * hq, (sq + pq) // block_q, (skv + pkv) // block_kv)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, sq=sq, skv=skv,
        block_q=block_q, block_kv=block_kv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bh, qi, ki: (bh // hq, bh % hq, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bh, qi, ki: (bh // hq, (bh % hq) // group, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bh, qi, ki: (bh // hq, (bh % hq) // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bh, qi, ki: (bh // hq, bh % hq, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq + pq, d), q.dtype),
        scratch_shapes=[
            _vmem((block_q, 1)),
            _vmem((block_q, 1)),
            _vmem((block_q, d)),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out[:, :, :sq, :]


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)
