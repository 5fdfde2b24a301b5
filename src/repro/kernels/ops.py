"""Jit'd public wrappers around the Pallas kernels.

``flash_attention`` matches ``repro.models.layers.attention``'s calling
convention ((B,S,H,hd) GQA layout + position arrays) so the model can
select ``attn_impl="pallas"``. On a TPU the kernels are compiled by
Mosaic; on the CPU backend, where the tests run, they run in the Pallas
interpreter (same kernel code, no lowering). There is no fallback to
interpret mode or to the reference on any other backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rn


def _interpret() -> bool:
    """Interpret only on the CPU backend; read at trace time."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_q",
                                    "block_kv"))
def flash_attention(q, k, v, q_pos=None, k_pos=None, *, causal=True,
                    window=None, block_q=128, block_kv=128):
    """q: (B,Sq,H,hd); k,v: (B,Sk,KH,hd) GQA. Positions must be
    contiguous 0..S-1 (the kernel derives them from block indices)."""
    b, sq, h, hd = q.shape
    kh = k.shape[2]
    n_rep = h // kh
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    qb = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kb = k.transpose(0, 2, 1, 3).reshape(b * h, -1, hd)
    vb = v.transpose(0, 2, 1, 3).reshape(b * h, -1, hd)
    ob = fa.flash_attention_bh(qb, kb, vb, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv,
                               interpret=_interpret())
    return ob.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, scale, eps=1e-6, block_rows=128):
    return rn.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                      interpret=_interpret())
