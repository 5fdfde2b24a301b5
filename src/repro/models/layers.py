"""Core transformer layers, pure JAX.

All functions are shape-polymorphic and jit/pjit friendly; attention has
three implementations selectable via ``ModelOptions.attn_impl``:

  * ``naive``     — materializes (B,H,S,S) scores. Reference semantics.
  * ``flash_jnp`` — two-level lax.scan blockwise softmax (pure-JAX flash);
                    O(block_q x block_kv) live scores. Default for long S.
  * ``pallas``    — the Pallas TPU kernel in ``repro.kernels`` (train fwd).

Weights use Megatron-style logical axes so ``repro.parallel.sharding`` can
map them onto the mesh: q/k/v projections are column-parallel over heads,
the output projection is row-parallel, the MLP is column→row.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class ModelOptions:
    """Runtime (non-architectural) knobs."""
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "auto"          # auto | naive | flash_jnp | pallas
    block_q: int = 512
    block_kv: int = 1024
    remat: bool = True               # activation checkpointing per layer
    moe_impl: str = "gather"         # gather | dense_dispatch
    # sequence threshold above which "auto" switches naive → flash_jnp
    flash_threshold: int = 2048
    # Megatron-SP: PartitionSpec constraint applied to the residual stream
    # at layer boundaries (shards the scan carry → activation memory / mp)
    act_spec: object = None
    # attention-internal layout: (batch, seq, heads, hd) — heads over
    # `model` (the Megatron decomposition); forces the SP all-gather to
    # happen exactly once at the qkv projections
    qkv_spec: object = None
    # separate spec for K/V: GQA kv-head count may not divide the model
    # axis (then KV heads are replicated across the TP group)
    kv_spec: object = None
    # explicit expert parallelism (moe_impl="ep_a2a"): experts sharded
    # over `ep_axis`, tokens over `dp_axes` (+ seq over ep_axis)
    ep_axis: object = None
    dp_axes: object = None


def constrain(x: jax.Array, opts: "ModelOptions") -> jax.Array:
    if opts.act_spec is not None:
        return jax.lax.with_sharding_constraint(x, opts.act_spec)
    return x


def constrain_qkv(x: jax.Array, opts: "ModelOptions",
                  is_kv: bool = False) -> jax.Array:
    spec = opts.kv_spec if is_kv else opts.qkv_spec
    if spec is not None:
        return jax.lax.with_sharding_constraint(x, spec)
    return x


DEFAULT_OPTIONS = ModelOptions()


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dtype)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    sin = sin[..., :, None, :]                          # broadcast over heads
    cos = cos[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B,S,KH,hd) → (B,S,KH*n_rep,hd)."""
    if n_rep == 1:
        return k
    b, s, kh, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, n_rep, hd))
    return k.reshape(b, s, kh * n_rep, hd)


def _causal_window_mask(q_pos: jax.Array, k_pos: jax.Array,
                        causal: bool, window: Optional[int]) -> jax.Array:
    """Boolean mask (..., Q, K): True = attend."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]),
                 dtype=bool)
    d = q_pos[..., :, None] - k_pos[..., None, :]
    if causal:
        m &= d >= 0
    if window is not None:
        m &= d < window
    return m


def attention_naive(q, k, v, q_pos, k_pos, causal=True, window=None):
    """q: (B,Sq,H,hd), k/v: (B,Sk,KH,hd). Returns (B,Sq,H,hd)."""
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = _causal_window_mask(q_pos, k_pos, causal, window)   # (B,Q,K)
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _blockify(x, block, pad_value=0.0):
    """(B, S, ...) → (nblocks, B, block, ...)."""
    b, s = x.shape[:2]
    p = (-s) % block
    if p:
        pads = [(0, 0), (0, p)] + [(0, 0)] * (x.ndim - 2)
        x = jnp.pad(x, pads, constant_values=pad_value)
    n = x.shape[1] // block
    x = x.reshape((b, n, block) + x.shape[2:])
    return jnp.moveaxis(x, 1, 0)


def _match_vma(tree, ref):
    """Mark scan-carry inits device-varying to match a reference value's
    varying-manual-axes (required inside shard_map bodies)."""
    vma = tuple(getattr(jax.typeof(ref), "vma", ()))
    if not vma:
        return tree
    return jax.tree.map(lambda x: jax.lax.pcast(x, vma, to="varying"), tree)


def _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, window,
                    block_q, block_kv):
    """Returns (out (B,Sq,H,hd), lse (B,Sq,H)). KV already head-repeated."""
    b, sq, h, hd = q.shape
    scale = hd ** -0.5
    qb = _blockify(q, block_q)
    qposb = _blockify(q_pos, block_q, pad_value=-1)
    kb = _blockify(k, block_kv)
    vb = _blockify(v, block_kv)
    kposb = _blockify(k_pos, block_kv, pad_value=2 ** 30)

    def q_block(carry, qi):
        qblk, qpblk = qi                                 # (B,bq,H,hd),(B,bq)

        def kv_block(state, ki):
            m, l, acc = state
            kblk, vblk, kpblk = ki
            logits = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk,
                                preferred_element_type=jnp.float32) * scale
            msk = _causal_window_mask(qpblk, kpblk, causal, window)
            msk &= (kpblk < 2 ** 29)[:, None, :] & (qpblk >= 0)[:, :, None]
            logits = jnp.where(msk[:, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(qblk.dtype), vblk
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        init = _match_vma(
            (jnp.full((b, h, block_q), NEG_INF, jnp.float32),
             jnp.zeros((b, h, block_q), jnp.float32),
             jnp.zeros((b, h, block_q, hd), jnp.float32)), qblk)
        (m, l, acc), _ = lax.scan(kv_block, init, (kb, vb, kposb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))         # (B,H,bq)
        return carry, (out.transpose(0, 2, 1, 3).astype(q.dtype),
                       lse.transpose(0, 2, 1))           # (B,bq,H,*)

    _, (outs, lses) = lax.scan(q_block, None, (qb, qposb))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, -1, h, hd)[:, :sq]
    lse = jnp.moveaxis(lses, 0, 1).reshape(b, -1, h)[:, :sq]
    return out, lse


def _flash_bwd_impl(q, k, v, q_pos, k_pos, out, lse, dout, causal, window,
                    block_q, block_kv):
    """FlashAttention backward: blockwise recompute of p from (q,k,lse).
    Live memory O(block_q x block_kv) — no O(S²) residuals."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = hd ** -0.5
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                              # (B,Sq,H)

    qb = _blockify(q, block_q)
    qposb = _blockify(q_pos, block_q, pad_value=-1)
    lseb = _blockify(lse, block_q, pad_value=1.0)
    deltab = _blockify(delta, block_q)
    doutb = _blockify(dout, block_q)
    kb = _blockify(k, block_kv)
    vb = _blockify(v, block_kv)
    kposb = _blockify(k_pos, block_kv, pad_value=2 ** 30)
    nq = qb.shape[0]

    def kv_block(dq_acc, ki):
        kblk, vblk, kpblk = ki                            # (B,bkv,H,hd)

        def q_block(state, qi):
            dk, dv = state
            qblk, qpblk, lblk, deltblk, doblk, dq_i = qi
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            msk = _causal_window_mask(qpblk, kpblk, causal, window)
            msk &= (kpblk < 2 ** 29)[:, None, :] & (qpblk >= 0)[:, :, None]
            p = jnp.where(msk[:, None],
                          jnp.exp(s - lblk.transpose(0, 2, 1)[..., None]),
                          0.0)                            # (B,H,bq,bkv)
            dv = dv + jnp.einsum("bhqk,bqhd->bkhd", p.astype(doblk.dtype),
                                 doblk).astype(jnp.float32)
            dp = jnp.einsum("bqhd,bkhd->bhqk", doblk, vblk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta_t(deltblk)[..., None]) * scale
            dq_i = dq_i + jnp.einsum("bhqk,bkhd->bqhd",
                                     ds.astype(qblk.dtype), kblk
                                     ).astype(jnp.float32)
            dk = dk + jnp.einsum("bhqk,bqhd->bkhd", ds.astype(qblk.dtype),
                                 qblk).astype(jnp.float32)
            return (dk, dv), dq_i

        def delta_t(x):                                   # (B,bq,H)→(B,H,bq)
            return x.transpose(0, 2, 1)

        init = _match_vma(
            (jnp.zeros((b, block_kv, h, hd), jnp.float32),
             jnp.zeros((b, block_kv, h, hd), jnp.float32)), kblk)
        (dk, dv), dq_new = lax.scan(
            q_block, init, (qb, qposb, lseb, deltab, doutb, dq_acc))
        return dq_new, (dk, dv)

    dq0 = _match_vma(jnp.zeros((nq, b, block_q, h, hd), jnp.float32), q)
    dq, (dks, dvs) = lax.scan(kv_block, dq0, (kb, vb, kposb))
    dq = jnp.moveaxis(dq, 0, 1).reshape(b, -1, h, hd)[:, :sq]
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, -1, h, hd)[:, :sk]
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, -1, h, hd)[:, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_core(q, k, v, q_pos, k_pos, causal, window, block_q, block_kv):
    out, _ = _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, window,
                             block_q, block_kv)
    return out


def _flash_core_fwd(q, k, v, q_pos, k_pos, causal, window, block_q,
                    block_kv):
    out, lse = _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, window,
                               block_q, block_kv)
    return out, (q, k, v, q_pos, k_pos, out, lse)


def _flash_core_bwd(causal, window, block_q, block_kv, res, dout):
    q, k, v, q_pos, k_pos, out, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, q_pos, k_pos, out, lse, dout,
                                 causal, window, block_q, block_kv)
    return dq, dk, dv, None, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def attention_flash_jnp(q, k, v, q_pos, k_pos, causal=True, window=None,
                        block_q=512, block_kv=1024):
    """Blockwise (FlashAttention-style) online-softmax attention in pure
    JAX with a custom flash BACKWARD (blockwise recompute from lse) —
    O(block_q x block_kv) live memory in both directions."""
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    return _flash_core(q, k, v, q_pos, k_pos, causal, window,
                       min(block_q, q.shape[1]), min(block_kv, k.shape[1]))


def attention_decode(q, k_cache, v_cache, q_pos, k_pos, window=None):
    """Single-step decode attention.

    q: (B,1,H,hd); caches: (B,S,KH,hd); k_pos: (B,S) absolute positions of
    cache slots (2**30 marks empty slots — they mask out via causality).
    """
    n_rep = q.shape[2] // k_cache.shape[2]
    kh = k_cache.shape[2]
    b, s = k_cache.shape[:2]
    hd = q.shape[-1]
    scale = hd ** -0.5
    # grouped-query einsum without materializing repeated KV
    qg = q.reshape(b, 1, kh, n_rep, hd)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = k_pos[:, None, :] <= q_pos[:, :, None]       # (B,1,S)
    if window is not None:
        valid &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v_cache)
    return out.reshape(b, 1, kh * n_rep, hd)


def attention(q, k, v, q_pos, k_pos, *, causal=True, window=None,
              opts: ModelOptions = DEFAULT_OPTIONS):
    impl = opts.attn_impl
    if impl == "auto":
        impl = "flash_jnp" if k.shape[1] > opts.flash_threshold else "naive"
    if impl == "naive":
        return attention_naive(q, k, v, q_pos, k_pos, causal, window)
    if impl == "flash_jnp":
        return attention_flash_jnp(q, k, v, q_pos, k_pos, causal, window,
                                   opts.block_q, opts.block_kv)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, q_pos, k_pos, causal=causal,
                                    window=window)
    raise ValueError(f"unknown attn_impl {impl!r}")


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("bsd,df->bsf", x, w_gate)
    u = jnp.einsum("bsd,df->bsf", x, w_up)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, w_down)


def gelu_mlp(x, w1, b1, w2, b2):
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, w1) + b1)
    return jnp.einsum("bsf,fd->bsd", h, w2) + b2


# --------------------------------------------------------------------------
# ring attention (context parallelism)
# --------------------------------------------------------------------------

def combine_attention_partials(outs, lses):
    """Merge attention partials computed against disjoint KV shards.

    outs: list of (B,S,H,hd); lses: list of (B,S,H) log-sum-exp. The
    online-softmax identity: softmax over the union = exp-weighted
    combination of the partials. This is the math under both flash
    (sequential blocks) and ring attention (distributed blocks).
    """
    m = lses[0]
    for l in lses[1:]:
        m = jnp.maximum(m, l)
    num = jnp.zeros_like(outs[0], dtype=jnp.float32)
    den = jnp.zeros(lses[0].shape, jnp.float32)
    for o, l in zip(outs, lses):
        w = jnp.exp(l - m)
        num = num + o.astype(jnp.float32) * w[..., None]
        den = den + w
    return (num / jnp.maximum(den, 1e-30)[..., None]).astype(outs[0].dtype)


def attention_partial(q, k, v, q_pos, k_pos, causal=True, window=None,
                      block_q=512, block_kv=1024):
    """Flash attention returning (out, lse) for partial-KV combination."""
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    return _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, window,
                           min(block_q, q.shape[1]),
                           min(block_kv, k.shape[1]))


def ring_attention(q, k, v, q_pos, k_pos, axis_name: str, causal=True,
                   window=None, block_q=512, block_kv=1024):
    """Context-parallel attention: sequence sharded over `axis_name`.

    Call INSIDE shard_map with q,k,v local shards (B, S_loc, H|KH, hd)
    and q_pos/k_pos the local absolute positions. Each of the
    ring-size steps computes a flash partial against the resident KV
    shard, then rotates KV (+positions) to the next neighbour with
    collective_permute — compute and comm overlap on real hardware.
    GSPMD cannot derive this program from a sharded-sequence constraint
    (measured: mass resharding, EXPERIMENTS.md §Perf C3); shard_map
    states it explicitly.
    """
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    # mark the rotating tensors device-varying over the ring axis (the
    # scan carry must have stable varying-manual-axes types); inputs
    # already varying (sharded over the ring) pass through unchanged
    def _vary(x):
        if axis_name in getattr(jax.typeof(x), "vma", ()):
            return x
        return jax.lax.pcast(x, (axis_name,), to="varying")

    k, v, k_pos = _vary(k), _vary(v), _vary(k_pos)

    def step(carry, _):
        k_cur, v_cur, kpos_cur, outs = carry
        out, lse = attention_partial(q, k_cur, v_cur, q_pos, kpos_cur,
                                     causal, window, block_q, block_kv)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        kpos_nxt = jax.lax.ppermute(kpos_cur, axis_name, perm)
        return (k_nxt, v_nxt, kpos_nxt, None), (out, lse)

    (_, _, _, _), (outs, lses) = lax.scan(
        step, (k, v, k_pos, None), None, length=n)
    return combine_attention_partials(
        [outs[i] for i in range(n)], [lses[i] for i in range(n)])
