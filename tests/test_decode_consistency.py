"""Decode-vs-forward consistency: running the decode path token-by-token
must reproduce the teacher-forced forward logits — validates KV caches,
SSM recurrent states, ring buffers and rope positions across families.

MoE root cause (was a "seed-known defect", now understood): capacity-
factor routing is non-causal along the sequence — the per-expert argsort
competes ALL tokens, including future positions, for cap slots, so a
token's drop fate depends on tokens after it. Token-by-token decode sees
a different competitor set by construction and CANNOT reproduce a
batched forward that dropped tokens. Where consistency is well-defined
(dropless capacity: no competition binds) decode matches exactly; the
minimal repro below pins the divergence to exactly the drop mechanism.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke_config
from repro.models import moe as M
from repro.models.api import build_model
from repro.models.layers import ModelOptions

OPTS = ModelOptions(dtype=jnp.float32, remat=False, attn_impl="naive")

# one representative per family (full 10-arch coverage in smoke tests).
# MoE archs are tested at dropless capacity — the only regime where
# decode == forward is mathematically possible (module docstring).
FAMILIES = ["qwen2_1_5b",            # dense GQA
            "h2o_danube_1_8b",       # SWA
            "mamba2_2_7b",           # SSM
            "qwen3_moe_30b_a3b",     # MoE
            "jamba_v0_1_52b",        # hybrid
            "whisper_tiny"]          # enc-dec


def _dropless(cfg):
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=M.dropless_capacity_factor(cfg.moe)))


@pytest.mark.parametrize("arch", FAMILIES)
def test_decode_matches_forward(arch):
    cfg = _dropless(smoke_config(get_config(arch)))
    api = build_model(cfg, OPTS)
    key = jax.random.PRNGKey(1)
    params = api.init(key)
    b, s = 2, 16
    toks = jax.random.randint(jax.random.fold_in(key, 1), (b, s), 1,
                              cfg.vocab, jnp.int32)

    if cfg.enc_dec:
        frames = jax.random.normal(jax.random.fold_in(key, 2),
                                   (b, 8, cfg.d_model), jnp.float32)
        batch = {"tokens": toks, "frame_embeds": frames}
        full = api.forward(params, batch)           # (b, s, V)
        from repro.models import encdec
        enc_out = encdec.encode(cfg, params, frames, OPTS)
        ck, cv = encdec.precompute_cross(cfg, params, enc_out)
        cache = {**api.init_cache(b, s), "cross_k": ck, "cross_v": cv}
    else:
        batch = {"tokens": toks}
        full = api.forward(params, batch)
        cache = api.init_cache(b, s)

    step = jax.jit(api.decode_step)
    for t in range(s):
        logits, cache = step(params, cache, {"tokens": toks[:, t:t + 1]})
        ref = full[:, t]
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref), atol=2e-3, rtol=2e-3,
            err_msg=f"{arch}: mismatch at position {t}")


def _zero_routers(params):
    """Zero every router weight: all logits tie, so top-k sends every
    token to the same top_k experts and capacity binds by construction
    (no dependence on the PRNG stream)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x)
        if getattr(path[-1], "key", None) == "router" else x, params)


def test_moe_capacity_drops_are_non_causal():
    """Minimal repro of the (formerly unexplained) MoE decode defect.

    1. with every token routed to the same experts, the batched forward
       DOES drop tokens (those experts oversubscribe), and decode
       diverges from forward past the first dropped position;
    2. raising ONLY the capacity factor to the dropless point makes
       decode match forward exactly — pinning the divergence to the
       drop mechanism, not the KV/SSM caches.
    """
    cfg = smoke_config(get_config("qwen3_moe_30b_a3b"))
    b, s = 2, 16
    t = b * s
    cap = M.capacity(t, cfg.moe)
    assert cap < t                    # capacity binds: all t tokens
    #                                   compete for the same experts

    api = build_model(cfg, OPTS)
    key = jax.random.PRNGKey(1)
    params = _zero_routers(api.init(key))
    toks = jax.random.randint(jax.random.fold_in(key, 1), (b, s), 1,
                              cfg.vocab, jnp.int32)
    full = api.forward(params, {"tokens": toks})
    cache = api.init_cache(b, s)
    step = jax.jit(api.decode_step)
    errs = []
    for pos in range(s):
        logits, cache = step(params, cache, {"tokens": toks[:, pos:pos + 1]})
        errs.append(float(jnp.abs(logits - full[:, pos]).max()))
    assert max(errs) > 1e-3           # drops happened -> decode diverges
    assert errs[0] < 1e-5             # ...but not at position 0

    # same weights, dropless capacity: exact agreement
    dcfg = _dropless(cfg)
    assert M.capacity(t, dcfg.moe) == t
    dapi = build_model(dcfg, OPTS)
    dfull = dapi.forward(params, {"tokens": toks})
    dcache = dapi.init_cache(b, s)
    dstep = jax.jit(dapi.decode_step)
    for pos in range(s):
        logits, dcache = dstep(params, dcache,
                               {"tokens": toks[:, pos:pos + 1]})
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(dfull[:, pos]),
                                   atol=2e-3, rtol=2e-3)


def test_swa_ring_buffer_evicts_correctly():
    """With window w, decode at position >= w must match forward —
    exercising slot eviction in the rolling cache."""
    cfg = smoke_config(get_config("h2o_danube_1_8b"))
    assert cfg.sliding_window == 32
    api = build_model(cfg, OPTS)
    key = jax.random.PRNGKey(3)
    params = api.init(key)
    b, s = 1, 48                      # > window 32
    toks = jax.random.randint(key, (b, s), 1, cfg.vocab, jnp.int32)
    full = api.forward(params, {"tokens": toks})
    cache = api.init_cache(b, s)
    step = jax.jit(api.decode_step)
    for t in range(s):
        logits, cache = step(params, cache, {"tokens": toks[:, t:t + 1]})
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, -1]), atol=2e-3,
                               rtol=2e-3)
