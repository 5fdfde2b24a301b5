"""Plain reference of the dense decoder step that the plan cells run.

Straight ``jax.numpy`` with no kernels and no caching (layers are one
``lax.scan``, which keeps the compiled program small), written from the
equations of the in-tree dense decoder:

- token embedding, tied with the output head;
- per layer, pre-norm RMSNorm (scale only, eps 1e-6), q/k/v projections
  with bias, rotary position embedding (theta from the configuration),
  causal softmax attention, output projection, residual; then
  pre-norm RMSNorm, GELU MLP (tanh form) with biases, residual;
- final RMSNorm, logits over the vocabulary, mean cross-entropy over
  the positions whose label is not negative;
- AdamW with global-norm clipping, linear warm-up and cosine decay, as
  the configuration's ``adamw`` group states.

GPT-2 itself uses LayerNorm and learned positions; the reference follows
the in-tree model, whose step is the one measured. Weights are made
here from the seed and handed to both the program and the reference.
Matrix products run at ``highest`` precision unless ``dtype`` asks for
less (the control).
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def param_shapes(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The parameter tree of a dense decoder with a GELU MLP, biased
    q/k/v and tied embeddings, layers stacked on the leading axis."""
    d, f, v, n = (conf["d_model"], conf["d_ff"], conf["vocab"],
                  conf["n_layers"])
    hd = d // conf["n_heads"]
    h, kh = conf["n_heads"] * hd, conf["n_kv_heads"] * hd
    return {
        "embed": (v, d),
        "final_norm": (d,),
        "attn_layers": {
            "ln": (n, d), "wq": (n, d, h), "wk": (n, d, kh),
            "wv": (n, d, kh), "wo": (n, h, d), "bq": (n, h),
            "bk": (n, kh), "bv": (n, kh),
            "ffn": {"ln": (n, d), "w1": (n, d, f), "b1": (n, f),
                    "w2": (n, f, d), "b2": (n, d)},
        },
    }


def _leaves(tree, prefix=()) -> List[Tuple[Tuple[str, ...], tuple]]:
    out = []
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.extend(_leaves(v, prefix + (k,)))
        else:
            out.append((prefix + (k,), v))
    return out


def _set(tree: Dict, path: Tuple[str, ...], value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make_weights(conf: Dict[str, Any], key, dtype=jnp.float32):
    """Weights from ``key``, in one jitted call on the default device:
    matrices and biases N(0, 0.02), norm scales 1 + N(0, 0.02)."""
    leaves = _leaves(param_shapes(conf))

    def build(key):
        out: Dict[str, Any] = {}
        for i, (path, shape) in enumerate(leaves):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * 0.02
            if path[-1] in ("ln", "final_norm"):
                x = x + 1.0
            _set(out, path, x.astype(dtype))
        return out

    return jax.jit(build)(key)


def token_pool(conf: Dict[str, Any], seed: int, rows: int, batch: int,
               seq: int) -> List[Dict[str, np.ndarray]]:
    """``rows`` batches of uniform tokens, every row distinct; labels
    are the next token, the last position has none (-1)."""
    rng = np.random.default_rng([seed, 7])
    out = []
    for _ in range(rows):
        tokens = rng.integers(0, conf["vocab"], size=(batch, seq),
                              dtype=np.int32)
        labels = np.roll(tokens, -1, axis=1)
        labels[:, -1] = -1
        out.append({"tokens": tokens, "labels": labels})
    return out


def _rmsnorm(x, scale):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 / jnp.sqrt(var + 1e-6) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def _rope(x, theta):
    """x: (b, s, heads, hd); rotate the two halves of each head."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _layer(conf, p, x):
    b, s, d = x.shape
    nh = conf["n_heads"]
    hd = d // nh
    rep = nh // conf["n_kv_heads"]
    h = _rmsnorm(x, p["ln"])
    q = (h @ p["wq"] + p["bq"]).reshape(b, s, nh, hd)
    k = (h @ p["wk"] + p["bk"]).reshape(b, s, -1, hd)
    v = (h @ p["wv"] + p["bv"]).reshape(b, s, -1, hd)
    q, k = _rope(q, conf["rope_theta"]), _rope(k, conf["rope_theta"])
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores * hd ** -0.5
    causal = np.tril(np.ones((s, s), dtype=bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nh * hd)
    x = x + o @ p["wo"]
    f = p["ffn"]
    h = _rmsnorm(x, f["ln"])
    u = h @ f["w1"] + f["b1"]
    g = 0.5 * u * (1 + jnp.tanh(float(np.sqrt(2 / np.pi))
                                * (u + 0.044715 * u ** 3)))
    return x + g @ f["w2"] + f["b2"]


def loss(conf: Dict[str, Any], params, batch):
    x = params["embed"][batch["tokens"]]
    x, _ = jax.lax.scan(lambda h, p: (_layer(conf, p, h), None), x,
                        params["attn_layers"])
    x = _rmsnorm(x, params["final_norm"])
    logits = (x @ params["embed"].T).astype(jnp.float32)
    labels = batch["labels"]
    valid = labels >= 0
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    nll = jax.nn.logsumexp(logits, axis=-1) - gold
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.sum(valid)


def lr_at(adamw: Dict[str, Any], step: int) -> float:
    warm = min(1.0, (step + 1) / max(1, adamw["warmup_steps"]))
    prog = min(max((step - adamw["warmup_steps"])
                   / max(1, adamw["total_steps"] - adamw["warmup_steps"]),
                   0.0), 1.0)
    frac = adamw["min_lr_frac"] + (1 - adamw["min_lr_frac"]) * 0.5 * (
        1 + np.cos(np.pi * prog))
    return adamw["lr"] * warm * frac


def adamw_step(adamw: Dict[str, Any], params, grads, mu, nu, step: int):
    """One AdamW step after global-norm clipping; returns the new
    params, moments and the clipped gradient the update used."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in leaves))
    scale = jnp.minimum(1.0, adamw["grad_clip"] / (gnorm + 1e-9))
    b1, b2, t = adamw["b1"], adamw["b2"], step + 1
    lr = lr_at(adamw, step)

    def one(p, g, m, v):
        g = (g.astype(jnp.float32) * scale).astype(p.dtype)
        m = (b1 * m + (1 - b1) * g).astype(p.dtype)
        v = (b2 * v + (1 - b2) * g * g).astype(p.dtype)
        delta = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t))
                                       + adamw["eps"])
        delta = delta + adamw["weight_decay"] * p
        return (p - lr * delta).astype(p.dtype), m, v, g

    out = jax.tree.map(one, params, grads, mu, nu)
    pick = [jax.tree.map(lambda o, i=i: o[i], out,
                         is_leaf=lambda o: isinstance(o, tuple))
            for i in range(4)]
    return tuple(pick)


def leaf_norms(tree) -> Dict[str, float]:
    """Float32 norm of each leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(
        jnp.ravel(x).astype(jnp.float32))) for p, x in flat}


def batch_grad(grad_fn, params, batch) -> Tuple[float, Any]:
    """Loss and gradient of the batch's mean, one row at a time so that
    a batch of any size fits one chip: each row's mean weighted by its
    share of the batch's labelled positions (a one-row batch is its own
    row, weighted by 1)."""
    valid = (np.asarray(batch["labels"]) >= 0).sum(axis=1)
    value, grads = 0.0, None
    for r, count in enumerate(valid):
        w = float(count / valid.sum())
        v, g = grad_fn(params, {k: x[r:r + 1] for k, x in batch.items()})
        value += w * float(v)
        grads = (jax.tree.map(lambda x: w * x, g) if grads is None else
                 jax.tree.map(lambda a, x: a + w * x, grads, g))
        del g
    return value, grads


def run(conf: Dict[str, Any], adamw: Dict[str, Any], key, batches,
        dtype=jnp.float32) -> Dict[str, Any]:
    """The reference's first three steps: each step's loss, the first
    step's clipped gradient per leaf, and each leaf's change after
    three steps. ``dtype`` below float32 gives the control. The weights
    are made again at the end (the same from the same key) rather than
    kept, which leaves room for a batch's gradients."""
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        params = make_weights(conf, key, dtype)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, b: loss(conf, p, b)))
        step_fn = jax.jit(
            lambda p, g, m, v, s: adamw_step(adamw, p, g, m, v, s),
            static_argnums=4)
        losses, first_grad = [], None
        for s, batch in enumerate(batches[:3]):
            value, grads = batch_grad(grad_fn, params, batch)
            losses.append(value)
            params, mu, nu, clipped = step_fn(params, grads, mu, nu, s)
            if s == 0:
                first_grad = leaf_norms(clipped)
            del grads, clipped
        change = leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            params, make_weights(conf, key, dtype)))
    return {"losses": losses, "grad": first_grad, "change": change}
