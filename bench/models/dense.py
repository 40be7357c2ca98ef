"""The plain reference of the dense decoder family (``kind="dense"``, the
family of ``canonicalizer-100m``), written from the layer equations in
``jax.numpy`` and float32 at the highest matmul precision.

    x   = E[ids]                                  (x sqrt(d_model) for gemma)
    per layer:
      h   = rms(x) (1 + ln1)
      q, k, v = h Wq, h Wk, h Wv                  (heads of head_dim; rms of
                                                  each head (1 + q_norm/k_norm)
                                                  with qk_norm)
      q, k = rotary(q, k) on positions 0..T-1     (pairs (2i, 2i+1) of the
                                                  first rope_fraction of a head)
      a   = softmax(q k^T / sqrt(head_dim) + causal) v, query head i reading
            key/value head i // (n_heads / kv_heads)
      x   = x + a Wo
      h   = rms(x) (1 + ln2)
      x   = x + (act(h W1) * h W3) W2             (swiglu: silu, geglu: gelu;
                                                  squared_relu and gelu: no W3)
    logits = rms(x) (1 + final_norm) H            (H = E^T with tied embeddings)

``rms(x) = x / sqrt(mean(x^2) + 1e-6)``.  The parameters are a dict in the
layout the program's dense family serves (``embed``, ``layers`` stacked on a
leading layer axis, ``final_norm``, ``lm_head``); ``init`` makes them from a
key in one jitted call.  Nothing here imports the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-6
GLU = ("swiglu", "geglu")
NORMS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def shapes(cfg: dict) -> dict:
    d, hd, L = cfg["d_model"], cfg["head_dim"] or cfg["d_model"] // cfg["n_heads"], cfg["n_layers"]
    layer = {"wq": (d, cfg["n_heads"] * hd), "wk": (d, cfg["kv_heads"] * hd),
             "wv": (d, cfg["kv_heads"] * hd), "wo": (cfg["n_heads"] * hd, d),
             "ln1": (d,), "ln2": (d,),
             "mlp_w1": (d, cfg["d_ff"]), "mlp_w2": (cfg["d_ff"], d)}
    if cfg["activation"] in GLU:
        layer["mlp_w3"] = (d, cfg["d_ff"])
    if cfg["qk_norm"]:
        layer["q_norm"] = (hd,)
        layer["k_norm"] = (hd,)
    out = {"embed": (cfg["vocab"], d),
           "layers": {k: (L, *s) for k, s in layer.items()},
           "final_norm": (d,)}
    if not cfg["tie_embeddings"]:
        out["lm_head"] = (d, cfg["vocab"])
    return out


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted(cfg.items()))


@functools.lru_cache(maxsize=None)
def _init_fn(frozen: tuple):
    cfg = dict(frozen)
    if cfg["kind"] != "dense" or cfg["embed_inputs"]:
        raise ValueError("the dense reference takes token ids of a dense model, "
                         f"not kind {cfg['kind']!r} with embed_inputs {cfg['embed_inputs']}")
    tree = shapes(cfg)
    paths, treedef = jax.tree.flatten_with_path(tree, is_leaf=lambda x: isinstance(x, tuple))
    dtype = jnp.dtype(cfg["dtype"])

    def init(key):
        out = []
        for k, (path, shape) in zip(jax.random.split(key, len(paths)), paths):
            w = jax.random.normal(k, shape, jnp.float32)
            # matrices: variance 1/fan_in; norm weights: small, so that the
            # (1 + w) of every norm is exercised
            norm = path[-1].key in NORMS
            w = w * (0.1 if norm else shape[-2] ** -0.5)
            out.append(w.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(init)


def init(cfg: dict, key):
    """Random weights in the served dtype, made on the device in one call."""
    return _init_fn(_frozen(cfg))(key)


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * (1.0 + w)


def _rope(x, theta: float, fraction: float):
    """x: (T, H, Dh) on positions 0..T-1."""
    t, _, dh = x.shape
    rot = int(dh * fraction) // 2 * 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0:rot:2], x[..., 1:rot:2]
    pairs = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([pairs.reshape(t, x.shape[1], rot), x[..., rot:]], axis=-1)


def _act(h, name: str):
    if name == "swiglu":
        return jax.nn.silu(h)
    if name in ("geglu", "gelu"):
        return jax.nn.gelu(h)  # the tanh form, as the published GeGLU models use
    if name == "squared_relu":
        return jnp.square(jax.nn.relu(h))
    raise ValueError(f"unknown activation {name!r}")


@functools.lru_cache(maxsize=None)
def _logits_fn(frozen: tuple):
    cfg = dict(frozen)
    H, KV = cfg["n_heads"], cfg["kv_heads"]
    hd = cfg["head_dim"] or cfg["d_model"] // H

    def layer(x, lp):
        lp = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
        t = x.shape[0]
        h = _rms(x, lp["ln1"])
        q = (h @ lp["wq"]).reshape(t, H, hd)
        k = (h @ lp["wk"]).reshape(t, KV, hd)
        v = (h @ lp["wv"]).reshape(t, KV, hd)
        if cfg["qk_norm"]:
            q, k = _rms(q, lp["q_norm"]), _rms(k, lp["k_norm"])
        q = _rope(q, cfg["rope_theta"], cfg["rope_fraction"])
        k = _rope(k, cfg["rope_theta"], cfg["rope_fraction"])
        kv_of = jnp.arange(H) // (H // KV)
        s = jnp.einsum("qhd,khd->hqk", q, k[:, kv_of]) / math.sqrt(hd)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v[:, kv_of])
        x = x + a.reshape(t, H * hd) @ lp["wo"]
        h = _rms(x, lp["ln2"])
        u = _act(h @ lp["mlp_w1"], cfg["activation"])
        if cfg["activation"] in GLU:
            u = u * (h @ lp["mlp_w3"])
        return x + u @ lp["mlp_w2"], None

    def logits(params, ids):
        embed = params["embed"].astype(jnp.float32)
        x = embed[ids]
        if cfg["name"].startswith("gemma"):
            x = x * math.sqrt(cfg["d_model"])
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rms(x, params["final_norm"].astype(jnp.float32))
        head = embed.T if cfg["tie_embeddings"] else params["lm_head"].astype(jnp.float32)
        return x @ head

    return jax.jit(logits)


def logits(cfg: dict, params, token_ids):
    """float32 logits (T, vocab) of every position of one sequence; the
    layers run one at a time (a scan), each cast to float32 as it runs."""
    with jax.default_matmul_precision("highest"):
        return _logits_fn(_frozen(cfg))(params, jnp.asarray(token_ids, jnp.int32))
