"""Plain reference of a decoder-only transformer LM (llama-style blocks):
pre-norm (RMSNorm with a scale, or OLMo's non-parametric LayerNorm),
grouped-query attention with half-split rotary embeddings, Qwen3's
QK-norm where the model has it (an RMSNorm with a scale over each
head's features of q and k, before the rotation) and a causal softmax, a SwiGLU MLP, tied input/output embeddings, mean next-token
cross-entropy.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no sharding, no bf16. It imports nothing of the program. The
parameter tree has the layout the trainer holds (names and shapes), so
that the benchmark can hand both the same weights, made here from the
seed.

``precision="fp8"`` is the control: every matrix product takes its two
operands rounded to float8_e4m3fn (one scale per tensor), forward and
backward, the next step down from the bf16 products the configuration
states.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn


def param_shapes(model: Dict[str, Any]) -> Dict[str, Any]:
    """Shapes of the parameter tree, as nested dicts of tuples."""
    d, h, kv = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // h
    f, v, n = model["d_ff"], model["vocab_size"], model["num_layers"]
    if not model["tie_embeddings"] or model["activation"] != "swiglu":
        raise ValueError("dense_lm covers tied embeddings and SwiGLU only")
    norm = {"scale": (n, d)} if model["norm"] == "rmsnorm" else {}
    attn = {"wq": (n, d, h * hd), "wk": (n, d, kv * hd),
            "wv": (n, d, kv * hd), "wo": (n, h * hd, d)}
    if model.get("qk_norm"):
        attn.update(q_norm=(n, hd), k_norm=(n, hd))
    layers = {
        "attn": attn,
        "attn_norm": dict(norm),
        "ffn": {"wi_gate": (n, d, f), "wi_up": (n, d, f), "wo": (n, f, d)},
        "mlp_norm": dict(norm),
    }
    final = {"scale": (d,)} if model["norm"] == "rmsnorm" else {}
    return {"embed": {"tokens": (v, d)}, "final_norm": final,
            "layers": layers}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init_params(model: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Weights from ``key``: token embeddings N(0, 0.02), norm scales
    (QK-norm's too) 1,
    every matrix N(0, 1/fan_in) with fan_in its second-last dimension;
    leaf i (in flatten order) draws from ``fold_in(key, i)``. Jit it with
    the trainer's shardings to make the weights on the device in one
    call."""
    shapes = param_shapes(model)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    leaves = []
    for i, (path, shape) in enumerate(paths):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        if name in ("scale", "q_norm", "k_norm"):
            leaves.append(jnp.ones(shape, jnp.float32))
        elif name == "tokens":
            leaves.append(0.02 * jax.random.normal(k, shape, jnp.float32))
        else:
            leaves.append(jax.random.normal(k, shape, jnp.float32)
                          / math.sqrt(shape[-2]))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -- matrix products ---------------------------------------------------------

def _fp8(x):
    """Round to float8_e4m3fn with one scale for the tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(FP8).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_dot2(a, b):
    return jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)


def _fp8_fwd(a, b):
    qa, qb = _fp8(a), _fp8(b)
    return jnp.matmul(qa, qb, precision=HIGHEST), (qa, qb)


def _fp8_bwd(res, g):
    qa, qb = res
    qg = _fp8(g)
    da = jnp.matmul(qg, jnp.swapaxes(qb, -1, -2), precision=HIGHEST)
    if qb.ndim == 2 and qa.ndim > 2:  # b shared by every leading index
        qa = qa.reshape(-1, qa.shape[-1])
        qg = qg.reshape(-1, qg.shape[-1])
    db = jnp.matmul(jnp.swapaxes(qa, -1, -2), qg, precision=HIGHEST)
    return da, db


_fp8_dot2.defvjp(_fp8_fwd, _fp8_bwd)


def matmul(a, b, precision: str):
    """``a @ b`` over the last two axes (batched over leading axes)."""
    if precision == "fp8":
        return _fp8_dot2(a, b)
    return jnp.matmul(a, b, precision=HIGHEST)


# -- the model ---------------------------------------------------------------

def _norm(p, x, kind, eps):
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * p["scale"]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    """Half-split rotary embedding of x: (b, s, heads, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _block(model, precision, p, x):
    b, s, d = x.shape
    h, kv = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // h
    eps, theta = model["norm_eps"], model["rope_theta"]
    y = _norm(p["attn_norm"], x, model["norm"], eps)
    q = matmul(y, p["attn"]["wq"], precision).reshape(b, s, h, hd)
    k = matmul(y, p["attn"]["wk"], precision).reshape(b, s, kv, hd)
    v = matmul(y, p["attn"]["wv"], precision).reshape(b, s, kv, hd)
    if model.get("qk_norm"):
        q = _norm({"scale": p["attn"]["q_norm"]}, q, "rmsnorm", eps)
        k = _norm({"scale": p["attn"]["k_norm"]}, k, "rmsnorm", eps)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = matmul(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1),
                    precision) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = matmul(probs, v.transpose(0, 2, 1, 3), precision)
    att = att.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    x = x + matmul(att, p["attn"]["wo"], precision)
    y = _norm(p["mlp_norm"], x, model["norm"], eps)
    gate = matmul(y, p["ffn"]["wi_gate"], precision)
    up = matmul(y, p["ffn"]["wi_up"], precision)
    return x + matmul(jax.nn.silu(gate) * up, p["ffn"]["wo"], precision)


def loss_sum(params, tokens, labels, model, precision="f32"):
    """Summed next-token cross-entropy over every position of the rows.
    tokens, labels: int32 (rows, seq)."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)

    def body(x, p):
        return jax.checkpoint(
            lambda p, x: _block(model, precision, p, x))(p, x), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _norm(params["final_norm"], x, model["norm"], model["norm_eps"])
    logits = matmul(x, params["embed"]["tokens"].T, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)
