"""Operations and bytes the benchmark divides by time: the model FLOPs
of a training step and the bytes GradientFlow's pool kernels need.

Both are worked out from the configuration and the job alone, never from
what the program reports, so a later change to the program cannot move
the yardstick.
"""
from __future__ import annotations

import math
from typing import Dict

BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def _leaf_sizes(shapes) -> list:
    if isinstance(shapes, dict):
        return [s for k in sorted(shapes) for s in _leaf_sizes(shapes[k])]
    return [math.prod(shapes)]


def param_count(shapes) -> int:
    """Parameters of a tree of shapes (tied embeddings held once)."""
    return sum(_leaf_sizes(shapes))


def flops_per_token(model: Dict, seq_len: int, n_params: int) -> float:
    """Model FLOPs of one trained token: 6 per parameter (forward and
    backward of every matrix product, the tied embedding counted once,
    as the output head) plus attention's 12 * layers * seq * heads *
    head_dim (scores and values, forward and backward). Recomputation
    (remat) is not counted."""
    head_dim = model.get("head_dim") or model["d_model"] // model["num_heads"]
    return 6.0 * n_params + 12.0 * model["num_layers"] * seq_len \
        * model["num_heads"] * head_dim


def pool_layout(n_params: int, gf: Dict) -> Dict[str, int]:
    """GradientFlow's pool: ``n`` gradients, padded to whole chunks where
    CSC chunks it; ``selected`` chunks of the sparse stage."""
    chunked = gf["mode"] == "csc"
    chunk = gf["chunk_elems"] if chunked else 1
    size = -(-n_params // chunk) * chunk
    out = {"n": n_params, "size": size}
    if chunked:
        chunks = size // chunk
        k = int(round((1.0 - gf["sparsity"]) * chunks))
        out.update(chunks=chunks, selected=min(max(k, 1), chunks))
    return out


def pool_kernel_bytes(n_params: int, gf: Dict) -> Dict[str, int]:
    """Bytes per training step that each of GradientFlow's kernels needs:
    every input it needs read once and every output written once, at the
    dtype the algorithm holds it in, the pool's padding left out. A
    kernel that moves more (padding, a mask it could do without, a wider
    dtype) reads below 100% of its roofline; none can read above it.

    Keyed by the kernel names the compiled program gives them
    (``pool_pack`` twice: gradients to the wire pool, and the f32 master
    pool of the params; ``pool_unpack_update``: momentum SGD on the pool,
    unpacked to the param leaves; under CSC ``csc_compact``, the gather of
    the selected chunks, and ``chunk_l1norm``, the census)."""
    lay = pool_layout(n_params, gf)
    n = lay["n"]
    f32 = 4
    csc = gf["mode"] == "csc"
    # dense/lazy pack straight to the wire dtype; CSC packs f32 (hg is
    # added before the wire cast).
    packed = f32 if csc else BYTES[gf["wire_dtype"]]
    out = {
        # grads (f32) -> packed pool; params (f32) -> f32 master pool
        "pool_pack": n * (f32 + packed) + n * (f32 + f32),
        # read master, reduced grads, momentum; write momentum, params
        "pool_unpack_update": n * (f32 + packed + f32 + f32 + f32),
    }
    if csc:
        chunk = gf["chunk_elems"]
        sent = lay["selected"] * chunk
        out["csc_compact"] = sent * (f32 + f32)
        out["chunk_l1norm"] = n * f32 + lay["chunks"] * f32
    return out
