"""The plain reference of a training job: the configuration's plain
model (``bench/references/<name>.py``) trained from the seed's weights on
the seed's batches by momentum SGD in float32, and, for CSC, Algorithm 1
of the paper written out on the flat gradient pool.

It imports nothing of the program. It follows the job the traffic file
states: the learning-rate schedule (linear warm-up over a twentieth of
``schedule_steps``, then cosine decay), momentum, weight decay, and the
CSC chunk size and sparsity with the sparse stage from the first step.
Gradients are taken over the global batch in blocks of rows, spread over
every local device, so that the reference fits beside nothing else on
the chip.

``fault`` plants one of the faults the comparison must catch, for the
calibration of the limits: ``"half"`` trains on the first half of every
batch only.
"""
from __future__ import annotations

import importlib
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import traffic as traffic_mod


def model_module(config: Dict):
    return importlib.import_module(
        f"bench.references.{config['reference']['module']}")


def reference_model(config: Dict) -> Dict:
    """The model settings the reference runs: the configuration's sizes
    plus the reference's own constants (such as the norm's epsilon)."""
    return {**config["model"], **config["reference"]}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    if seed < 0:
        raise ValueError(f"seed must be a whole number, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def lr_at(opt: Dict, step: int) -> float:
    """Linear warm-up over max(schedule_steps // 20, 1) steps from
    lr/warm at step 0, then cosine decay to 0 at schedule_steps."""
    total = opt["schedule_steps"]
    warm = max(total // 20, 1)
    frac = min((step + 1.0) / warm, 1.0)
    progress = min(max((step - warm) / max(total - warm, 1.0), 0.0), 1.0)
    return opt["lr"] * frac * 0.5 * (1.0 + math.cos(math.pi * progress))


def leaf_names(tree) -> list:
    """'a/b/c' names of a dict tree's leaves, in flatten order."""
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(k.key) for k in path) for path, _ in paths]


class Reference:
    """The reference job of one configuration and traffic mix."""

    def __init__(self, config: Dict, traffic: Dict,
                 precision: str = "f32"):
        self.config, self.traffic = config, traffic
        self.model = reference_model(config)
        self.mod = model_module(config)
        self.precision = precision
        devices = jax.devices()
        self.mesh = Mesh(np.array(devices), ("rows",))
        self.block = config["reference"]["rows_per_block"] * len(devices)
        model, mod = self.model, self.mod

        def block_grad(params, tokens, labels):
            return jax.value_and_grad(mod.loss_sum)(
                params, tokens, labels, model, precision)

        rows = NamedSharding(self.mesh, P("rows"))
        rep = NamedSharding(self.mesh, P())
        self._rows, self._rep = rows, rep
        self._block_grad = jax.jit(block_grad,
                                   in_shardings=(rep, rows, rows),
                                   out_shardings=rep)
        self._init = jax.jit(lambda k: mod.init_params(model, k),
                             out_shardings=rep)
        self._add = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.add, a, b), donate_argnums=0)
        self._opt = None

    def params0(self, seed: int):
        return self._init(seed_key(seed))

    def grads(self, params, tokens: np.ndarray, labels: np.ndarray):
        """(mean loss, mean gradient) over the rows, in blocks."""
        n = tokens.shape[0]
        if n % self.block:
            raise ValueError(f"{n} rows do not split into blocks of "
                             f"{self.block}")
        total, acc = 0.0, None
        for i in range(0, n, self.block):
            loss, g = self._block_grad(params, tokens[i:i + self.block],
                                       labels[i:i + self.block])
            total += float(loss)
            acc = g if acc is None else self._add(acc, g)
        scale = 1.0 / (n * tokens.shape[1])
        return total * scale, jax.tree_util.tree_map(
            lambda x: x * scale, acc)

    def run(self, seed: int, steps: int,
            fault: Optional[str] = None) -> Dict:
        """``steps`` training steps from the seed. Returns the losses and,
        per leaf, the norms the comparison reads (see ``bench.check``)."""
        tr, model = self.traffic, self.model
        opt, gf = tr["optimizer"], tr["gradientflow"]
        batch, seq = tr["global_batch"], tr["seq_len"]
        src = traffic_mod.source(tr, model["vocab_size"], seed)
        params = self.params0(seed)
        names = leaf_names(params)
        csc = gf["mode"] == "csc"
        if self._opt is None:  # one compiled update for every run
            self._opt = _CSC(params, gf, opt) if csc else _SGD(params, opt)
        state = self._opt
        state.reset(params)
        losses, gnorm0 = [], None
        for step in range(steps):
            rows = src.rows(step, 0, batch, seq)
            if fault == "half":
                rows = rows[:batch // 2]
            loss, grads = self.grads(params, rows[:, :-1], rows[:, 1:])
            losses.append(loss)
            if gnorm0 is None:
                gnorm0 = _leaf_norms(grads)
            params = state.step(params, grads, lr_at(opt, step))
        out = {"names": names, "losses": losses, "grad0": gnorm0,
               "update": _leaf_norms(jax.tree_util.tree_map(
                   jnp.subtract, params, self.params0(seed))),
               "momentum": state.momentum_norms()}
        if csc:
            out["hg"] = state.hg_norms()
            out["selected"] = state.selected
        return out


@jax.jit
def _leaf_norms_dev(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


def _leaf_norms(tree) -> list:
    return [float(x) for x in _leaf_norms_dev(tree)]


class _SGD:
    """Momentum SGD as the paper states it: u = m u + lr (g + wd w),
    w -= u, on every element."""

    def __init__(self, params, opt):
        self.m, self.wd = opt["momentum"], opt["weight_decay"]
        self._step = jax.jit(self._update, donate_argnums=(0, 1, 2))

    def reset(self, params):
        self.u = jax.tree_util.tree_map(jnp.zeros_like, params)

    def _update(self, params, u, grads, lr):
        u = jax.tree_util.tree_map(
            lambda u, g, w: self.m * u + lr * (g + self.wd * w),
            u, grads, params)
        return jax.tree_util.tree_map(jnp.subtract, params, u), u

    def step(self, params, grads, lr):
        params, self.u = self._step(params, self.u, grads,
                                    jnp.float32(lr))
        return params

    def momentum_norms(self):
        return _leaf_norms(self.u)


class _CSC:
    """Algorithm 1 on one worker, on the flat pool: the leaves in reverse
    flatten order (backward's generation order), padded to whole chunks.
    Each step re-injects the historical gradients hg, sends the chunks
    that the previous step's L1 census ranks highest (the first chunks at
    the first step), updates only those by momentum SGD, keeps momentum
    times the rest in hg, and takes the census of the result."""

    def __init__(self, params, gf, opt):
        self.chunk = gf["chunk_elems"]
        self.m, self.wd = opt["momentum"], opt["weight_decay"]
        leaves, self.treedef = jax.tree_util.tree_flatten(params)
        self.shapes = [x.shape for x in leaves]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        n = sum(self.sizes)
        self.size = -(-n // self.chunk) * self.chunk
        chunks = self.size // self.chunk
        k = int(round((1.0 - gf["sparsity"]) * chunks))
        self.k = min(max(k, 1), chunks)
        self._step = jax.jit(self._update, donate_argnums=(0, 1, 2, 3, 4))

    def reset(self, params):
        chunks = self.size // self.chunk
        self.u = jnp.zeros((self.size,), jnp.float32)
        self.hg = jnp.zeros((self.size,), jnp.float32)
        self.norms = jnp.arange(chunks, 0, -1, dtype=jnp.float32)
        self.selected = None

    def flat(self, tree):
        leaves = jax.tree_util.tree_leaves(tree)[::-1]
        pool = jnp.concatenate([x.reshape(-1) for x in leaves])
        return jnp.pad(pool, (0, self.size - pool.shape[0]))

    def unflat(self, pool):
        out, off = [], 0
        for shape, size in zip(self.shapes[::-1], self.sizes[::-1]):
            out.append(pool[off:off + size].reshape(shape))
            off += size
        return jax.tree_util.tree_unflatten(self.treedef, out[::-1])

    def _update(self, params, u, hg, norms, grads, lr):
        c = self.chunk
        w, g = self.flat(params), self.flat(grads) + hg
        _, idx = jax.lax.top_k(norms, self.k)
        sel = jnp.zeros(norms.shape, jnp.bool_).at[idx].set(True)
        mask = jnp.repeat(sel, c)
        u_new = jnp.where(mask, self.m * u + lr * (g + self.wd * w), u)
        w = jnp.where(mask, w - u_new, w)
        hg = jnp.where(mask, 0.0, self.m * g)
        norms = jnp.sum(jnp.abs(g).reshape(-1, c), axis=1)
        return self.unflat(w), u_new, hg, norms, sel

    def step(self, params, grads, lr):
        params, self.u, self.hg, self.norms, sel = self._step(
            params, self.u, self.hg, self.norms, grads, jnp.float32(lr))
        self.selected = np.asarray(sel)
        return params

    def _pool_norms(self, pool):
        tree = self.unflat(pool)
        return _leaf_norms(tree)

    def momentum_norms(self):
        return self._pool_norms(self.u)

    def hg_norms(self):
        return self._pool_norms(self.hg)
