import jax

from repro.optim import adamw, lars, schedules, sgd


def init_state(name: str, pool_size: int):
    if name in ("momentum_sgd", "lars"):
        return sgd.init(pool_size)
    if name == "adamw":
        return adamw.init(pool_size)
    raise ValueError(f"unknown optimizer {name}")


def abstract_state(name: str, pool_size: int):
    if name in ("momentum_sgd", "lars"):
        return sgd.abstract_state(pool_size)
    if name == "adamw":
        return adamw.abstract_state(pool_size)
    raise ValueError(f"unknown optimizer {name}")


def update_pool(name: str, *args, **kwargs):
    if name in ("momentum_sgd", "lars"):
        return sgd.update_pool(*args, **kwargs)
    if name == "adamw":
        return adamw.update_pool(*args, **kwargs)
    raise ValueError(f"unknown optimizer {name}")


def update_unpack(name: str, pool, master, grads, state, mask, cfg, lr, *,
                  scale=None, ratios=None, use_kernels: bool = False,
                  tile_elems: int = 0):
    """Fused update+unravel: returns (new_params_pytree, new_opt_state).

    SGD/LARS take the single-pass streaming kernel path (LARS preferably
    as the per-tensor ``ratios`` vector — expanded per tile in-kernel, no
    pool-sized scale buffer); optimizers without a fused kernel (adamw)
    fall back to update_pool + the static-slice unravel — same output
    pytree, one extra pool pass. Scope ``gf.update``."""
    with jax.named_scope("gf.update"):
        if name in ("momentum_sgd", "lars"):
            return sgd.update_unpack(pool, master, grads, state, mask, cfg,
                                     lr, scale=scale, ratios=ratios,
                                     use_kernels=use_kernels,
                                     tile_elems=tile_elems)
        if ratios is not None:
            from repro.kernels import ref
            assert scale is None
            scale = ref.expand_ratios(ratios, pool.sizes, pool.size)
        new_master, new_state = update_pool(name, master, grads, state,
                                            mask, cfg, lr, scale=scale,
                                            use_kernels=use_kernels)
        return pool.unravel(new_master), new_state


def update_view(name: str, view, master, grads, state, mask, cfg, lr, *,
                scale=None, ratios=None, use_kernels: bool = False,
                tile_elems: int = 0):
    """Per-bucket segment update: the overlap engine's retire step.

    ``view`` is a ``GradientPool.bucket_view`` and every array argument a
    span-relative SEGMENT (master/grads/mask plus the optimizer state's
    pool-sized leaves sliced to the span). SGD/LARS run the fused
    update+unpack kernels on the view's sub-table; optimizers without a
    fused kernel (adamw) fall back to the segment ``update_pool`` + static
    slices. Returns (1-D leaves for the view's tensors, cast to their
    declared dtype, plus the updated state segment)."""
    if name in ("momentum_sgd", "lars"):
        return sgd.update_view(view, master, grads, state, mask, cfg, lr,
                               scale=scale, ratios=ratios,
                               use_kernels=use_kernels,
                               tile_elems=tile_elems)
    import jax

    if ratios is not None:
        from repro.kernels import ref
        assert scale is None
        scale = ref.expand_ratios(ratios, view.sizes, view.size)
    new_master, new_state = update_pool(name, master, grads, state, mask,
                                        cfg, lr, scale=scale,
                                        use_kernels=use_kernels)
    leaves = [jax.lax.slice(new_master, (off,), (off + size,))
              for off, size in zip(view.offsets, view.sizes)]
    leaves = [x if x.dtype == spec.dtype else x.astype(spec.dtype)
              for x, spec in zip(leaves, view.specs)]
    return leaves, new_state
