"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the program's ``init_lm`` gives (read with
``jax.eval_shape``: shapes only, no values), and the values are the
benchmark's own: normal weights scaled by 1/sqrt(fan-in), embeddings
at 0.02, norm scales near 1 and small biases, each leaf from its own
fold of the seed. The reference is handed the same function's output,
so it takes no value that the program made.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.common import seed_key


def layout(cfg) -> Any:
    """ShapeDtypeStructs of one model's {'base', 'modular'} tree."""
    from repro.models.transformer import init_lm

    return jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))


def _fill(key, spec, dtype) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten_with_path(spec)
    out = []
    for i, (path, s) in enumerate(leaves):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        x = jax.random.normal(k, s.shape, jnp.float32)
        if name == "w":
            x = x / math.sqrt(s.shape[-2])
        elif name == "table":
            x = 0.02 * x
        elif name == "scale":
            x = 1.0 + 0.05 * x
        elif name in ("b", "bias"):
            x = 0.02 * x
        else:
            raise ValueError(f"no init rule for leaf {jax.tree_util.keystr(path)}")
        out.append(x.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


@functools.lru_cache(maxsize=None)
def _serve_init(cfg, n_tenants: int):
    spec = layout(cfg)
    dt = jnp.dtype(cfg.param_dtype)

    @jax.jit
    def init(key):
        mod = _fill(jax.random.fold_in(key, 0), spec["modular"], dt)
        bases = [_fill(jax.random.fold_in(key, 1 + t), spec["base"], dt)
                 for t in range(n_tenants)]
        return bases, mod

    return init


def serve_weights(cfg, n_tenants: int, seed: int
                  ) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """(per-tenant base blocks, the shared modular block)."""
    return _serve_init(cfg, n_tenants)(seed_key(seed, 1))


def _client(spec, dt, key):
    return {"base": _fill(jax.random.fold_in(key, 1), spec["base"], dt),
            "modular": _fill(jax.random.fold_in(key, 2), spec["modular"], dt)}


@functools.lru_cache(maxsize=None)
def _clients_init(cfg, n_clients: int, sharding):
    spec, dt = layout(cfg), jnp.dtype(cfg.param_dtype)

    def init(key):
        keys = jnp.stack([jax.random.fold_in(key, k)
                          for k in range(n_clients)])
        return jax.vmap(lambda k: _client(spec, dt, k))(keys)

    return jax.jit(init, out_shardings=sharding)


@functools.lru_cache(maxsize=None)
def _client_init(cfg):
    spec, dt = layout(cfg), jnp.dtype(cfg.param_dtype)
    return jax.jit(lambda key, k: _client(spec, dt,
                                          jax.random.fold_in(key, k)))


def client_params(cfg, n_clients: int, seed: int, sharding=None):
    """Stacked (N, ...) params of N IFL clients, each its own model."""
    return _clients_init(cfg, n_clients, sharding)(seed_key(seed, 2))


def client_one(cfg, seed: int, k: int):
    """Client k's params alone: the k-th slice of ``client_params``."""
    return _client_init(cfg)(seed_key(seed, 2), k)
