"""The weights and tenants a cell serves, made from ``--seed``.

The frozen base is random, made on the device in one jitted call, in the
dtype it is served in.  The tree has the layout the program's serving
entry points take (``params=``); only its structure is read from the
program, never a value.  The plain reference (``reference.py``) reads the
same tree, so both sides compute from the same numbers and the program
makes none of them.

QR-LoRA's basis is built here too: for each adapted projection ``W``,
``Q, R = qr(W)`` and ``B = Q[:, :cap]``, ``A = R[:cap, :]`` (the paper pivots
the columns first, which orders the directions; the served product
``x·W + ((x·B)·λ)·A`` is the same computation either way).  Layers without an
adapter carry zero factors, as the program's own initialisation gives them.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

NORM_JITTER = 0.05  # norm scales 1 + 0.05·N(0,1): a dropped scale shows
BIAS_STD = 0.02
EMBED_STD = 0.02


def _path_names(path) -> tuple:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def adapted_rows(n_layers: int, last: int) -> np.ndarray:
    return np.arange(n_layers) >= n_layers - last


def make_params(shapes: Pytree, conf: Dict, seed: int) -> Pytree:
    """Fill ``shapes`` (a ShapeDtypeStruct tree in the program's layout)
    from ``seed`` in one jitted call on the default device."""
    arch = conf["architecture"]
    n_layers = arch["num_hidden_layers"]
    adapted = adapted_rows(n_layers, conf["adapter"]["last_layers"])
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_path_names(p) for p, _ in flat]
    leaves = [s for _, s in flat]
    shape_of = {n: s.shape for n, s in zip(names, leaves)}

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out: Dict[tuple, jax.Array] = {}
        # base weights first: adapters are derived from them
        for k, name, s in zip(keys, names, leaves):
            if "adapters" in name:
                continue
            leaf = name[-1]
            if leaf in ("ln1", "ln2", "final_norm"):
                v = 1.0 + NORM_JITTER * jax.random.normal(k, s.shape, jnp.float32)
            elif leaf in ("bq", "bk", "bv"):
                v = BIAS_STD * jax.random.normal(k, s.shape, jnp.float32)
            elif leaf == "embed":
                v = EMBED_STD * jax.random.normal(k, s.shape, jnp.float32)
            elif leaf == "unembed":
                continue  # tied below
            else:  # stacked (layers, d_in, d_out) projections
                std = s.shape[-2] ** -0.5
                if leaf in ("wo", "w_down"):
                    std /= (2 * n_layers) ** 0.5
                v = std * jax.random.normal(k, s.shape, jnp.float32)
            out[name] = v.astype(s.dtype)
        for name, s in zip(names, leaves):
            if name[-1] == "unembed":
                if arch["tie_word_embeddings"]:
                    out[name] = out[("embed",)].T.astype(s.dtype)
                else:
                    raise ValueError("untied output embeddings are not configured")
        rows = jnp.asarray(adapted)
        for name, s in zip(names, leaves):
            if "adapters" not in name:
                continue
            mod, proj, leaf = name[-3], name[-2], name[-1]
            if leaf == "B" or leaf == "A":
                W = out[("groups", mod, proj)].astype(jnp.float32)
                cap = s.shape[-1] if leaf == "B" else s.shape[-2]
                q, r = jnp.linalg.qr(W)  # batched over layers
                v = q[..., :cap] if leaf == "B" else r[..., :cap, :]
                v = jnp.where(rows[:, None, None], v, 0.0)
                out[name] = v.astype(s.dtype)
            elif leaf == "lam":
                out[name] = jnp.zeros(s.shape, s.dtype)
            elif leaf == "ranks":
                cap = shape_of[name[:-1] + ("lam",)][-1]
                out[name] = jnp.where(rows, cap, 0).astype(s.dtype)
        return jax.tree_util.tree_unflatten(treedef, [out[n] for n in names])

    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(build)(key)


def tenant_lambdas(lam_shapes: Dict[str, Dict[str, tuple]], conf: Dict,
                   n_tenants: int, seed: int) -> Dict[str, Dict[str, Dict[str, np.ndarray]]]:
    """``{tenant name: {module: {proj: λ (layers, cap) f32}}}`` — i.i.d.
    normal on the adapted layers, zero elsewhere."""
    std = conf["adapter"]["tenant_lambda_std"]
    rng = np.random.default_rng([seed, 3])
    out = {}
    for t in range(n_tenants):
        tree = {}
        for mod, projs in lam_shapes.items():
            tree[mod] = {}
            for proj, shape in projs.items():
                n_layers = shape[0]
                lam = np.zeros(shape, np.float32)
                rows = adapted_rows(n_layers, conf["adapter"]["last_layers"])
                lam[rows] = rng.standard_normal((int(rows.sum()), shape[1])) * std
                tree[mod][proj] = lam
        out[tenant_name(t)] = tree
    return out


def tenant_name(t: int) -> str:
    return f"t{t}"
