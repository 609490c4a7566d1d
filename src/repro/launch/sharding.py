"""Sharding resolution for runtime state (caches, tokens, optimizer).

Parameters get their specs from the model template (models.base).  This
module covers the remaining state that exists only at run time, with
divisibility-checked fallbacks:

  attention KV caches (..., B, S, KV, hd):
      B -> (pod, data) when divisible, else S -> data (long-context,
      batch=1 decode shards the *cache sequence* across the data axis),
      KV -> model when divisible.
  ssm conv cache (..., B, W, CH):   B -> data axes, CH -> model
  ssm state      (..., B, H, N, P): B -> data axes, H -> model
  tokens/pos     (B, ...):          B -> data axes
"""
from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import data_axes


def _size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, tuple):
        return math.prod(mesh.shape[a] for a in axes)
    return mesh.shape[axes]


def _div(dim, mesh, axes):
    if not (axes and dim % _size(mesh, axes) == 0):
        return None
    # PartitionSpec equality distinguishes 'data' from ('data',): collapse
    # single-axis tuples to the bare name so specs compare as documented.
    if isinstance(axes, tuple) and len(axes) == 1:
        return axes[0]
    return axes


def batch_spec(mesh, ndim: int, batch_dim_size: int) -> P:
    da = data_axes(mesh)
    first = _div(batch_dim_size, mesh, da)
    return P(*((first,) + (None,) * (ndim - 1)))


def attn_cache_spec(mesh, shape) -> P:
    """shape: (*prefix, B, S, KV, hd).

    B -> data axes; if B=1 (long-context decode) the cache *sequence*
    shards across data instead.  The model axis takes KV heads when they
    divide, else head_dim (GQA models routinely have kv < model-axis
    size; without the hd fallback a 32B model's 32k cache is 68
    GB/device and cannot fit)."""
    b, s, kv, hd = shape[-4:]
    prefix = (None,) * (len(shape) - 4)
    da = data_axes(mesh)
    b_ax = _div(b, mesh, da)
    s_ax = None
    if b_ax is None:
        s_ax = _div(s, mesh, "data" if "data" in mesh.axis_names else None)
    model = "model" if "model" in mesh.axis_names else None
    kv_ax = _div(kv, mesh, model)
    hd_ax = None
    if kv_ax is None:
        hd_ax = _div(hd, mesh, model)
    return P(*(prefix + (b_ax, s_ax, kv_ax, hd_ax)))


def ssm_conv_spec(mesh, shape) -> P:
    b, _, ch = shape[-3:]
    prefix = (None,) * (len(shape) - 3)
    b_ax = _div(b, mesh, data_axes(mesh))
    ch_ax = _div(ch, mesh, "model" if "model" in mesh.axis_names else None)
    return P(*(prefix + (b_ax, None, ch_ax)))


def ssm_state_spec(mesh, shape) -> P:
    b, h, _, _ = shape[-4:]
    prefix = (None,) * (len(shape) - 4)
    b_ax = _div(b, mesh, data_axes(mesh))
    h_ax = _div(h, mesh, "model" if "model" in mesh.axis_names else None)
    return P(*(prefix + (b_ax, h_ax, None, None)))


def cache_specs(cache_tree, mesh):
    """PartitionSpec tree for a cache ShapeDtypeStruct tree (path-keyed)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    out = []
    for path, leaf in flat:
        keys = [str(getattr(k, "key", k)) for k in path]
        last = keys[-1]
        if last in ("k", "v"):
            out.append(attn_cache_spec(mesh, leaf.shape))
        elif last in ("k_scale", "v_scale"):
            # (*prefix, B, S, KV): same layout minus the head_dim axis
            spec = attn_cache_spec(mesh, leaf.shape + (1,))
            out.append(P(*spec[:-1]))
        elif last == "conv":
            out.append(ssm_conv_spec(mesh, leaf.shape))
        elif last == "state":
            out.append(ssm_state_spec(mesh, leaf.shape))
        else:
            raise ValueError(f"unknown cache leaf {keys}")
    return jax.tree_util.tree_unflatten(treedef, out)


def named(mesh, spec_tree):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  spec_tree)


def batch_shardings(batch_specs_tree, mesh):
    """NamedShardings for a train/prefill input-spec dict."""
    out = {}
    for k, sds in batch_specs_tree.items():
        out[k] = NamedSharding(mesh,
                               batch_spec(mesh, len(sds.shape),
                                          sds.shape[0]))
    return out
