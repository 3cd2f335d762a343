"""Logical→physical sharding rules for params, activations and caches.

Conventions (GSPMD / pjit):
  * batch-like dims   → ("pod", "data")   (whichever axes the mesh has)
  * model-parallel    → "model": attention heads, FFN hidden, vocab,
                        expert (EP), mamba/mLSTM inner dims
  * everything else   → replicated

All rules are divisibility-checked against the active mesh: an axis that
does not divide the dim is dropped (GSPMD could pad, but clean factors keep
the collective schedule predictable — and vocab sizes like 122753 are not
16-divisible). `shard()` is a no-op outside a mesh context, so smoke tests
run unsharded.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

Array = jax.Array

BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"

# FSDP (ZeRO-3): when enabled, parameter/optimizer leaves additionally
# shard their non-"model" dim over the data axes; GSPMD inserts the
# per-layer weight all-gathers inside the scan (and reduce-scatters the
# grads), trading collective traffic for the per-device residency that
# lets ≥100B-param configs fit a 16 GB v5e.
_FSDP = False


def set_fsdp(enabled: bool) -> None:
    global _FSDP
    _FSDP = bool(enabled)


def _mesh_axis_sizes() -> dict[str, int]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return {}
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def _resolve(spec_axes: Sequence, shape: tuple[int, ...],
             sizes: dict[str, int]):
    """Filter logical spec entries by mesh presence + divisibility."""
    out = []
    for dim, entry in zip(shape, spec_axes):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = [a for a in axes if a in sizes]
        factor = 1
        for a in axes:
            factor *= sizes[a]
        if axes and dim % factor == 0:
            out.append(tuple(axes) if len(axes) > 1 else axes[0])
        else:
            out.append(None)
    return P(*out)


def shard(x: Array, *spec_axes) -> Array:
    """Activation sharding constraint; silently skipped with no mesh."""
    sizes = _mesh_axis_sizes()
    if not sizes:
        return x
    spec = _resolve(spec_axes, x.shape, sizes)
    return jax.lax.with_sharding_constraint(x, spec)


def batch_spec(x_shape: tuple[int, ...]) -> P:
    """(batch, ...) arrays: shard dim 0 over pod+data."""
    sizes = _mesh_axis_sizes()
    axes = [BATCH_AXES] + [None] * (len(x_shape) - 1)
    return _resolve(axes, x_shape, sizes) if sizes else P()


# ---------------------------------------------------------------------------
# Parameter rules: path regex → logical spec per dim (matched in order).
# Paths look like "layers/attn/wq/kernel", "layers/moe/wi_gate", ...
# ---------------------------------------------------------------------------

_PARAM_RULES: list[tuple[str, list]] = [
    # embeddings / unembeddings: vocab over model (fallback d handled by
    # divisibility: if vocab % model != 0 the axis is dropped; then the
    # second rule with d sharded would not match the same path, so we give
    # vocab-first spec with d fallback baked in via tuple-of-options below)
    (r"embed/table$", [MODEL_AXIS, None]),
    (r"lm_head/kernel$", [None, MODEL_AXIS]),
    # attention: out-features of q/k/v over model, in-features of o
    (r"(attn|self_attn|cross_attn|shared_attn)/w[qkv]/kernel$",
     [None, MODEL_AXIS]),
    (r"(attn|self_attn|cross_attn|shared_attn)/w[qkv]/bias$", [MODEL_AXIS]),
    (r"(attn|self_attn|cross_attn|shared_attn)/wo/kernel$",
     [MODEL_AXIS, None]),
    # dense MLPs
    (r"mlp/wi(_gate|_up)?/kernel$", [None, MODEL_AXIS]),
    (r"mlp/wo/kernel$", [MODEL_AXIS, None]),
    (r"mlp/wi/bias$", [MODEL_AXIS]),
    # MoE: expert-parallel over model
    (r"moe/router/kernel$", [None, None]),
    (r"moe/wi_(gate|up)$", [MODEL_AXIS, None, None]),
    (r"moe/wo$", [MODEL_AXIS, None, None]),
    # Mamba2 / mLSTM inner projections
    (r"(mamba|mlstm)/in_proj/kernel$", [None, MODEL_AXIS]),
    (r"(mamba|mlstm)/(out_proj|down)/kernel$", [MODEL_AXIS, None]),
    (r"mlstm/(up|up_gate|wq|wk|wv|w_if)/kernel$", [None, MODEL_AXIS]),
    # everything else replicated
]


def param_path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_specs(params, *, extra_leading_dims: int = 0):
    """PartitionSpec pytree for a parameter tree.

    `extra_leading_dims` accounts for scan-stacked layer dims (the leading
    (L,) axis of stacked block params is never sharded).
    """
    sizes = _mesh_axis_sizes()

    def spec_for(path, leaf):
        pstr = param_path_str(path)
        ndim = leaf.ndim
        lead = 0
        # stacked layer axes: any path under "layers"/"blocks" has one
        if re.search(r"(^|/)(layers|blocks|encoder_layers|superblocks|"
                     r"tail_blocks)(/|$)", pstr):
            lead = 1
        for pattern, axes in _PARAM_RULES:
            if re.search(pattern, pstr):
                body = axes
                if lead + len(body) != ndim:
                    # rule arity mismatch (e.g. stacked bias): best effort
                    body = axes[-(ndim - lead):] if ndim > lead else []
                full = [None] * lead + list(body)
                if _FSDP and ndim - lead >= 2:
                    # shard the first free dim over the data axes
                    for i in range(lead, ndim):
                        if full[i] is None:
                            full[i] = BATCH_AXES
                            break
                if not sizes:
                    return P()
                return _resolve(full, leaf.shape, sizes)
        full = [None] * ndim
        if _FSDP and ndim - lead >= 2:
            full[lead] = BATCH_AXES
        return P() if not sizes else _resolve(full, leaf.shape, sizes)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def cache_specs(cache):
    """KV/state caches: batch dim over pod+data, head dims over model.

    Caches are scan-stacked over layers: leaves are (L, B, H, S, D) KV
    rings, (L, B, H, s, d) SSM states, (L, B, W, C) conv buffers, or
    scalar lengths. The stacked layer dim is never sharded.
    """
    sizes = _mesh_axis_sizes()

    model_size = sizes.get(MODEL_AXIS, 1)

    def spec_for(path, leaf):
        if not sizes:
            return P()
        ndim = leaf.ndim
        if ndim <= 1:
            return P() if ndim == 0 else _resolve([None], leaf.shape, sizes)
        axes: list = [None, BATCH_AXES] + [None] * (ndim - 2)
        # (L, B, H, S, D) KV rings / (L, B, H, s, d) SSM states: shard the
        # first trailing dim the model axis divides — heads when possible,
        # else sequence (ring decode = sequence-parallel attention), else
        # the state dim (mLSTM matrix memories with few heads).
        for d in range(2, ndim):
            if leaf.shape[d] % model_size == 0 and leaf.shape[d] >= \
                    model_size:
                axes[d] = MODEL_AXIS
                break
        return _resolve(axes, leaf.shape, sizes)

    return jax.tree_util.tree_map_with_path(spec_for, cache)
