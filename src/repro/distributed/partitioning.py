"""Path- and config-aware parameter / activation / cache partitioning.

Maps every leaf of the model state onto the production mesh
(``(pod, data, model)`` multi-pod or ``(data, model)`` single-pod).

**Divisibility-first**: explicit jit shardings must divide exactly (no
GSPMD padding for arguments), and the assigned archs have awkward head /
expert / vocab counts.  Every rule therefore checks divisibility against
the mesh and falls back along a documented chain:

* attention — Megatron head-parallel when the kv-head or query-group
  axis divides the ``model`` axis (recurrentgemma: G=16); otherwise the
  weights replicate over ``model`` and the *sequence* axis of attention
  activations is model-sharded instead (context-parallel style, applied
  by a ``ctx.constrain`` inside the block).  Decode shards the KV-cache
  *sequence* dimension over ``model`` (flash-decode with GSPMD-inserted
  LSE combine).
* MoE — expert-parallel over ``model`` when E divides; otherwise
  Megatron *within* each expert (per-expert d_ff sharded).
* FFN / RG-LRU — classic column/row (Megatron) over ``model``.
* embeddings — vocab padded to a multiple of 256 in-model
  (``ModelConfig.padded_vocab``) then vocab-sharded over ``model``.
* ``fsdp_units`` (llama4) — stacked unit params additionally shard their
  first free divisible dim over ``data`` (ZeRO-3 storage; the scan body
  all-gathers one unit per step, overlapping layer compute).
* ZeRO-1 — optimizer moments/master shard their first free divisible
  dim over ``data``.
* xLSTM mixers — pure DP (tiny weights replicate; 4 heads over 16 would
  not divide anyway); ZeRO-1 still applies.

Design rule inherited from the paper (DESIGN.md §2.1): don't serialise
independent resources on one budget — FSDP weight-gather rides ``data``
while tensor-parallel collectives ride ``model``; the two overlap.
"""

from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.transformer import LayerSpec, ModelConfig

MODEL_AXIS = "model"
FSDP_AXIS = "data"


def axis_size(mesh, name: str) -> int:
    return dict(mesh.shape)[name]   # works for Mesh and AbstractMesh


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(n for n in mesh.axis_names if n != MODEL_AXIS)


def _layer_spec_for(cfg: ModelConfig, path: str) -> LayerSpec | None:
    m = re.search(r"unit/layer(\d+)", path)
    if m:
        return cfg.pattern[int(m.group(1))]
    m = re.search(r"tail/tail(\d+)", path)
    if m:
        return cfg.tail[int(m.group(1))]
    return None


def _attn_param_spec(cfg: ModelConfig, name: str, tp: int) -> P:
    kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    if kvh % tp == 0:
        kv, gq = MODEL_AXIS, None
    elif g % tp == 0:
        kv, gq = None, MODEL_AXIS
    else:  # replicated weights; sequence-sharded activations instead
        kv = gq = None
    return {
        "wq": P(None, kv, gq, None),
        "wk": P(None, kv, None),
        "wv": P(None, kv, None),
        "wo": P(kv, gq, None, None),
        "bq": P(kv, gq, None),
        "bk": P(kv, None),
        "bv": P(kv, None),
        "bo": P(None),
    }[name]


def _rglru_spec(cfg: ModelConfig, name: str, tp: int) -> P:
    r = cfg.rglru.d_rnn if cfg.rglru else 0
    h = cfg.rglru.n_heads if cfg.rglru else 0
    rm = MODEL_AXIS if r % tp == 0 else None
    hm = MODEL_AXIS if h % tp == 0 else None
    return {
        "wx": P(None, rm), "wy": P(None, rm), "wo": P(rm, None),
        "conv_w": P(None, rm), "conv_b": P(rm),
        "a_gate": P(hm, None, None), "x_gate": P(hm, None, None),
        "a_bias": P(rm), "x_bias": P(rm), "lambda": P(rm),
    }[name]


def _ffn_spec(cfg: ModelConfig, name: str, tp: int) -> P:
    fm = MODEL_AXIS if cfg.d_ff % tp == 0 else None
    return {
        "wi": P(None, fm), "wg": P(None, fm), "wo": P(fm, None),
        "bi": P(fm), "bo": P(None),
    }[name]


def _moe_spec(cfg: ModelConfig, name: str, tp: int) -> P:
    """Expert-parallel when E divides the TP axis; otherwise *capacity-slot*
    parallel: weights replicate (non-divisible expert counts are small
    models) and the [G, E, C, d] dispatch buffer shards its slot axis over
    ``model`` via an activation constraint in ``apply_moe`` — every expert
    einsum stays local and the only collective is the post-combine
    all-reduce of [G, T, d] (same cost as a Megatron FFN).  The previous
    megatron-within-expert fallback (d_ff sharded) forced GSPMD to
    all-reduce the [G, E, C, f] intermediate — ~60× more collective bytes
    (EXPERIMENTS.md §Perf, hillclimb H1)."""
    e = cfg.moe.n_experts
    sf = cfg.moe.shared_d_ff
    sm = MODEL_AXIS if sf % tp == 0 and sf else None
    if cfg.moe_shard_mode == "e_data_f_model":
        # perf variant: experts sharded over 'data' in storage AND compute;
        # GSPMD moves tokens (a2a) instead of gathering expert weights.
        return {
            "router": P(None, None),
            "wi": P(FSDP_AXIS, None, MODEL_AXIS),
            "wg": P(FSDP_AXIS, None, MODEL_AXIS),
            "wo": P(FSDP_AXIS, MODEL_AXIS, None),
            "shared_wi": P(None, sm), "shared_wg": P(None, sm),
            "shared_wo": P(sm, None),
        }[name]
    if cfg.moe_shard_mode == "f_model":
        # legacy megatron-within-expert fallback, kept selectable so the
        # H1 hillclimb baseline stays reproducible (EXPERIMENTS.md §Perf)
        fm = MODEL_AXIS if cfg.moe.d_ff % tp == 0 else None
        return {
            "router": P(None, None),
            "wi": P(None, None, fm), "wg": P(None, None, fm), "wo": P(None, fm, None),
            "shared_wi": P(None, sm), "shared_wg": P(None, sm),
            "shared_wo": P(sm, None),
        }[name]
    ew = MODEL_AXIS if e % tp == 0 else None
    return {
        "router": P(None, None),
        "wi": P(ew, None, None), "wg": P(ew, None, None), "wo": P(ew, None, None),
        "shared_wi": P(None, sm), "shared_wg": P(None, sm), "shared_wo": P(sm, None),
    }[name]


def _leaf_param_spec(cfg: ModelConfig, path: str, ndim: int, tp: int) -> P:
    """Spec for the *unstacked* view of the leaf (``ndim`` excludes any
    leading unit axis)."""
    name = path.rsplit("/", 1)[-1]
    if path.startswith("embed/"):
        return P(MODEL_AXIS, None)   # vocab padded to ×256 => always divides
    if path.startswith("head/"):
        return P(None, MODEL_AXIS)
    if "norm" in path or path.startswith("final_norm"):
        return P(*([None] * ndim))
    spec = _layer_spec_for(cfg, path)
    if spec is None:
        return P(*([None] * ndim))
    if "/mixer/" in path:
        if spec.mixer == "attn":
            return _attn_param_spec(cfg, name, tp)
        if spec.mixer == "rglru":
            return _rglru_spec(cfg, name, tp)
        return P(*([None] * ndim))   # mlstm/slstm: replicated (pure DP)
    if "/ffn/" in path:
        if spec.ffn == "moe":
            return _moe_spec(cfg, name, tp)
        return _ffn_spec(cfg, name, tp)
    return P(*([None] * ndim))


def _path_str(key_path) -> str:
    parts = []
    for k in key_path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _insert_axis(spec: P, shape: tuple[int, ...], axis: str, divisor: int,
                 start_dim: int = 0) -> P:
    """Add ``axis`` on the first free exactly-divisible dim ≥ start_dim.
    No-op if the axis already shards some dim (a mesh axis may appear in
    at most one position of a PartitionSpec)."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for e in parts:
        used = (e,) if isinstance(e, str) or e is None else tuple(e)
        if axis in used:
            return P(*parts)
    for i in range(start_dim, len(shape)):
        if parts[i] is None and shape[i] % divisor == 0 and shape[i] > 1:
            parts[i] = axis
            return P(*parts)
    return P(*parts)


def param_pspecs(cfg: ModelConfig, mesh: Mesh, params_shape: Any) -> Any:
    """PartitionSpec pytree matching ``params_shape`` (arrays or SDS)."""
    tp = axis_size(mesh, MODEL_AXIS)
    fsdp = axis_size(mesh, FSDP_AXIS)

    def spec_of(key_path, leaf):
        path = _path_str(key_path)
        stacked = path.startswith("unit/")
        base = _leaf_param_spec(cfg, path, leaf.ndim - (1 if stacked else 0), tp)
        if stacked:
            base = P(None, *base)     # stacked unit axis in front
            if cfg.fsdp_units:
                base = _insert_axis(base, leaf.shape, FSDP_AXIS, fsdp, start_dim=1)
        elif cfg.fsdp_units and not path.startswith(("embed/", "head/")):
            base = _insert_axis(base, leaf.shape, FSDP_AXIS, fsdp)
        return base

    return jax.tree_util.tree_map_with_path(spec_of, params_shape)


def zero1_spec(spec: P, shape: tuple[int, ...], divisor: int) -> P:
    """Extra 'data' sharding for optimizer state (first free divisible dim)."""
    return _insert_axis(spec, shape, FSDP_AXIS, divisor)


# ---------------------------------------------------------------------------
# activations / batches / caches
# ---------------------------------------------------------------------------


def batch_axes(mesh, batch_size: int) -> tuple[str, ...] | None:
    """DP axes to shard a batch dim over (largest prefix that divides)."""
    axes = dp_axes(mesh)
    sizes = dict(mesh.shape)
    for cand in (axes, axes[1:] if len(axes) > 1 else ()):
        if not cand:
            continue
        prod = 1
        for a in cand:
            prod *= sizes[a]
        if batch_size % prod == 0:
            return cand
    return None


def activation_rules(cfg: ModelConfig, mesh: Mesh, batch_size: int) -> dict:
    """Logical-dim rules consumed by repro.distributed.ctx.

    'seq' maps to the model axis only when attention weights could NOT be
    head-sharded (context-parallel fallback); otherwise constraining the
    sequence would conflict with Megatron head parallelism.
    """
    tp = axis_size(mesh, MODEL_AXIS)
    g = cfg.n_heads // cfg.n_kv_heads
    head_tp = (cfg.n_kv_heads % tp == 0) or (g % tp == 0)
    moe_slot = cfg.moe is not None and cfg.moe.n_experts % tp != 0
    return {"batch": batch_axes(mesh, batch_size),
            "seq": None if head_tp else MODEL_AXIS,
            "moe_cap": MODEL_AXIS if moe_slot else None}


def batch_pspecs(cfg: ModelConfig, mesh: Mesh, batch: Any) -> Any:
    """Specs for a train/prefill batch dict (leading batch dim sharded).
    ``position_ids`` has layout [3, B, S] — batch on axis 1."""

    def spec_of(key_path, leaf):
        path = _path_str(key_path)
        bdim = 1 if path.endswith("position_ids") else 0
        axes = batch_axes(mesh, leaf.shape[bdim])
        parts: list = [None] * leaf.ndim
        parts[bdim] = axes
        return P(*parts)

    return jax.tree_util.tree_map_with_path(spec_of, batch)


def cache_pspecs(cfg: ModelConfig, mesh: Mesh, cache_shape: Any) -> Any:
    """Decode-state specs: batch over DP; long (seq / width) dims over model.

    KV caches shard the *sequence* slot axis over ``model`` (flash-decode:
    GSPMD inserts the log-sum-exp style combine for the sharded-softmax);
    recurrent states shard their feature width when divisible.
    """
    tp = axis_size(mesh, MODEL_AXIS)

    def spec_of(key_path, leaf):
        path = _path_str(key_path)
        stacked = path.startswith("unit/")
        name = path.rsplit("/", 1)[-1]
        dims: list = [None] * leaf.ndim
        bdim = 1 if stacked else 0
        dims[bdim] = batch_axes(mesh, leaf.shape[bdim])
        if name in ("k", "v"):                       # [.., B, kvH, S, Dh]
            if leaf.shape[bdim + 2] % tp == 0:
                dims[bdim + 2] = MODEL_AXIS
        elif name == "pos":                          # [.., B, S]
            if leaf.shape[bdim + 1] % tp == 0:
                dims[bdim + 1] = MODEL_AXIS
        elif name in ("h", "c", "n", "m", "C", "conv"):
            if leaf.shape[-1] % tp == 0 and leaf.shape[-1] > 1:
                dims[-1] = MODEL_AXIS
        return P(*dims)

    return jax.tree_util.tree_map_with_path(spec_of, cache_shape)


def shardings(mesh: Mesh, pspecs: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))
