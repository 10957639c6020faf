"""Sharding rules: parameter specs, optimizer-state specs, cache specs, and
input specs for every (arch × shape × mesh) combination.

Strategy (baseline — EXPERIMENTS.md §Perf iterates from here):
  * TP on "model": attention projections, FFN hidden, experts (EP), vocab.
  * DP on ("pod","data"): batch.  Cross-pod is pure DP (grad all-reduce over
    the slow axis — where grad compression applies).
  * FSDP/ZeRO on "data": parameters of ≥3B models are sharded over "data" on
    their non-TP dimension; optimizer moments always are (ZeRO-1).
  * KV caches: batch over ("pod","data"); kv-head dim over "model" when
    divisible, else the sequence dim over "model" (sequence-parallel cache).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig, param_count

PyTree = Any

FSDP_THRESHOLD = 3e9


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


# -------------------------------------------------------------- param specs
# (regex on the path suffix, spec builder taking (ndim, fsdp_axis))
def _mat(in_ax, out_ax):
    """Spec for a (..., in, out) matrix; leading dims are stacked layers."""
    def build(ndim, fsdp):
        lead = (None,) * (ndim - 2)
        ia = fsdp if in_ax == "fsdp" else in_ax
        oa = fsdp if out_ax == "fsdp" else out_ax
        return P(*lead, ia, oa)
    return build


def _vec(ax):
    def build(ndim, fsdp):
        lead = (None,) * (ndim - 1)
        return P(*lead, ax)
    return build


def _moe_expert(in_ax, out_ax):
    """(..., E, in, out): experts over 'model' (EP)."""
    def build(ndim, fsdp):
        lead = (None,) * (ndim - 3)
        ia = fsdp if in_ax == "fsdp" else in_ax
        oa = fsdp if out_ax == "fsdp" else out_ax
        return P(*lead, "model", ia, oa)
    return build


_PARAM_RULES = [
    (r"embed$", lambda nd, f: P(*((None,) * (nd - 2)), "model", None)),
    (r"lm_head$", lambda nd, f: P(*((None,) * (nd - 2)), None, "model")),
    (r"attn/wq$", _mat("fsdp", "model")),
    (r"attn/wk$", _mat("fsdp", "model")),
    (r"attn/wv$", _mat("fsdp", "model")),
    (r"attn/wo$", _mat("model", "fsdp")),
    (r"attn/w_dkv$", _mat("fsdp", None)),
    (r"attn/w_krope$", _mat("fsdp", None)),
    (r"attn/w_uk$", _mat(None, "model")),
    (r"attn/w_uv$", _mat(None, "model")),
    (r"(mlp|shared)/w_gate$", _mat("fsdp", "model")),
    (r"(mlp|shared)/w_up$", _mat("fsdp", "model")),
    (r"(mlp|shared)/w_down$", _mat("model", "fsdp")),
    (r"moe/router$", _mat(None, None)),
    (r"moe/w_gate$", _moe_expert("fsdp", None)),
    (r"moe/w_up$", _moe_expert("fsdp", None)),
    (r"moe/w_down$", _moe_expert(None, "fsdp")),
    (r"ssm/in_[xz]$", _mat("fsdp", "model")),
    (r"ssm/in_[BC]$", _mat("fsdp", None)),
    (r"ssm/in_dt$", _mat("fsdp", None)),
    (r"ssm/x_proj$", _mat("model", None)),
    (r"ssm/dt_proj$", _mat(None, "model")),
    (r"ssm/out_proj$", _mat("model", "fsdp")),
    (r"ssm/A_log$", lambda nd, f: P(*((None,) * (nd - 2)), "model", None)
        if nd >= 2 else P(*((None,) * (nd - 1)), None)),
    (r"ssm/conv_x_w$", lambda nd, f: P(*((None,) * (nd - 1)), "model")),
    (r"ssm/conv_x_b$", _vec("model")),
    (r"ssm/(conv_[BC]_[wb]|conv_w|conv_b|dt_bias|D)$",
     lambda nd, f: P(*((None,) * nd))),
    (r"(scale|norm/scale|ln\d?/scale|.*norm.*)$", lambda nd, f: P(*((None,) * nd))),
]


def param_specs(shapes: PyTree, cfg: ModelConfig, mesh: Mesh) -> PyTree:
    """PartitionSpec tree matching the param tree (shapes = eval_shape out)."""
    total, _ = param_count(cfg)
    fsdp = "data" if total >= FSDP_THRESHOLD else None
    tp = mesh.shape.get("model", 1)
    dp = mesh.shape.get("data", 1)

    def assign(path, leaf):
        ps = _path_str(path)
        for pat, builder in _PARAM_RULES:
            if re.search(pat, ps):
                spec = builder(leaf.ndim, fsdp)
                return _fix_divisibility(spec, leaf.shape, mesh)
        return P(*((None,) * leaf.ndim))   # default: replicate

    return jax.tree_util.tree_map_with_path(assign, shapes)


def _fix_divisibility(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop axis assignments whose mesh size does not divide the dim."""
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(ax if dim % size == 0 else None)
    return P(*out)


def opt_state_specs(param_spec_tree: PyTree, shapes: PyTree, mesh: Mesh,
                    params_shapes: PyTree) -> Dict[str, PyTree]:
    """ZeRO-1: master/m/v follow the param spec, with 'data' added on the
    first unsharded divisible dim when the param itself is not data-sharded."""
    dp = mesh.shape.get("data", 1)

    def zero1(spec, shape_leaf):
        spec_t = tuple(spec) + (None,) * (shape_leaf.ndim - len(tuple(spec)))
        used = set()
        for ax in spec_t:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                used.add(a)
        if "data" in used:
            return P(*spec_t)
        out = list(spec_t)
        for i, (dim, ax) in enumerate(zip(shape_leaf.shape, spec_t)):
            if ax is None and dim % dp == 0 and dim >= dp:
                out[i] = "data"
                break
        return P(*out)

    moment_spec = jax.tree_util.tree_map(
        zero1, param_spec_tree, params_shapes,
        is_leaf=lambda x: isinstance(x, P))
    return moment_spec


# --------------------------------------------------------------- cache specs
def cache_specs(cache_shapes: PyTree, batch: int, seq: int, mesh: Mesh,
                batch_ax) -> PyTree:
    """Shape-driven assignment: batch dim -> batch_ax; then shard heads over
    'model' if divisible, else the sequence dim over 'model'."""
    tp = mesh.shape.get("model", 1)

    def bsz(ax):
        if ax is None:
            return 1
        axes = ax if isinstance(ax, tuple) else (ax,)
        return int(np.prod([mesh.shape[a] for a in axes]))

    def assign(path, leaf):
        dims = list(leaf.shape)
        spec = [None] * leaf.ndim
        # batch: first dim equal to `batch` after the leading stack dims
        b_idx = None
        for i, d in enumerate(dims):
            if d == batch and i <= 2:
                b_idx = i
                break
        if b_idx is not None and batch_ax is not None \
                and batch % bsz(batch_ax) == 0:
            spec[b_idx] = batch_ax
        # model axis: prefer a head-like dim (divisible, not batch/seq),
        # searching from the last dim backwards; else the seq dim
        s_idx = None
        for i, d in enumerate(dims):
            if d == seq and i != b_idx:
                s_idx = i
                break
        for i in range(leaf.ndim - 1, -1, -1):
            if i in (b_idx, s_idx):
                continue
            if dims[i] % tp == 0 and dims[i] >= tp:
                spec[i] = "model"
                break
        else:
            if s_idx is not None and dims[s_idx] % tp == 0:
                spec[s_idx] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(assign, cache_shapes)


# --------------------------------------------------------------- input specs
def batch_axis(mesh: Mesh, global_batch: int):
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if not axes:
        return None
    size = int(np.prod([mesh.shape[a] for a in axes]))
    if global_batch % size == 0:
        return axes if len(axes) > 1 else axes[0]
    # try data only
    if "data" in mesh.shape and global_batch % mesh.shape["data"] == 0:
        return "data"
    return None


def logical_rules(mesh: Mesh, global_batch: int,
                  cfg: Optional[ModelConfig] = None) -> Dict[str, Any]:
    """Logical-axis rules.  Head sharding is enabled only when the KV-head
    count divides the TP axis (otherwise the (Hkv, g) reshape would misalign
    shard boundaries and GSPMD would gather); the ff / ssm-channel / expert
    constraints are divisibility-guarded per-tensor in axes.constrain."""
    tp = mesh.shape.get("model", 1)
    heads_ok = cfg is not None and (
        (cfg.mla is not None and cfg.n_heads % tp == 0)
        or (cfg.mla is None and cfg.n_kv_heads > 0
            and cfg.n_kv_heads % tp == 0))
    rules = {
        "batch": batch_axis(mesh, global_batch),
        "seq": None,
        "vocab": "model",
        "expert": "model",
        "ff": "model",
        "heads": "model" if heads_ok else None,
        "kv": "model" if heads_ok else None,
        "ssm_ch": "model",
        "ssm_heads": "model",
    }
    return rules
