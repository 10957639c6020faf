"""Mixture-of-Experts layer with sort-based capacity dispatch.

Design notes
------------
* Dispatch is scatter/gather based (argsort by expert id), NOT one-hot einsum:
  the one-hot formulation adds O(T * E * C * d) fake FLOPs that would dominate
  the roofline for 64-128 expert models.  Here compute is exactly
  ``2 * 3 * E * C * d * ff`` with ``E*C ~= top_k * T * capacity_factor``
  (the true active-FLOPs of a capacity-bounded MoE).
* Experts are stacked on a leading E axis -> sharded over the "model" mesh axis
  (expert parallelism).  Tokens routed over capacity are dropped (standard
  capacity-factor semantics); the load-balancing auxiliary loss keeps routing
  near-uniform.
* Router math in f32.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.axes import constrain
from repro.models.config import ModelConfig, MoEConfig
from repro.models.layers import Params, _dense_init, init_mlp, mlp


def moe_capacity(m: MoEConfig, n_tokens: int) -> int:
    cap = int(n_tokens * m.top_k * m.capacity_factor / m.n_routed)
    return max(8, -(-cap // 8) * 8)  # round up to multiple of 8


def init_moe(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    """The router over every routed expert, and the held experts' weights.

    Each routed expert draws from a key of its own, so a share of the
    experts holds what the whole layer holds at those indices.  An expert
    matrix's fan-in is its own input width (d for gate and up, ff for down).
    The router is in the weights' dtype, as AdamW returns it."""
    m = cfg.moe
    d, ff, E = cfg.d_model, m.d_ff_expert, m.n_routed
    ks = jax.random.split(key, 5)

    def experts(k, shape):
        held = jax.random.split(k, E)[m.first_held:m.first_held + m.held]
        return jax.vmap(lambda kk: _dense_init(kk, shape, dtype))(held)

    p = {
        "router": _dense_init(ks[0], (d, E), dtype, scale=0.02),
        "w_gate": experts(ks[1], (d, ff)),
        "w_up": experts(ks[2], (d, ff)),
        "w_down": experts(ks[3], (ff, d)),
    }
    if m.n_shared > 0:
        p["shared"] = init_mlp(ks[4], d, m.n_shared * ff, dtype)
    return p


def zero_stats() -> dict:
    """An expert layer's stats before any layer: its balance loss and the
    counters of its held experts' assignments."""
    return {"aux": jnp.float32(0.0), "routed": jnp.int32(0),
            "dropped": jnp.int32(0), "load_max": jnp.int32(0)}


def add_stats(total: dict, part: dict) -> dict:
    """Stats over layers: the losses and counts summed, the largest load kept."""
    return {k: jnp.maximum(total[k], v) if k == "load_max" else total[k] + v
            for k, v in part.items()}


def _dispatch_ffn_combine(xf, top_w, top_i, w_gate, w_up, w_down,
                          m: MoEConfig, C: int, e0) -> Tuple[jnp.ndarray, dict]:
    """Sort-based dispatch + expert FFN + weighted combine for the LOCAL
    expert block [e0, e0+Eb) over the LOCAL token shard.

    xf: (N, d); top_w/top_i: (N, K); w_*: (Eb, d, f)/(Eb, f, d).
    Returns the partial output (N, d) f32 (zeros for tokens whose expert is
    outside this block) — the caller sums partials over the expert axis —
    and the block's counters: assignments routed to it, those dropped over
    capacity (each expert keeps its first ``C`` in token order), and its
    largest per-expert load.
    """
    N, d = xf.shape
    K = top_w.shape[1]
    Eb = w_gate.shape[0]
    E = m.n_routed

    with jax.named_scope("moe.dispatch"):
        flat_e = top_i.reshape(-1)                                      # (N*K,)
        flat_w = top_w.reshape(-1)
        tok = jnp.arange(N * K, dtype=jnp.int32) // K
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
        starts = jnp.cumsum(counts) - counts                            # (E,)
        pos_sorted = jnp.arange(N * K, dtype=jnp.int32) - starts[sorted_e]
        pos = jnp.zeros((N * K,), jnp.int32).at[order].set(pos_sorted)  # slot in expert
        local_e = flat_e - e0
        held = (local_e >= 0) & (local_e < Eb)
        keep = (pos < C) & held
        slot = jnp.where(keep, local_e * C + pos, Eb * C)               # OOB -> dropped

        buf = jnp.zeros((Eb * C, d), xf.dtype).at[slot].set(xf[tok], mode="drop")
        eb = buf.reshape(Eb, C, d)
        stats = {"routed": jnp.sum(held, dtype=jnp.int32),
                 "dropped": jnp.sum(held & (pos >= C), dtype=jnp.int32),
                 "load_max": jnp.max(jax.lax.dynamic_slice(counts, (e0,), (Eb,)))}

    # ---- expert FFN (active FLOPs only) ------------------------------------
    with jax.named_scope("moe.experts"):
        g = jnp.einsum("ecd,edf->ecf", eb, w_gate)
        u = jnp.einsum("ecd,edf->ecf", eb, w_up)
        h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(xf.dtype)
        y = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(Eb * C, d)

    # ---- combine ------------------------------------------------------------
    with jax.named_scope("moe.combine"):
        safe_slot = jnp.where(keep, slot, 0)
        gathered = y[safe_slot].astype(jnp.float32) * (flat_w * keep)[:, None]
        out = jnp.zeros((N, d), jnp.float32).at[tok].add(gathered)
    return out, stats


def _routing(p: Params, m: MoEConfig, xf: jnp.ndarray, n_seq: int = 1):
    """Router softmax over every routed expert, greedy top-k, and the
    balance loss: Switch-style over the batch, or with ``seq_aux`` per
    sequence (DeepSeek-V2's sequence-wise loss, without its weight):
    ``mean_b sum_i f_bi P_bi`` with ``f_bi = E / (K T) * count_bi`` and
    ``P_bi`` the sequence's mean score.  ``xf`` holds ``n_seq`` sequences
    one after another."""
    N = xf.shape[0]
    E, K = m.n_routed, m.top_k
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, K)                             # (N, K)
    if m.router_norm_topk:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-9)
    if m.seq_aux:
        T = N // n_seq
        count = jnp.zeros((n_seq, E), jnp.float32).at[
            jnp.arange(n_seq)[:, None], top_i.reshape(n_seq, T * K)].add(1.0)
        score = jnp.mean(probs.reshape(n_seq, T, E), axis=1)
        aux_loss = jnp.mean(jnp.sum(count * (E / (K * T)) * score, axis=-1))
    else:
        me = jnp.mean(probs, axis=0)                                    # (E,)
        ce = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (N * K)
        aux_loss = E * jnp.sum(me * ce)
    return top_w, top_i, aux_loss


def moe_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                ) -> Tuple[jnp.ndarray, dict]:
    """x: (B, T, d) -> (out (B, T, d), stats): the balance loss ``aux`` and
    the held experts' counters (``zero_stats``'s keys).

    The layer holds experts ``[first_held, first_held + held)`` of
    ``n_routed``: it routes over all of them and adds its own experts' part
    of the result, and the shared experts whole.

    Two paths:
      * sharded (production, active when a mesh/logical-rules context is
        installed): explicit shard_map — tokens stay sharded over the DP axes,
        experts over "model" (EP).  Each device routes ITS tokens, builds the
        dispatch buffer for ITS expert block only (capacity is per token
        shard), runs the block's FFN, and the partial outputs are psum'd over
        the expert axis.  No full-batch buffer is ever replicated — under
        plain GSPMD the scatter/gather dispatch was replicated per device
        (measured 145 GB/device at prefill_32k).
      * dense (single device): same math on the full batch, for the held
        block.
    """
    m = cfg.moe
    B, T, d = x.shape
    N = B * T

    sharded = _sharded_moe_context(N)
    if sharded is not None:
        mesh, dp_axes = sharded
        out, stats = _moe_forward_shardmap(p, cfg, x, mesh, dp_axes)
    else:
        xf = x.reshape(N, d)
        with jax.named_scope("moe.route"):
            top_w, top_i, aux_loss = _routing(p, m, xf, B)
        C = moe_capacity(m, N)
        out, stats = _dispatch_ffn_combine(
            xf, top_w, top_i, p["w_gate"], p["w_up"], p["w_down"], m, C,
            e0=m.first_held)
        out = out.astype(x.dtype).reshape(B, T, d)
        stats["aux"] = aux_loss

    if m.n_shared > 0:
        with jax.named_scope("moe.shared"):
            out = out + mlp(p["shared"], x)
    return out, stats


def _sharded_moe_context(n_tokens: int):
    """Use the shard_map path iff logical rules are installed, the mesh has a
    'model' axis, and the token count divides evenly over the DP axes."""
    from repro.models import axes as AX
    active = AX.current_rules()
    if active is None:
        return None
    mesh, rules = active
    if "model" not in mesh.shape:
        return None
    bax = rules.get("batch")
    dp_axes = tuple() if bax is None else (
        bax if isinstance(bax, tuple) else (bax,))
    import numpy as _np
    dp = int(_np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    if n_tokens % max(dp, 1):
        return None
    return mesh, dp_axes


def _moe_forward_shardmap(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                          mesh, dp_axes) -> Tuple[jnp.ndarray, dict]:
    from jax.sharding import PartitionSpec as P
    m = cfg.moe
    B, T, d = x.shape
    N = B * T
    ep = mesh.shape["model"]
    assert m.held % ep == 0, (m.held, ep)
    Eb = m.held // ep
    import numpy as _np
    dp = int(_np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    C_local = moe_capacity(m, N // dp)
    dp_spec = dp_axes if len(dp_axes) != 1 else dp_axes[0]
    n_seq = max(B // dp, 1)

    def inner(xf, router, w_gate, w_up, w_down):
        # xf: (N/dp, d) local tokens; w_*: (Eb, ...) local expert block
        top_w, top_i, aux = _routing({"router": router}, m, xf, n_seq)
        e0 = m.first_held + jax.lax.axis_index("model") * Eb
        partial, stats = _dispatch_ffn_combine(xf, top_w, top_i, w_gate, w_up,
                                               w_down, m, C_local, e0)
        out = jax.lax.psum(partial, "model")            # combine expert blocks
        every = ("model",) + tuple(dp_axes)
        stats = {"routed": jax.lax.psum(stats["routed"], every),
                 "dropped": jax.lax.psum(stats["dropped"], every),
                 "load_max": jax.lax.pmax(stats["load_max"], every)}
        # aux identical across 'model' (same tokens); average over DP shards
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        stats["aux"] = aux
        return out, stats

    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(dp_spec, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp_spec, None), P()),
        check_vma=False)
    out, stats = fn(x.reshape(N, d), p["router"], p["w_gate"], p["w_up"],
                    p["w_down"])
    return out.astype(x.dtype).reshape(B, T, d), stats
