"""Transformer building blocks: RMSNorm, RoPE, GQA/MQA/MLA attention, dense
and MoE feed-forward.  Pure functional JAX — params are nested dicts.

All matmul-bearing ops accept a ``dtype`` for activations (bf16 on TPU) and
keep params in fp32 (mixed-precision convention); reductions (softmax, norm)
run in fp32.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LMConfig

__all__ = [
    "rmsnorm",
    "rope_frequencies",
    "apply_rope",
    "init_attention",
    "attention_apply",
    "init_ffn",
    "ffn_apply",
    "init_moe",
    "moe_apply",
]

Params = Dict[str, jnp.ndarray]


def _dense_init(key, shape, scale_axis=0):
    scale = 1.0 / np.sqrt(shape[scale_axis])
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


def rmsnorm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return ((xf / rms) * gamma).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, n_heads, d); positions: (..., seq)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # (d/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, d/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA / MQA / MLA)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: LMConfig) -> Params:
    d = cfg.d_model
    if cfg.attention == "mla":
        ks = jax.random.split(key, 6)
        qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return {
            "w_q": _dense_init(ks[0], (d, cfg.n_heads, qd)),
            "w_dkv": _dense_init(ks[1], (d, cfg.kv_lora_rank)),
            "w_krope": _dense_init(ks[2], (d, cfg.qk_rope_head_dim)),
            "w_uk": _dense_init(ks[3], (cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim)),
            "w_uv": _dense_init(ks[4], (cfg.kv_lora_rank, cfg.n_heads, cfg.v_head_dim)),
            "w_o": _dense_init(ks[5], (cfg.n_heads, cfg.v_head_dim, d), scale_axis=1),
            "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
        }
    ks = jax.random.split(key, 4)
    return {
        "w_q": _dense_init(ks[0], (d, cfg.n_heads, cfg.d_head)),
        "w_k": _dense_init(ks[1], (d, cfg.n_kv_heads, cfg.d_head)),
        "w_v": _dense_init(ks[2], (d, cfg.n_kv_heads, cfg.d_head)),
        "w_o": _dense_init(ks[3], (cfg.n_heads, cfg.d_head, d), scale_axis=1),
    }


def _sdpa_chunked(
    q: jnp.ndarray,  # (b, sq, h, d)
    k: jnp.ndarray,  # (b, sk, h_kv, d)
    v: jnp.ndarray,  # (b, sk, h_kv, dv)
    q_positions: jnp.ndarray,  # (sq,) absolute positions of queries
    kv_len: Optional[jnp.ndarray],  # scalar valid kv length (decode) or None (=sk)
    causal: bool,
    q_chunk: int,
) -> jnp.ndarray:
    """Query-chunked causal attention with fp32 softmax.

    Memory: O(q_chunk * sk) per chunk instead of O(sq * sk) — the XLA-level
    analogue of flash attention's outer loop (inner loop left to fusion).
    """
    b, sq, h, d = q.shape
    h_kv = k.shape[2]
    group = h // h_kv
    scale = 1.0 / np.sqrt(d)
    kv_pos = jnp.arange(k.shape[1])

    qg = q.reshape(b, sq, h_kv, group, d)

    def one_chunk(args):
        qc, qpos = args  # (b, c, h_kv, g, d), (c,)
        logits = jnp.einsum("bchgd,bshd->bchgs", qc.astype(jnp.float32), k.astype(jnp.float32)) * scale
        mask = jnp.ones((qc.shape[1], k.shape[1]), bool)
        if causal:
            mask = qpos[:, None] >= kv_pos[None, :]
        if kv_len is not None:
            mask = mask & (kv_pos[None, :] < kv_len)
        logits = jnp.where(mask[None, :, None, None, :], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bchgs,bshe->bchge", p, v.astype(jnp.float32))

    if sq <= q_chunk:
        out = one_chunk((qg, q_positions))
    else:
        pad = (-sq) % q_chunk
        if pad:
            qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
            q_positions = jnp.pad(q_positions, (0, pad))
        n_chunks = (sq + pad) // q_chunk
        qg_c = qg.reshape(b, n_chunks, q_chunk, h_kv, group, d).swapaxes(0, 1)
        pos_c = q_positions.reshape(n_chunks, q_chunk)
        out = jax.lax.map(one_chunk, (qg_c, pos_c))  # (n, b, c, h_kv, g, dv)
        out = out.swapaxes(0, 1).reshape(b, sq + pad, h_kv, group, v.shape[-1])[:, :sq]
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def attention_apply(
    params: Params,
    cfg: LMConfig,
    x: jnp.ndarray,  # (b, s, d)
    positions: jnp.ndarray,  # (s,)
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    cache_index: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Causal self-attention. With ``cache`` (decode), ``x`` is the new-token
    slice and ``cache_index`` the write offset; returns updated cache."""
    if cfg.attention == "mla":
        return _mla_apply(params, cfg, x, positions, cache, cache_index)

    q = jnp.einsum("bsd,dhe->bshe", x, params["w_q"].astype(x.dtype))
    k = jnp.einsum("bsd,dhe->bshe", x, params["w_k"].astype(x.dtype))
    v = jnp.einsum("bsd,dhe->bshe", x, params["w_v"].astype(x.dtype))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0))
        new_cache = {"k": k_cache, "v": v_cache}
        kv_len = cache_index + x.shape[1]
        out = _sdpa_chunked(q, k_cache, v_cache, positions, kv_len, causal=True, q_chunk=cfg.attn_q_chunk)
    elif cfg.attn_impl == "flash":
        # Pallas flash-attention kernel (interpret-mode on CPU hosts)
        from repro.kernels.flash_attention.ops import flash_attention

        interpret = jax.devices()[0].platform != "tpu"
        out = flash_attention(q, k, v, causal=True, interpret=interpret)
    else:
        out = _sdpa_chunked(q, k, v, positions, None, causal=True, q_chunk=cfg.attn_q_chunk)
    return jnp.einsum("bshe,hed->bsd", out, params["w_o"].astype(x.dtype)), new_cache


def _mla_apply(params, cfg: LMConfig, x, positions, cache, cache_index):
    """DeepSeek-V2 Multi-head Latent Attention.

    KV state is compressed to ``c_kv`` (kv_lora_rank) + a shared rope key —
    only those are cached; per-head K/V are decompressed on the fly.
    """
    b, s, d = x.shape
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    q = jnp.einsum("bsd,dhe->bshe", x, params["w_q"].astype(x.dtype))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    c_kv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"].astype(x.dtype))
    c_kv = rmsnorm(c_kv, params["kv_norm"], cfg.norm_eps)
    k_rope = jnp.einsum("bsd,de->bse", x, params["w_krope"].astype(x.dtype))
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    new_cache = None
    if cache is not None:
        c_cache = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, cache_index, 0))
        r_cache = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, cache_index, 0))
        new_cache = {"c_kv": c_cache, "k_rope": r_cache}
        c_all, r_all = c_cache, r_cache
        kv_len = cache_index + s
        causal = True
    else:
        c_all, r_all = c_kv, k_rope
        kv_len = None
        causal = True

    if cache is not None and s == 1:
        # ABSORBED decode (DeepSeek-V2 §2.1): fold w_uk into q and w_uv into
        # the output so attention runs entirely in the latent space — no
        # (b, s_kv, h, d) K/V decompression (17 GB/layer at 32k x 128).
        q_lat = jnp.einsum("bshe,rhe->bshr", q_nope, params["w_uk"].astype(x.dtype))
        logits_nope = jnp.einsum("bshr,btr->bhst", q_lat, c_all)
        logits_rope = jnp.einsum("bshe,bte->bhst", q_rope, r_all)
        scale = 1.0 / np.sqrt(dn + dr)
        logits_full = (logits_nope + logits_rope).astype(jnp.float32) * scale
        kv_pos = jnp.arange(c_all.shape[1])
        mask = kv_pos < kv_len  # (t,)
        logits_full = jnp.where(mask[None, None, None, :], logits_full, -1e30)
        p = jax.nn.softmax(logits_full, axis=-1)
        out_lat = jnp.einsum("bhst,btr->bshr", p.astype(x.dtype), c_all)
        out = jnp.einsum("bshr,rhe->bshe", out_lat, params["w_uv"].astype(x.dtype))
        return jnp.einsum("bshe,hed->bsd", out, params["w_o"].astype(x.dtype)), new_cache

    # Decompress K/V from the latent (prefill/train).
    k_nope = jnp.einsum("bsr,rhe->bshe", c_all, params["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsr,rhe->bshe", c_all, params["w_uv"].astype(x.dtype))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(r_all[:, :, None, :], (*r_all.shape[:2], h, dr))], axis=-1)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)

    out = _sdpa_chunked(qq, k, v, positions, kv_len, causal=causal, q_chunk=cfg.attn_q_chunk)
    return jnp.einsum("bshe,hed->bsd", out, params["w_o"].astype(x.dtype)), new_cache


# ---------------------------------------------------------------------------
# Feed-forward: dense + MoE
# ---------------------------------------------------------------------------


def init_ffn(key, d_model: int, d_ff: int, activation: str) -> Params:
    if activation in ("swiglu", "geglu"):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w_gate": _dense_init(k1, (d_model, d_ff)),
            "w_up": _dense_init(k2, (d_model, d_ff)),
            "w_down": _dense_init(k3, (d_ff, d_model)),
        }
    k1, k2 = jax.random.split(key, 2)
    return {"w_up": _dense_init(k1, (d_model, d_ff)), "w_down": _dense_init(k2, (d_ff, d_model))}


def _activate(gate: jnp.ndarray, up: Optional[jnp.ndarray], activation: str) -> jnp.ndarray:
    if activation == "swiglu":
        return jax.nn.silu(gate) * up
    if activation == "geglu":
        return jax.nn.gelu(gate) * up
    if activation == "squared_relu":  # Primer / Nemotron-4
        r = jax.nn.relu(gate)
        return r * r
    if activation == "gelu":  # GPT-BigCode / Granite-20B
        return jax.nn.gelu(gate)
    raise ValueError(f"unknown activation {activation!r}")


def ffn_apply(params: Params, activation: str, x: jnp.ndarray) -> jnp.ndarray:
    if activation in ("swiglu", "geglu"):
        h = _activate(
            x @ params["w_gate"].astype(x.dtype), x @ params["w_up"].astype(x.dtype), activation
        )
    else:
        h = _activate(x @ params["w_up"].astype(x.dtype), None, activation)
    return h @ params["w_down"].astype(x.dtype)


def init_moe(key, cfg: LMConfig) -> Params:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    gated = cfg.ffn_activation in ("swiglu", "geglu")
    params: Params = {
        "router": _dense_init(ks[0], (d, e)),
        "w_up": _dense_init(ks[1], (e, d, f)) / np.sqrt(1),
        "w_down": _dense_init(ks[2], (e, f, d)),
    }
    if gated:
        params["w_gate"] = _dense_init(ks[3], (e, d, f))
    if cfg.n_shared_experts:
        params["shared"] = init_ffn(ks[4], d, cfg.moe_d_ff * cfg.n_shared_experts, cfg.ffn_activation)
    return params


def _moe_apply_ep(params: Params, cfg: LMConfig, x: jnp.ndarray, act_spec) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map (the Switch/DeepSeek production path).

    Routing, position-cumsum, and capacity are **local per shard** (each
    device drops independently — standard EP semantics), eliminating the
    global-token cumsum/scatter of the pjit path.  Expert exchange is two
    ``all_to_all``s over the "model" axis:

        local buf (e, cap_l, d) --a2a--> (e/m, m*cap_l, d) -- expert FFN -->
        --a2a back--> (e, cap_l, d) --> local gather/combine.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    model_ax = "model"
    m_size = mesh.shape[model_ax]
    e, k = cfg.n_experts, cfg.moe_top_k
    gated = cfg.ffn_activation in ("swiglu", "geglu")

    def local_fn(router, w_up, w_gate, w_down, xl):
        b_l, s_l, d = xl.shape
        tokens = xl.reshape(b_l * s_l, d)
        t_l = b_l * s_l
        logits = tokens.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

        flat_e = expert_idx.reshape(-1)
        tokens_per_expert = (
            jax.ops.segment_sum(jnp.ones_like(flat_e, jnp.float32), flat_e, num_segments=e)
            / (t_l * k)
        )
        aux_local = e * jnp.sum(tokens_per_expert * probs.mean(0)) * cfg.router_aux_coef
        aux = jax.lax.pmean(aux_local, tuple(mesh.axis_names))

        cap_l = max(int(t_l * k * cfg.capacity_factor / e), 4)
        onehot_flat = jax.nn.one_hot(flat_e, e, dtype=jnp.float32)
        pos_flat = (
            (jnp.cumsum(onehot_flat, axis=0) - onehot_flat)[jnp.arange(t_l * k), flat_e]
        ).astype(jnp.int32)
        keep = pos_flat < cap_l
        safe_pos = jnp.where(keep, pos_flat, cap_l)
        gate_flat = gate_vals.reshape(-1) * keep

        tok_of_slot = jnp.arange(t_l * k) // k
        buf = jnp.zeros((e, cap_l + 1, d), dtype=xl.dtype)
        buf = buf.at[flat_e, safe_pos].add(tokens[tok_of_slot] * keep[:, None].astype(xl.dtype))
        buf = buf[:, :cap_l]

        # EP exchange: experts home to their shard
        buf = jax.lax.all_to_all(buf, model_ax, split_axis=0, concat_axis=1, tiled=True)
        # buf: (e/m, m*cap_l, d); w_up local: (e/m, d, f)
        up = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(xl.dtype))
        if gated:
            gh = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(xl.dtype))
            hh = _activate(gh, up, cfg.ffn_activation)
        else:
            hh = _activate(up, None, cfg.ffn_activation)
        eo = jnp.einsum("ecf,efd->ecd", hh, w_down.astype(xl.dtype))
        eo = jax.lax.all_to_all(eo, model_ax, split_axis=1, concat_axis=0, tiled=True)
        # eo: (e, cap_l, d) — back on the token-home shard
        back = eo[flat_e, jnp.minimum(safe_pos, cap_l - 1)]
        back = back * gate_flat[:, None].astype(xl.dtype)
        out = jax.ops.segment_sum(back, tok_of_slot, num_segments=t_l, indices_are_sorted=True)
        return out.reshape(b_l, s_l, d), aux

    w_gate = params.get("w_gate", params["w_up"])
    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(None, None),
            P(model_ax, None, None),
            P(model_ax, None, None),
            P(model_ax, None, None),
            act_spec,
        ),
        out_specs=(act_spec, P()),
    )(params["router"], params["w_up"], w_gate, params["w_down"], x)
    if cfg.n_shared_experts:
        out = out + ffn_apply(params["shared"], cfg.ffn_activation, x)
    return out, aux


def _flat_token_spec(act_spec):
    """(b, s, d) residual spec -> (tokens, d) spec for the flattened MoE view."""
    if act_spec is None:
        return None
    from jax.sharding import PartitionSpec as P

    def axes(entry):
        if entry is None:
            return ()
        return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)

    return P(axes(act_spec[0]) + axes(act_spec[1]), act_spec[2])


def moe_apply(params: Params, cfg: LMConfig, x: jnp.ndarray, act_spec=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k MoE with capacity + scatter/gather dispatch.

    Returns (output, aux_loss).  Instead of the GShard one-hot dispatch
    tensor ``(tokens, experts, capacity)`` — O(t*e*c) memory, infeasible at
    1M-token global batches — tokens are scattered into a dense per-expert
    buffer ``(e, capacity, d)`` with ``.at[].add`` (each slot receives at most
    one token) and gathered back after the expert FFN.  Expert weights carry
    a leading expert axis sharded over "model" (expert parallelism); the
    scatter/gather lower to all-to-all-style collectives under pjit.
    """
    from jax.sharding import PartitionSpec as P

    if act_spec is not None:
        return _moe_apply_ep(params, cfg, x, act_spec)

    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    n_tok = b * s
    e, k = cfg.n_experts, cfg.moe_top_k
    tok_spec = _flat_token_spec(act_spec)

    def wsc(v, spec):
        return jax.lax.with_sharding_constraint(v, spec) if act_spec is not None else v

    tokens = wsc(tokens, tok_spec)
    logits = tokens.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (t, e)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (t, k)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    flat_e = expert_idx.reshape(-1)  # (t*k,)
    # load-balancing aux loss (Switch): e * sum_e frac_tokens_e * frac_prob_e
    tokens_per_expert = (
        jax.ops.segment_sum(jnp.ones_like(flat_e, jnp.float32), flat_e, num_segments=e)
        / (n_tok * k)
    )
    aux = e * jnp.sum(tokens_per_expert * probs.mean(0)) * cfg.router_aux_coef

    capacity = max(int(n_tok * k * cfg.capacity_factor / e), 4)
    # position of each (token, slot) within its expert queue (cumsum order)
    onehot_flat = jax.nn.one_hot(flat_e, e, dtype=jnp.float32)  # (t*k, e)
    pos_flat = (
        (jnp.cumsum(onehot_flat, axis=0) - onehot_flat)[jnp.arange(n_tok * k), flat_e]
    ).astype(jnp.int32)
    keep = pos_flat < capacity
    safe_pos = jnp.where(keep, pos_flat, capacity)  # overflow -> scratch slot
    gate_flat = gate_vals.reshape(-1) * keep

    tok_of_slot = jnp.arange(n_tok * k) // k
    buf = jnp.zeros((e, capacity + 1, d), dtype=x.dtype)
    buf = buf.at[flat_e, safe_pos].add(tokens[tok_of_slot] * keep[:, None].astype(x.dtype))
    # expert buffers live sharded over the expert axis (EP) — without the
    # constraint the partitioner replicates the scatter target (30+ GB/dev)
    buf = wsc(buf, P("model", None, None))
    expert_in = buf[:, :capacity]  # (e, cap, d)

    gated = cfg.ffn_activation in ("swiglu", "geglu")
    up = jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"].astype(x.dtype))
    if gated:
        gate_h = jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"].astype(x.dtype))
        h = _activate(gate_h, up, cfg.ffn_activation)
    else:
        h = _activate(up, None, cfg.ffn_activation)
    expert_out = wsc(
        jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(x.dtype)), P("model", None, None)
    )

    # gather back + weighted combine over the k slots of each token
    back = expert_out[flat_e, jnp.minimum(safe_pos, capacity - 1)]  # (t*k, d)
    back = back * gate_flat[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(back, tok_of_slot, num_segments=n_tok, indices_are_sorted=True)
    out = wsc(out, tok_spec)
    if cfg.n_shared_experts:
        out = out + ffn_apply(params["shared"], cfg.ffn_activation, tokens)
    return out.reshape(b, s, d), aux
