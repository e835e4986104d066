"""Transformer layer library (pure functional JAX).

Every ``init_*`` returns ``(params, specs)`` built through :class:`ParamSet`
so the parameter tree and its logical-axis sharding tree can never drift.
Logical axes are resolved to mesh axes by ``distributed/sharding.py``.

The attention implementation is *chunk-pair* online-softmax causal
attention: a ``lax.scan`` over the statically enumerated causal (q-chunk,
kv-chunk) pairs. It has exact causal FLOPs (no masked-block waste), O(S)
live memory, is reverse-differentiable (the pair body is checkpointed),
honours sliding windows by static pair pruning, and doubles as the
reference the Pallas flash kernel is tested against.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]
Specs = Dict[str, Any]


class ParamSet:
    """Collects parameters and their logical-axis specs in lock-step."""

    def __init__(self, key: jax.Array, dtype):
        self._key = key
        self.dtype = dtype
        self.params: Params = {}
        self.specs: Specs = {}

    def _next(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def param(self, name: str, shape: Tuple[int, ...], axes: Tuple,
              init: str = "normal", scale: Optional[float] = None):
        assert len(shape) == len(axes), (name, shape, axes)
        if init == "normal":
            if scale is None:
                scale = 1.0 / math.sqrt(shape[0])
            arr = jax.random.normal(self._next(), shape, self.dtype) * scale
        elif init == "zeros":
            arr = jnp.zeros(shape, self.dtype)
        elif init == "ones":
            arr = jnp.ones(shape, self.dtype)
        else:
            raise ValueError(init)
        self.params[name] = arr
        self.specs[name] = axes

    def sub(self, name: str, ps: "ParamSet"):
        self.params[name] = ps.params
        self.specs[name] = ps.specs

    def child(self) -> "ParamSet":
        return ParamSet(self._next(), self.dtype)

    def done(self) -> Tuple[Params, Specs]:
        return self.params, self.specs


# ---------------------------------------------------------------- norms
def rms_norm(x, scale, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(dt) * scale


def layer_norm(x, scale, bias, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return y.astype(dt) * scale + bias


# ---------------------------------------------------------------- rotary
def rope_angles(positions, dim: int, theta: float):
    """positions (...,) -> cos/sin (..., dim/2)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., S, H, D); cos/sin (..., S, D/2) broadcast over heads."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[..., None, :].astype(x1.dtype)
    s = sin[..., None, :].astype(x1.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ---------------------------------------------------- TP matmul helpers
def tp_einsum(eq: str, x, w, sharder, *, w_model_dim=None,
              x_model_dim=None, out_model_dim=None, psum: bool = False):
    """Tensor-parallel einsum via shard_map (§Perf optimisation).

    Under pjit, row-parallel matmuls all-reduce the dot's fp32
    accumulator (measured: 2x the necessary bytes on every TP boundary),
    and column-parallel backward passes do the same for dx. Expressing
    the matmul per-shard makes the psum operate on the bf16 activation
    (forward) / cotangent (backward). Falls back to a plain einsum when
    no mesh is active or the weight isn't model-sharded.
    """
    mesh = getattr(sharder, "mesh", None)
    if mesh is None or "model" not in mesh.axis_names \
            or w_model_dim is None:
        return jnp.einsum(eq, x, w)
    from jax.sharding import PartitionSpec as P
    tp = "model"
    dp = sharder.rules.rules.get("batch")
    out_ndim = len(eq.split("->")[1])

    def spec(ndim, model_dim, batched=False):
        ax = [None] * ndim
        if model_dim is not None:
            ax[model_dim] = tp
        if batched:
            ax[0] = dp
        return P(*ax)

    def f(xl, wl):
        y = jnp.einsum(eq, xl, wl)
        if psum:
            # reduce on the activation dtype, not the accumulator's
            y = lax.psum(y.astype(xl.dtype), tp)
        return y

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(spec(x.ndim, x_model_dim, batched=True),
                  spec(w.ndim, w_model_dim)),
        out_specs=spec(out_ndim, out_model_dim, batched=True),
        check_vma=False,
    )(x, w)


def _heads_sharded(sharder) -> bool:
    """True when attention heads are model-sharded AND the explicit
    shard_map TP path is enabled (rules flag "_tp_shardmap").

    §Perf iteration A3: routing TP matmuls through shard_map was meant to
    force bf16 psums; XLA:CPU re-promotes them to f32, and the explicit
    boundaries add FSDP re-gather collectives — measured regressions of
    +20-45 % on internlm2/internvl2/deepseek-v3 cells. Default OFF; the
    code stays for TPU-target experiments (flip the rules flag).
    """
    rules = getattr(sharder, "rules", None)
    return (rules is not None
            and bool(rules.rules.get("_tp_shardmap"))
            and rules.rules.get("heads") == "model")


def _seq_attn(sharder) -> bool:
    rules = getattr(sharder, "rules", None)
    return rules is not None and bool(rules.rules.get("_seq_attn"))


def seq_parallel_attention(q, k, v, sharder, *, chunk: int,
                           window=None, softmax_scale=None):
    """Sequence-parallel attention for head counts that do not divide the
    model axis (§Perf qwen3-14b/prefill_32k iteration).

    Baseline replicated attention does the full S x S wedge on every
    model rank (16x redundant compute and tile traffic — the dominant
    roofline term for these archs). Here every rank takes its S/TP query
    slice against the full locally-computed K/V: forward needs ZERO
    collectives (k, v are already replicated over 'model'); backward
    psums dk/dv once. Causal masking uses the rank's dynamic offset, so
    per-rank compute is S^2/TP masked pairs (2x the exact wedge, 8x
    better than replication at TP=16).
    """
    mesh = sharder.mesh
    from jax.sharding import PartitionSpec as P
    tp = "model"
    dp = sharder.rules.rules.get("batch")
    S = q.shape[1]
    tp_size = mesh.shape[tp]
    S_local = S // tp_size

    def f(ql, kl, vl):
        rank = lax.axis_index(tp)
        off = rank * S_local
        q_slice = lax.dynamic_slice_in_dim(ql, off, S_local, axis=1)
        y = chunked_attention(q_slice, kl, vl, chunk=chunk, causal=True,
                              window=window, softmax_scale=softmax_scale,
                              q_offset_dyn=off)
        return y

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(dp, None, None, None),) * 3,
        out_specs=P(dp, tp, None, None),
        check_vma=False,
    )(q, k, v)


def _ff_sharded(sharder) -> bool:
    rules = getattr(sharder, "rules", None)
    return (rules is not None
            and bool(rules.rules.get("_tp_shardmap"))
            and rules.rules.get("ff") == "model")


# ------------------------------------------------------------- attention
def init_attention(ps: ParamSet, cfg) -> None:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ps.param("wq", (d, h, hd), ("embed", "heads", "head_dim"))
    ps.param("wk", (d, kv, hd), ("embed", "kv_heads", "head_dim"))
    ps.param("wv", (d, kv, hd), ("embed", "kv_heads", "head_dim"))
    ps.param("wo", (h, hd, d), ("heads", "head_dim", "embed"),
             scale=1.0 / math.sqrt(h * hd))
    if cfg.qkv_bias:
        ps.param("bq", (h, hd), ("heads", "head_dim"), init="zeros")
        ps.param("bk", (kv, hd), ("kv_heads", "head_dim"), init="zeros")
        ps.param("bv", (kv, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        ps.param("q_norm", (hd,), ("head_dim",), init="ones")
        ps.param("k_norm", (hd,), ("head_dim",), init="ones")


def _causal_pairs(n_q: int, n_kv: int, q_offset_chunks: int,
                  window_chunks: Optional[int]):
    """Static (i, j) chunk-pair list for causal (+windowed) attention.

    q chunk i covers absolute chunk index i + q_offset_chunks; kv chunk j
    is attended iff j <= i + q_offset_chunks and (no window or
    i + q_offset_chunks - j < window_chunks + 1).
    """
    pairs = []
    for i in range(n_q):
        ai = i + q_offset_chunks
        for j in range(n_kv):
            if j > ai:
                continue
            if window_chunks is not None and ai - j > window_chunks:
                continue
            pairs.append((i, j))
    return pairs


def chunked_attention(q, k, v, *, chunk: int, causal: bool = True,
                      q_offset: int = 0, window: Optional[int] = None,
                      softmax_scale: Optional[float] = None,
                      q_offset_dyn=None):
    """Online-softmax attention over statically enumerated chunk pairs.

    q: (B, Sq, H, D); k, v: (B, Skv, KVH, D) with H % KVH == 0 (grouped
    query attention — kv heads are never materialised H-wide).
    ``q_offset``: absolute position of q[0] (prefill continuation).
    Exact causal FLOPs; reverse-differentiable (checkpointed body).
    """
    B, Sq, H, D = q.shape
    _, Skv, KVH, Dv = v.shape       # v may have its own head dim (MLA)
    G = H // KVH
    scale = softmax_scale or (1.0 / math.sqrt(D))

    c = min(chunk, Sq, Skv)
    # pad seqs to chunk multiples (static)
    pq = (-Sq) % c
    pk = (-Skv) % c
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    n_q, n_kv = (Sq + pq) // c, (Skv + pk) // c

    if causal and q_offset_dyn is None:
        assert q_offset % c == 0, "q_offset must be chunk-aligned"
        # pair (i, j) can contain a visible element iff
        # c*(i-j) - (c-1) <= window  <=>  i-j <= (window + c - 1) // c
        wc = None if window is None else (window + c - 1) // c
        pairs = _causal_pairs(n_q, n_kv, q_offset // c, wc)
    else:
        # dynamic offset (sequence-parallel shards): masking is runtime,
        # so the pair list cannot be pruned statically
        pairs = [(i, j) for i in range(n_q) for j in range(n_kv)]
    pi = jnp.array([p[0] for p in pairs], jnp.int32)
    pj = jnp.array([p[1] for p in pairs], jnp.int32)

    qc = q.reshape(B, n_q, c, KVH, G, D)
    kc = k.reshape(B, n_kv, c, KVH, D)
    vc = v.reshape(B, n_kv, c, KVH, Dv)

    acc = jnp.zeros((B, n_q, c, KVH, G, Dv), jnp.float32)
    m = jnp.full((B, n_q, c, KVH, G), -jnp.inf, jnp.float32)
    l = jnp.zeros((B, n_q, c, KVH, G), jnp.float32)

    kv_pos = jnp.arange(c)
    q_pos = jnp.arange(c)

    def body(carry, ij):
        acc, m, l = carry
        i, j = ij
        qi = qc[:, i]                      # (B, c, KVH, G, D)
        kj = kc[:, j]                      # (B, c, KVH, D)
        vj = vc[:, j]
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qi.astype(jnp.float32),
                       kj.astype(jnp.float32)) * scale
        if causal:
            off = q_offset if q_offset_dyn is None else q_offset_dyn
            aq = i * c + q_pos + off
            ak = j * c + kv_pos
            mask = aq[:, None] >= ak[None, :]
            if window is not None:
                mask &= (aq[:, None] - ak[None, :]) <= window
            s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        # mask padded kv positions
        if pk:
            valid = (j * c + kv_pos) < Skv
            s = jnp.where(valid[None, None, None, None, :], s, -jnp.inf)
        # clamp: a fully-masked tile (window pruning) must not produce
        # -inf - -inf = nan
        m_new = jnp.maximum(jnp.maximum(m[:, i], s.max(axis=-1)), -1e30)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m[:, i] - m_new)
        l_new = l[:, i] * corr + p.sum(axis=-1)
        acc_new = (acc[:, i] * corr[..., None]
                   + jnp.einsum("bqhgk,bkhd->bqhgd", p,
                                vj.astype(jnp.float32)))
        return (acc.at[:, i].set(acc_new), m.at[:, i].set(m_new),
                l.at[:, i].set(l_new)), None

    (acc, m, l), _ = lax.scan(jax.checkpoint(body), (acc, m, l), (pi, pj))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.reshape(B, n_q * c, H, Dv)[:, :Sq]
    return out.astype(q.dtype)


def attention_apply(params: Params, cfg, x, cos, sin, sharder,
                    *, q_offset: int = 0, window: Optional[int] = None,
                    causal: bool = True, kv_override=None):
    """Full-sequence attention (train / prefill). Returns (y, (k, v))."""
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    hm = 1 if _heads_sharded(sharder) else None
    q = tp_einsum("bsd,dhk->bshk", x, params["wq"], sharder,
                  w_model_dim=hm, out_model_dim=2 if hm else None)
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    else:  # cross attention: precomputed encoder k, v
        k, v = kv_override
    if cfg.qkv_bias:
        q = q + params["bq"]
        if kv_override is None:
            k = k + params["bk"]
            v = v + params["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        if kv_override is None:
            k = apply_rope(k, cos, sin)
    q = sharder(q, ("batch", "seq_q", "heads", None))
    k = sharder(k, ("batch", "seq_kv", "kv_heads", None))
    v = sharder(v, ("batch", "seq_kv", "kv_heads", None))
    if causal and q_offset == 0 and _seq_attn(sharder) \
            and q.shape[1] % sharder.mesh.shape["model"] == 0:
        y = seq_parallel_attention(q, k, v, sharder,
                                   chunk=cfg.attn_chunk, window=window)
        y = sharder(y, ("batch", "seq_q", "heads", None))
    else:
        y = chunked_attention(q, k, v, chunk=cfg.attn_chunk,
                              causal=causal, q_offset=q_offset,
                              window=window)
    hm = 0 if _heads_sharded(sharder) else None
    y = tp_einsum("bshk,hkd->bsd", y, params["wo"], sharder,
                  w_model_dim=hm, x_model_dim=2 if hm == 0 else None,
                  psum=hm == 0)
    return y, (k, v)


# ------------------------------------------------------------------ MLP
def init_mlp(ps: ParamSet, cfg, d_ff: Optional[int] = None,
             gelu: bool = False) -> None:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ps.param("w_gate", (d, f), ("embed", "ff"))
    if not gelu:
        ps.param("w_up", (d, f), ("embed", "ff"))
    ps.param("w_down", (f, d), ("ff", "embed"))
    if gelu:
        ps.param("b_gate", (f,), ("ff",), init="zeros")
        ps.param("b_down", (d,), ("embed",), init="zeros")


def mlp_apply(params: Params, x, sharder, gelu: bool = False):
    if gelu:
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, params["w_gate"])
                        + params["b_gate"])
        h = sharder(h, ("batch", "seq_q", "ff"))
        return jnp.einsum("bsf,fd->bsd", h, params["w_down"]) \
            + params["b_down"]
    fm = 1 if _ff_sharded(sharder) else None
    g = tp_einsum("bsd,df->bsf", x, params["w_gate"], sharder,
                  w_model_dim=fm, out_model_dim=2 if fm else None)
    u = tp_einsum("bsd,df->bsf", x, params["w_up"], sharder,
                  w_model_dim=fm, out_model_dim=2 if fm else None)
    h = jax.nn.silu(g) * u
    h = sharder(h, ("batch", "seq_q", "ff"))
    return tp_einsum("bsf,fd->bsd", h, params["w_down"], sharder,
                     w_model_dim=0 if fm else None,
                     x_model_dim=2 if fm else None, psum=fm is not None)


# ------------------------------------------------------------------ MoE
def init_moe(ps: ParamSet, cfg) -> None:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    ps.param("router", (d, e), ("embed", None), scale=0.02)
    ps.param("we_gate", (e, d, f), ("experts", "embed", "moe_ff"))
    ps.param("we_up", (e, d, f), ("experts", "embed", "moe_ff"))
    ps.param("we_down", (e, f, d), ("experts", "moe_ff", "embed"))
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        ps.param("ws_gate", (d, fs), ("embed", "ff"))
        ps.param("ws_up", (d, fs), ("embed", "ff"))
        ps.param("ws_down", (fs, d), ("ff", "embed"))


def router_probs(params, cfg, x):
    """Softmax router over experts (fp32), top-k selection."""
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, cfg.topk)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_e


def moe_aux_loss(probs, top_e, n_experts: int):
    """Switch-style load-balancing loss."""
    density = jnp.mean(
        jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32), axis=(0, 1, 2))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    return n_experts * jnp.sum(density * mean_prob)


def moe_apply_dense(params: Params, cfg, x, sharder):
    """Oracle MoE: every expert on every token, masked combine. Exact but
    O(E) FLOPs — smoke tests and kernel references only."""
    probs, top_p, top_e = router_probs(params, cfg, x)
    gate = jnp.einsum("btd,edf->betf", x, params["we_gate"])
    up = jnp.einsum("btd,edf->betf", x, params["we_up"])
    h = jax.nn.silu(gate) * up
    y_e = jnp.einsum("betf,efd->betd", h, params["we_down"])
    combine = jnp.sum(
        jax.nn.one_hot(top_e, cfg.n_experts, dtype=x.dtype)
        * top_p.astype(x.dtype)[..., None], axis=2)           # (B,T,E)
    y = jnp.einsum("betd,bte->btd", y_e, combine)
    aux = moe_aux_loss(probs, top_e, cfg.n_experts)
    return y + _shared_expert(params, cfg, x, sharder), aux


def _shared_expert(params, cfg, x, sharder):
    if not cfg.n_shared_experts:
        return 0.0
    fm = 1 if _ff_sharded(sharder) else None
    g = tp_einsum("bsd,df->bsf", x, params["ws_gate"], sharder,
                  w_model_dim=fm, out_model_dim=2 if fm else None)
    u = tp_einsum("bsd,df->bsf", x, params["ws_up"], sharder,
                  w_model_dim=fm, out_model_dim=2 if fm else None)
    h = jax.nn.silu(g) * u
    h = sharder(h, ("batch", "seq_q", "ff"))
    return tp_einsum("bsf,fd->bsd", h, params["ws_down"], sharder,
                     w_model_dim=0 if fm else None,
                     x_model_dim=2 if fm else None, psum=fm is not None)


def moe_dispatch_indices(top_e, top_p, n_experts: int, capacity: int):
    """Capacity-based dispatch: returns (dest, weight) where
    dest (B, T, K) in [0, capacity) or capacity (dropped)."""
    B, T, K = top_e.shape
    flat_e = top_e.reshape(B, T * K)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) - 1            # position within expert
    slot = jnp.take_along_axis(pos, flat_e[..., None], axis=-1)[..., 0]
    slot = slot.reshape(B, T, K)
    keep = slot < capacity
    return jnp.where(keep, slot, capacity), jnp.where(keep, top_p, 0.0)


def moe_apply_capacity(params: Params, cfg, x, sharder, capacity: int):
    """Capacity-dropping MoE with expert-sharded buffers.

    Tokens are scattered into (E, capacity) buffers, each expert runs a
    dense FFN over its buffer, results are gathered back with combine
    weights. Under the production mesh the expert axis is sharded
    ('model'); dispatch/combine lower to collectives chosen by SPMD.
    """
    B, T, _ = x.shape
    E = cfg.n_experts
    probs, top_p, top_e = router_probs(params, cfg, x)
    slot, w = moe_dispatch_indices(top_e, top_p, E, capacity)

    # scatter tokens into expert buffers: (B, E, capacity, d)
    buf = jnp.zeros((B, E, capacity + 1, x.shape[-1]), x.dtype)
    bidx = jnp.arange(B)[:, None, None]
    buf = buf.at[bidx, top_e, slot].add(
        x[:, :, None, :] * (w[..., None] > 0).astype(x.dtype))
    buf = buf[:, :, :capacity]
    buf = sharder(buf, ("batch", "experts", None, None))

    g = jnp.einsum("becd,edf->becf", buf, params["we_gate"])
    u = jnp.einsum("becd,edf->becf", buf, params["we_up"])
    h = jax.nn.silu(g) * u
    h = sharder(h, ("batch", "experts", None, "moe_ff"))
    y_buf = jnp.einsum("becf,efd->becd", h, params["we_down"])
    y_buf = jnp.pad(y_buf, ((0, 0), (0, 0), (0, 1), (0, 0)))  # drop slot

    # gather back: token (b,t) takes y_buf[b, top_e[k], slot[k]] * w[k]
    y = jnp.einsum(
        "btkd,btk->btd",
        y_buf[bidx, top_e, slot],
        w.astype(x.dtype))
    aux = moe_aux_loss(probs, top_e, E)
    return y + _shared_expert(params, cfg, x, sharder), aux


def moe_apply_ep_shardmap(params: Params, cfg, x, sharder, capacity: int):
    """Expert-parallel MoE under ``shard_map`` (§Perf optimisation).

    The pjit/GSPMD lowering of ``moe_apply_capacity`` materialises the
    per-token expert outputs as a REPLICATED (B, T, K, d) fp32 tensor and
    all-reduces it across the whole mesh per layer (measured: 77 GB/dev
    per layer on deepseek-moe-16b — EXPERIMENTS.md §Perf). Here the
    dispatch/combine runs per shard: each model rank owns E/TP experts,
    scatters only its own tokens, and the single collective is a
    bf16 psum of the (B_local, T, d) partial outputs.

    Requires a mesh-carrying sharder; the router runs redundantly on
    every model rank (identical results — cheap) so no token shuffling
    collective is needed at all ("replicated-dispatch EP").
    """
    from jax.sharding import PartitionSpec as P

    mesh = sharder.mesh
    dp = sharder.rules.rules.get("batch")
    tp = "model"
    E = cfg.n_experts
    # NOTE (§Perf A4, not implemented): 2D expert parallelism (experts
    # over data x model) would eliminate the FSDP per-layer weight
    # gathers that cap deepseek-v3's multi-pod scaling at 1.14x — but it
    # requires an all-to-all token exchange (tokens are data-sharded and
    # a replicated-dispatch variant would have to gather the full global
    # batch per device: 15 GB/layer for dsv3 train). Recorded as the
    # 1000+-node direction in EXPERIMENTS.md.
    tp_size = mesh.shape[tp]
    E_local = E // tp_size

    def block(x_l, router, we_gate, we_up, we_down):
        # x_l: (B_local, T, d); we_*: (E_local, d, f)
        logits = jnp.einsum("btd,de->bte", x_l.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = lax.top_k(probs, cfg.topk)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        slot, w = moe_dispatch_indices(top_e, top_p, E, capacity)

        rank = lax.axis_index(tp)
        e0 = rank * E_local
        local = (top_e >= e0) & (top_e < e0 + E_local) & (w > 0)
        le = jnp.clip(top_e - e0, 0, E_local - 1)
        lslot = jnp.where(local, slot, capacity)

        B = x_l.shape[0]
        buf = jnp.zeros((B, E_local, capacity + 1, x_l.shape[-1]),
                        x_l.dtype)
        bidx = jnp.arange(B)[:, None, None]
        buf = buf.at[bidx, le, lslot].add(
            x_l[:, :, None, :] * local[..., None].astype(x_l.dtype))
        buf = buf[:, :, :capacity]

        g = jnp.einsum("becd,edf->becf", buf, we_gate)
        u = jnp.einsum("becd,edf->becf", buf, we_up)
        h = jax.nn.silu(g) * u
        y_buf = jnp.einsum("becf,efd->becd", h, we_down)
        y_buf = jnp.pad(y_buf, ((0, 0), (0, 0), (0, 1), (0, 0)))

        y = jnp.einsum(
            "btkd,btk->btd", y_buf[bidx, le, lslot],
            (w * local).astype(x_l.dtype))
        y = lax.psum(y.astype(cfg.cdtype), tp)
        aux = moe_aux_loss(probs, top_e, E)   # identical on all tp ranks
        if dp:
            aux = lax.pmean(aux, dp)          # P() out_spec needs global
        return y, aux

    y, aux = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(dp, None, None), P(), P(tp, None, None),
                  P(tp, None, None), P(tp, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, params["router"], params["we_gate"], params["we_up"],
      params["we_down"])
    return y + _shared_expert(params, cfg, x, sharder), aux


# ------------------------------------------------------------------ MLA
def init_mla(ps: ParamSet, cfg) -> None:
    """DeepSeek multi-head latent attention."""
    d, h = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ps.param("wq_a", (d, qr), ("embed", "lora"))
    ps.param("q_a_norm", (qr,), (None,), init="ones")
    ps.param("wq_b", (qr, h, dn + dr), ("lora", "heads", "head_dim"))
    ps.param("wkv_a", (d, kvr + dr), ("embed", None))
    ps.param("kv_a_norm", (kvr,), (None,), init="ones")
    ps.param("wk_b", (kvr, h, dn), ("lora", "heads", "head_dim"))
    ps.param("wv_b", (kvr, h, dv), ("lora", "heads", "head_dim"))
    ps.param("wo", (h, dv, d), ("heads", "head_dim", "embed"),
             scale=1.0 / math.sqrt(h * dv))


def mla_apply(params: Params, cfg, x, cos, sin, sharder):
    """MLA for train/prefill (decompressed path). Returns (y, latent_cache)
    where latent_cache = (c_kv, k_rope) is what decode keeps per token."""
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("bsd,dr->bsr", x, params["wq_a"])
    q = rms_norm(q, params["q_a_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q, params["wq_b"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, cos, sin)

    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    c_kv, k_rope = kv[..., :cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank:]
    c_kv = rms_norm(c_kv, params["kv_a_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)  # 1 shared head

    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", c_kv, params["wv_b"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(
            k_rope, (*k_nope.shape[:3], dr))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    qf = sharder(qf, ("batch", "seq_q", "heads", None))
    k = sharder(k, ("batch", "seq_kv", "heads", None))
    v = sharder(v, ("batch", "seq_kv", "heads", None))
    y = chunked_attention(qf, k, v, chunk=cfg.attn_chunk,
                          softmax_scale=1.0 / math.sqrt(dn + dr))
    y = jnp.einsum("bshk,hkd->bsd", y, params["wo"])
    return y, (c_kv, k_rope[:, :, 0, :])


# ----------------------------------------------------------- embeddings
def init_embeddings(ps: ParamSet, cfg) -> None:
    # The token-id gather resists FSDP resharding (SPMD full-remat), and
    # vocab sharding already divides the table 16-way — so the d_model dim
    # stays unsharded ("embed_t" is never FSDP-mapped).
    v, d = cfg.padded_vocab, cfg.d_model
    ps.param("embed", (v, d), ("vocab", "embed_t"), scale=0.02)
    if not cfg.tie_embeddings:
        ps.param("unembed", (d, v), ("embed_t", "vocab"))
    ps.param("final_norm", (d,), ("embed_t",), init="ones")


def embed_tokens(params, cfg, tokens):
    return params["embed"].astype(cfg.cdtype)[tokens]


def logits_from_hidden(params, cfg, h, sharder):
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = jnp.einsum("bsd,dv->bsv", h, w.astype(cfg.cdtype))
    return sharder(logits, ("batch", "seq_q", "vocab"))


def cross_entropy(logits, labels, vocab_size: int):
    """Mean CE over positions with label >= 0 (padded vocab tail masked)."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32),
        jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0) & (labels < vocab_size)
    loss = jnp.where(mask, lse - gold, 0.0)
    return loss.sum() / jnp.maximum(mask.sum(), 1)
