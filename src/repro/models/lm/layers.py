"""Shared transformer building blocks for the assigned-architecture zoo.

Everything is functional (params-in, activations-out) and scan-friendly:
per-layer parameter leaves carry a leading L dimension and blocks are run
under ``jax.lax.scan`` with a configurable remat policy (MaxText-style),
which keeps HLO size O(1) in depth — essential for 40-cell dry-run compiles.

Sharding is injected, not global: every function takes ``shard``, a callable
``(x, logical_axes) -> x`` that the launcher binds to
``with_sharding_constraint`` through the logical-axis rules in
``repro.distributed.sharding``; CPU unit tests bind identity.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from jax import shard_map

Shard = Callable[[jax.Array, tuple[str | None, ...]], jax.Array]


def no_shard(x: jax.Array, logical_axes: tuple[str | None, ...]) -> jax.Array:
    return x


# ---------------------------------------------------------------------------
# norms & rotary
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * gamma.astype(jnp.float32)).astype(dt)


def rope_freqs(head_dim: int, theta: float = 10_000.0) -> jax.Array:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10_000.0) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))                  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                                     # (..., S, 1, hd/2)
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, optional qk-norm) — full / causal / cached-decode
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_model: int
    qk_norm: bool = False
    rope_theta: float = 10_000.0


def init_attn(key, dims: AttnDims, dtype) -> dict:
    d, h, kv, hd = dims.d_model, dims.n_heads, dims.n_kv_heads, dims.head_dim
    ks = jax.random.split(key, 4)
    s = float(1.0 / np.sqrt(d))
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dtype=dtype) * s,
        "wk": jax.random.normal(ks[1], (d, kv * hd), dtype=dtype) * s,
        "wv": jax.random.normal(ks[2], (d, kv * hd), dtype=dtype) * s,
        "wo": jax.random.normal(ks[3], (h * hd, d), dtype=dtype)
              * float(1.0 / np.sqrt(h * hd)),
    }
    if dims.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype=dtype)
        p["k_norm"] = jnp.ones((hd,), dtype=dtype)
    return p


def _qkv(p: dict, dims: AttnDims, x: jax.Array, positions: jax.Array,
         shard: Shard, rope: bool = True):
    b, s, _ = x.shape
    h, kv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (x @ p["wk"]).reshape(b, s, kv, hd)
    v = (x @ p["wv"]).reshape(b, s, kv, hd)
    # q is head-sharded over the model axis; k/v keep kv heads unsharded
    # (GQA TP > kv_heads would force uneven splits / involuntary remats —
    # the repeat-to-h below lets GSPMD slice the broadcast per shard).
    q = shard(q, ("batch", "seq", "heads", None))
    if dims.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if rope:
        q = apply_rope(q, positions, dims.rope_theta)
        k = apply_rope(k, positions, dims.rope_theta)
    return q, k, v


def _expand_gqa(k, h):
    """(b, s, kv, hd) -> (b, s, h, hd) by group broadcast (fused by XLA)."""
    kv = k.shape[2]
    if kv == h:
        return k
    return jnp.repeat(k, h // kv, axis=2)


def _sdpa(q, k, v, *, causal: bool, q_pos=None, k_pos=None):
    """q: (b, sq, h, hd); k/v: (b, sk, kv, hd) — GQA via head repeat."""
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    k = _expand_gqa(k, h)
    v = _expand_gqa(v, h)
    scale = float(1.0 / np.sqrt(hd))
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qp = (jnp.arange(sq) if q_pos is None else q_pos)
        kp = (jnp.arange(sk) if k_pos is None else k_pos)
        mask = qp[:, None] >= kp[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


# sequences at or above this length use chunked online-softmax attention
# (direct attention would materialize an s×s score tensor: at 4k×4k×f32 and
# 2 heads/chip × 16 samples that alone is ~4GiB — §Perf iteration M1)
FLASH_THRESHOLD = 4096
FLASH_CHUNK = 1024


def _attend(q, k, v, *, causal: bool):
    if q.shape[1] >= FLASH_THRESHOLD or k.shape[1] >= FLASH_THRESHOLD:
        return flash_attention(q, k, v, causal=causal,
                               q_chunk=FLASH_CHUNK, k_chunk=FLASH_CHUNK)
    return _sdpa(q, k, v, causal=causal)


def attention(p: dict, dims: AttnDims, x: jax.Array, *,
              shard: Shard = no_shard, causal: bool = True,
              positions: jax.Array | None = None,
              memory: jax.Array | None = None,
              rope: bool = True) -> jax.Array:
    """Full (train/prefill) attention; ``memory`` switches to cross-attn."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :].astype(jnp.int32)
    if memory is None:
        q, k, v = _qkv(p, dims, x, positions, shard, rope)
    else:
        # cross attention: q from x, k/v from memory (no rope on memory)
        h, kv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
        sm = memory.shape[1]
        q = (x @ p["wq"]).reshape(b, s, h, hd)
        k = (memory @ p["wk"]).reshape(b, sm, kv, hd)
        v = (memory @ p["wv"]).reshape(b, sm, kv, hd)
        causal = False
    out = _attend(q, k, v, causal=causal)
    out = out.reshape(b, s, dims.n_heads * dims.head_dim)
    out = out @ p["wo"]
    return shard(out, ("batch", "seq", "embed"))


@dataclasses.dataclass(frozen=True)
class DecodeShardCtx:
    """Distributed flash-decode context (sequence-parallel KV).

    Without it, GSPMD resolves attention over a seq-sharded KV cache by
    ALL-GATHERING the cache per layer (measured 65.75 GiB/device/step on
    llama3-8b decode_32k — EXPERIMENTS §Perf H1-baseline). With it, each
    model-axis shard attends over its local sequence slice and the partial
    softmax states (running max / denominator / weighted value) are combined
    with three tiny psums — the flash-decoding scheme, made explicit via
    shard_map so the partitioner cannot choose the gather.
    """
    mesh: object
    batch_axes: tuple | None       # None = batch unsharded (e.g. b == 1)
    seq_axis: str = "model"


def flash_decode_sharded(q, k_cache, v_cache, k_new, v_new, cache_index,
                         ctx: DecodeShardCtx):
    """One-token attention over a sequence-sharded KV cache + in-place
    (shard-local) cache update at ``cache_index``.

    q (b, 1, h, hd); caches (b, S, kv, hd) sharded (batch, seq_axis, -, -).
    Returns (out (b, 1, h, hd), k_cache, v_cache).
    """
    from jax.sharding import PartitionSpec as P

    ax = ctx.seq_axis
    b_ax = ctx.batch_axes

    update = k_new is not None

    def local(q, kc, vc, kn, vn, idx):
        s_local = kc.shape[1]
        shard_id = jax.lax.axis_index(ax)
        start = shard_id * s_local
        if update:
            li = idx - start
            in_range = (li >= 0) & (li < s_local)
            safe = jnp.clip(li, 0, s_local - 1)
            kc_u = jax.lax.dynamic_update_slice_in_dim(kc, kn, safe, axis=1)
            vc_u = jax.lax.dynamic_update_slice_in_dim(vc, vn, safe, axis=1)
            kc = jnp.where(in_range, kc_u, kc)
            vc = jnp.where(in_range, vc_u, vc)
        h = q.shape[2]
        ke = _expand_gqa(kc, h)
        ve = _expand_gqa(vc, h)
        scale = float(1.0 / np.sqrt(q.shape[-1]))
        logits = jnp.einsum("bqhd,bshd->bhqs", q, ke,
                            preferred_element_type=jnp.float32) * scale
        kpos = start + jnp.arange(s_local)
        valid = kpos <= idx
        logits = jnp.where(valid[None, None, None, :], logits, -1e30)
        m_loc = jnp.max(logits, axis=-1)                       # (b,h,1)
        m = jax.lax.pmax(m_loc, ax)
        p = jnp.exp(logits - m[..., None])
        l = jax.lax.psum(jnp.sum(p, axis=-1), ax)              # (b,h,1)
        o = jax.lax.psum(
            jnp.einsum("bhqs,bshd->bqhd", p.astype(ve.dtype), ve), ax)
        out = o / jnp.maximum(l[:, None], 1e-30).astype(o.dtype)  # (b,1,h,1)
        return out, kc, vc

    cache_spec = P(b_ax, ax, None, None)
    q_spec = P(b_ax, None, None, None)
    if not update:
        k_new = jnp.zeros_like(q[:, :, :1])
        v_new = jnp.zeros_like(q[:, :, :1])
    fn = shard_map(local, mesh=ctx.mesh,
                   in_specs=(q_spec, cache_spec, cache_spec,
                             q_spec, q_spec, P()),
                   out_specs=(q_spec, cache_spec, cache_spec),
                   check_vma=False)
    return fn(q, k_cache, v_cache, k_new, v_new, cache_index)


def attention_decode(p: dict, dims: AttnDims, x: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     cache_index: jax.Array, *,
                     shard: Shard = no_shard, rope: bool = True,
                     decode_ctx: "DecodeShardCtx | None" = None):
    """One-token decode against a (b, S_max, kv, hd) KV cache.

    Returns (out (b, 1, d), k_cache, v_cache) with the caches updated at
    ``cache_index``. Masking is positional: cache slots ≥ cache_index+1 are
    excluded, so pre-zeroed caches need no validity bitmap.
    """
    b = x.shape[0]
    positions = jnp.full((b, 1), cache_index, dtype=jnp.int32)
    q, k, v = _qkv(p, dims, x, positions, shard, rope)
    if decode_ctx is not None:
        out, k_cache, v_cache = flash_decode_sharded(
            q, k_cache, v_cache, k, v, cache_index, decode_ctx)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k, cache_index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v, cache_index, axis=1)
        s_max = k_cache.shape[1]
        kpos = jnp.arange(s_max)
        valid = (kpos <= cache_index)[None, :]               # (1, S_max)
        out = _sdpa_decode(q, k_cache, v_cache, valid)
    out = out.reshape(b, 1, dims.n_heads * dims.head_dim) @ p["wo"]
    return shard(out, ("batch", "seq", "embed")), k_cache, v_cache


def _sdpa_decode(q, k, v, valid):
    b, sq, h, hd = q.shape
    k = _expand_gqa(k, h)
    v = _expand_gqa(v, h)
    scale = float(1.0 / np.sqrt(hd))
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# chunked (online-softmax / "flash") attention — memory-feasible long-context
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool, q_chunk: int = 1024,
                    k_chunk: int = 1024) -> jax.Array:
    """Exact attention with O(s·chunk) memory via online softmax.

    q (b, sq, h, hd); k/v (b, sk, kv, hd). Pure-jnp reference form (the
    Pallas kernel variant lives in repro.kernels.flash_attention); the
    k-chunk loop is a lax.scan, so HLO cost_analysis counts its body once —
    the roofline analyzer corrects analytically (DESIGN §Roofline).
    """
    b, sq, h, hd = q.shape
    _, sk, kv, _ = k.shape
    k = _expand_gqa(k, h)
    v = _expand_gqa(v, h)
    qc = min(q_chunk, sq)
    kc = min(k_chunk, sk)
    nq, nk = sq // qc, sk // kc
    assert sq % qc == 0 and sk % kc == 0, "seq must divide chunk"
    scale = float(1.0 / np.sqrt(hd))

    qr = q.reshape(b, nq, qc, h, hd)
    kr = k.reshape(b, nk, kc, h, hd)
    vr = v.reshape(b, nk, kc, h, hd)

    def q_block(qi, q_blk):
        # q_blk: (b, qc, h, hd)
        m0 = jnp.full((b, h, qc), -jnp.inf, dtype=jnp.float32)
        l0 = jnp.zeros((b, h, qc), dtype=jnp.float32)
        a0 = jnp.zeros((b, h, qc, hd), dtype=jnp.float32)

        @jax.checkpoint
        def k_block(carry, inp):
            m, l, acc = carry
            ki, k_blk, v_blk = inp
            logits = jnp.einsum("bqhd,bshd->bhqs", q_blk, k_blk,
                                preferred_element_type=jnp.float32) * scale
            if causal:
                qpos = qi * qc + jnp.arange(qc)
                kpos = ki * kc + jnp.arange(kc)
                mask = qpos[:, None] >= kpos[None, :]
                logits = jnp.where(mask[None, None], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l = l * corr + p.sum(axis=-1)
            acc = (acc * corr[..., None]
                   + jnp.einsum("bhqs,bshd->bhqd", p,
                                v_blk.astype(jnp.float32)))
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            k_block, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.transpose(out, (0, 2, 1, 3))           # (b, qc, h, hd)

    out = jax.lax.map(lambda inp: q_block(inp[0], inp[1]),
                      (jnp.arange(nq), jnp.moveaxis(qr, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, h, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    s_in, s_out = float(1.0 / np.sqrt(d_model)), float(1.0 / np.sqrt(d_ff))
    return {
        "w_gate": jax.random.normal(ks[0], (d_model, d_ff), dtype=dtype) * s_in,
        "w_up": jax.random.normal(ks[1], (d_model, d_ff), dtype=dtype) * s_in,
        "w_down": jax.random.normal(ks[2], (d_ff, d_model), dtype=dtype) * s_out,
    }


def swiglu(p: dict, x: jax.Array, shard: Shard = no_shard) -> jax.Array:
    g = x @ p["w_gate"]
    u = x @ p["w_up"]
    h = jax.nn.silu(g) * u
    h = shard(h, ("batch", "seq", "mlp"))
    out = h @ p["w_down"]
    return shard(out, ("batch", "seq", "embed"))


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype) -> dict:
    ks = jax.random.split(key, 2)
    return {
        "w_in": jax.random.normal(ks[0], (d_model, d_ff), dtype=dtype)
                * float(1.0 / np.sqrt(d_model)),
        "b_in": jnp.zeros((d_ff,), dtype=dtype),
        "w_out": jax.random.normal(ks[1], (d_ff, d_model), dtype=dtype)
                 * float(1.0 / np.sqrt(d_ff)),
        "b_out": jnp.zeros((d_model,), dtype=dtype),
    }


def gelu_mlp(p: dict, x: jax.Array, shard: Shard = no_shard) -> jax.Array:
    h = jax.nn.gelu(x @ p["w_in"] + p["b_in"])
    h = shard(h, ("batch", "seq", "mlp"))
    return shard(h @ p["w_out"] + p["b_out"], ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def next_token_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Shifted cross entropy; logits (b, s, v), tokens (b, s)."""
    logits = logits[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None],
                              axis=-1)[..., 0]
    return jnp.mean(logz - tgt)


def chunked_ce_loss(x: jax.Array, gamma: jax.Array, w_head: jax.Array,
                    tokens: jax.Array, *, chunk: int = 1024,
                    shard: Shard = no_shard) -> jax.Array:
    """Next-token CE directly from final hidden states, sequence-chunked so
    the (b, s, vocab) f32 logits tensor is never materialized (§Perf M2).

    The chunk loop is a *python* loop (unrolled HLO): exact cost_analysis
    accounting and still O(s/chunk) live memory.
    """
    b, s, d = x.shape
    s_eff = s - 1                              # last position has no target
    chunk = min(chunk, s_eff)

    @jax.checkpoint
    def chunk_loss(xc, targets):
        # rematerialized in the backward: the (b, chunk, vocab) f32 softmax
        # residuals never accumulate across chunks (§Perf M4)
        xc = rms_norm(xc, gamma)
        logits = (xc @ w_head).astype(jnp.float32)
        logits = shard(logits, ("batch", "seq", "vocab"))
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None],
                                  axis=-1)[..., 0]
        return jnp.sum(logz - tgt)

    total = jnp.zeros((), dtype=jnp.float32)
    for lo in range(0, s_eff, chunk):
        hi = min(lo + chunk, s_eff)
        total = total + chunk_loss(x[:, lo:hi], tokens[:, lo + 1:hi + 1])
    return total / (b * s_eff)


def stack_layer_params(per_layer: list[dict]) -> dict:
    """[{leaf: (..)}, ...] -> {leaf: (L, ..)} for lax.scan."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
