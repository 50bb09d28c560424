"""Shared model primitives: norms, RoPE, activations, param trees with
logical sharding axes, chunked (flash-style) attention in pure JAX.

Parameters are plain dict pytrees.  Every leaf is created through
:func:`param`, which records a tuple of *logical axis names* in a parallel
specs tree; ``runtime.mesh_rules`` maps logical axes → mesh axes at jit time.
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax
import jax.numpy as jnp

Array = jnp.ndarray
PyTree = Any

# --------------------------------------------------- activation sharding
# Model code is mesh-agnostic: it annotates activations with *logical* axes
# via `constrain`; the launcher installs the active mesh around tracing so
# the annotation resolves to with_sharding_constraint, and smoke tests (no
# mesh) make it a no-op.
_ACTIVATION_MESH: list = [None]


@contextlib.contextmanager
def activation_mesh(mesh):
    prev = _ACTIVATION_MESH[0]
    _ACTIVATION_MESH[0] = mesh
    try:
        yield
    finally:
        _ACTIVATION_MESH[0] = prev


def constrain(x: Array, *logical_axes) -> Array:
    mesh = _ACTIVATION_MESH[0]
    if mesh is None:
        return x
    from repro.runtime import mesh_rules

    spec = mesh_rules.logical_to_spec(tuple(logical_axes), mesh)
    return jax.lax.with_sharding_constraint(x, spec)

# ---------------------------------------------------------------- param trees


class ParamFactory:
    """Creates params and records logical-axis specs side by side."""

    def __init__(self, rng: jax.Array, dtype=jnp.float32) -> None:
        self._rng = rng
        self.dtype = dtype
        self.specs: dict = {}

    def _next(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def param(self, tree: dict, specs: dict, name: str, shape, axes, *, scale=None, zeros=False):
        assert len(shape) == len(axes), (name, shape, axes)
        if zeros:
            tree[name] = jnp.zeros(shape, self.dtype)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            s = scale if scale is not None else fan_in**-0.5
            tree[name] = (jax.random.normal(self._next(), shape) * s).astype(self.dtype)
        specs[name] = axes
        return tree[name]


def rms_norm(x: Array, gamma: Array, eps: float = 1e-6) -> Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def swiglu(x: Array, wg: Array, wi: Array, wo: Array) -> Array:
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


# ---------------------------------------------------------------------- RoPE
def rope_freqs(dim: int, theta: float = 10000.0) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x [..., S, D] with D even; positions [..., S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------- chunked attention
def chunked_attention(
    q: Array,  # [B, Hq, Sq, D]
    k: Array,  # [B, Hkv, Sk, D]
    v: Array,  # [B, Hkv, Sk, Dv]
    *,
    causal: bool = True,
    q_offset: Array | int = 0,  # absolute position of q[..., 0, :]
    block_q: int = 512,
    block_k: int = 1024,
    kv_valid_len: Array | None = None,  # mask KV positions ≥ this (decode cache)
) -> Array:
    """Flash-style online-softmax attention in pure JAX (lax.scan over KV
    blocks inside a scan over Q blocks).  Peak memory O(Bq·Bk) per (B, H)
    instead of O(Sq·Sk): this is what lets 32k prefill and 32k-cache decode
    lower within HBM on the production mesh.  GQA via head grouping."""
    b, hq, sq, d = q.shape
    _, hkv, sk, dv = v.shape[0], v.shape[1], v.shape[2], v.shape[3]
    group = hq // k.shape[1]
    bq, bk = min(block_q, sq), min(block_k, sk)
    nq, nk = -(-sq // bq), -(-sk // bk)
    qpad, kpad = nq * bq - sq, nk * bk - sk
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, qpad), (0, 0)))
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, kpad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, kpad), (0, 0)))
    scale = d**-0.5
    kg = k.reshape(b, k.shape[1], nk, bk, d)
    vg = v.reshape(b, v.shape[1], nk, bk, dv)
    valid = jnp.asarray(kv_valid_len if kv_valid_len is not None else sk)

    def q_block(iq):
        qb = jax.lax.dynamic_slice_in_dim(q, iq * bq, bq, axis=2) * scale
        qb32 = qb.astype(jnp.float32)
        rows = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(carry, ik):
            m, l, acc = carry
            kb = kg[:, :, ik].astype(jnp.float32)  # [B, Hkv, Bk, D]
            vb = vg[:, :, ik].astype(jnp.float32)
            # group query heads onto their KV head
            qh = qb32.reshape(b, k.shape[1], group, bq, d)
            s = jnp.einsum("bngqd,bnkd->bngqk", qh, kb)  # [B,Hkv,G,Bq,Bk]
            cols = ik * bk + jnp.arange(bk)
            mask = cols[None, :] <= rows[:, None] if causal else jnp.ones((bq, bk), bool)
            mask = mask & (cols < valid)[None, :]
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            # (measured in §Perf: casting p to bf16 here ADDS traffic at the
            # HLO level — the f32 p is still materialized for the row sum —
            # so the PV product stays f32; the true fix is the fused Pallas
            # flash kernel, where p never leaves VMEM.)
            acc_new = acc * alpha[..., None] + jnp.einsum("bngqk,bnkd->bngqd", p, vb)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, k.shape[1], group, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((b, k.shape[1], group, bq), jnp.float32)
        a0 = jnp.zeros((b, k.shape[1], group, bq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(b, hq, bq, dv).astype(q.dtype)

    if nq == 1:
        out = q_block(0)
    else:
        out = jax.lax.map(q_block, jnp.arange(nq))  # [nq, B, Hq, Bq, Dv]
        out = jnp.moveaxis(out, 0, 2).reshape(b, hq, nq * bq, dv)
    return out[:, :, :sq]


def dlse_decode_attention(
    q: Array,  # [B, Hq, 1, D]   (replicated over model inside the map)
    ck: Array,  # [B, Hkv, S, D]  kv_seq sharded over "model"
    cv: Array,  # [B, Hkv, S, D]
    kv_valid_len: Array,  # scalar — #valid cache positions
) -> Array:
    """Distributed log-sum-exp decode attention (§Perf, decode cells).

    The KV cache stays sequence-sharded over the model axis; every device
    computes partial softmax stats (m, l, acc) on its local chunk and the
    combine crosses the ICI as one pmax + two psums of [B, Hq, D]-sized
    tensors — KBs per layer instead of gathering the multi-GB cache.
    """
    mesh = _ACTIVATION_MESH[0]
    from jax.sharding import PartitionSpec as P

    b, hq, _, d = q.shape
    hkv = ck.shape[1]
    group = hq // hkv
    batch_ax = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    bspec = batch_ax if len(batch_ax) > 1 else batch_ax[0]

    def body(q_l, ck_l, cv_l, valid):
        # local chunk: [B_loc, Hkv, S/tp, D]
        s_loc = ck_l.shape[2]
        off = jax.lax.axis_index("model") * s_loc
        qh = q_l.reshape(q_l.shape[0], hkv, group, d).astype(jnp.float32)
        k = ck_l.astype(jnp.float32)
        v = cv_l.astype(jnp.float32)
        scores = jnp.einsum("bngd,bnsd->bngs", qh, k) * (d**-0.5)
        pos_ok = (off + jnp.arange(s_loc)) < valid
        scores = jnp.where(pos_ok[None, None, None, :], scores, -1e30)
        m = scores.max(axis=-1)  # [B, Hkv, G]
        m_glob = jax.lax.pmax(m, "model")
        p = jnp.exp(scores - m_glob[..., None])
        l = jax.lax.psum(p.sum(axis=-1), "model")
        acc = jax.lax.psum(jnp.einsum("bngs,bnsd->bngd", p, v), "model")
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(q_l.shape[0], hq, 1, d).astype(q_l.dtype)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None, None),
            P(bspec, None, "model", None),
            P(bspec, None, "model", None),
            P(),
        ),
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, ck, cv, kv_valid_len)


def dlse_mla_decode_attention(
    q: Array,  # [B, H, 1, nd+rd]
    ckv: Array,  # [B, S, kvr] latents, kv_seq sharded over "model"
    krope: Array,  # [B, S, rd]
    wuk: Array,  # [kvr, H*nd]
    wuv: Array,  # [kvr, H*vd]
    kv_valid_len: Array,
    *,
    nope_dim: int,
    v_dim: int,
) -> Array:
    """MLA variant of the distributed-LSE decode: each device expands only
    its LOCAL latent chunk (ckv @ wuk/wuv) — the S×H×d expansion never
    crosses the ICI either, on top of the KV gather it already saves."""
    mesh = _ACTIVATION_MESH[0]
    from jax.sharding import PartitionSpec as P

    b, h, _, qk = q.shape
    rd = qk - nope_dim
    batch_ax = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    bspec = batch_ax if len(batch_ax) > 1 else batch_ax[0]

    def body(q_l, ckv_l, krope_l, wuk_l, wuv_l, valid):
        s_loc = ckv_l.shape[1]
        off = jax.lax.axis_index("model") * s_loc
        k_nope = (ckv_l @ wuk_l).reshape(-1, s_loc, h, nope_dim)
        v = (ckv_l @ wuv_l).reshape(-1, s_loc, h, v_dim).astype(jnp.float32)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope_l[:, :, None], (*krope_l.shape[:2], h, rd))],
            axis=-1,
        ).astype(jnp.float32)  # [B, S_loc, H, qk]
        qf = q_l[:, :, 0].astype(jnp.float32)  # [B, H, qk]
        scores = jnp.einsum("bhd,bshd->bhs", qf, k) * (qk**-0.5)
        pos_ok = (off + jnp.arange(s_loc)) < valid
        scores = jnp.where(pos_ok[None, None, :], scores, -1e30)
        m = scores.max(axis=-1)
        m_glob = jax.lax.pmax(m, "model")
        p = jnp.exp(scores - m_glob[..., None])
        l = jax.lax.psum(p.sum(axis=-1), "model")
        acc = jax.lax.psum(jnp.einsum("bhs,bshd->bhd", p, v), "model")
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out[:, :, None, :].astype(q_l.dtype)  # [B, H, 1, vd]

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None, None),
            P(bspec, "model", None),
            P(bspec, "model", None),
            P(None, None),
            P(None, None),
            P(),
        ),
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, ckv, krope, wuk, wuv, kv_valid_len)


def cross_entropy_loss(logits: Array, labels: Array) -> Array:
    """Mean token cross-entropy; logits [..., vocab], labels [...] int32."""
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1
    )[..., 0]
    return (logz - gold).mean()
