"""Flash attention for TPU (pl.pallas_call + BlockSpec VMEM tiling).

Why it exists here: the dry-run roofline showed every train/prefill cell
memory-bound, dominated by the (S, S) score/prob traffic that XLA must
materialize between the QK^T and PV matmuls (two dots cannot fuse). This
kernel keeps the (block_q, block_k) tiles and the online-softmax state in
VMEM; HBM traffic per attention is exactly q+k+v+o.

Grid layout: (batch, q_heads, nq, nk) with the kv dimension "arbitrary"
(sequential) — the running max/denominator/accumulator live in VMEM scratch
across the nk steps (the standard TPU flash schedule). GQA is folded via the
k/v index_map (kv_head = q_head // group). Causal cells skip fully-masked
blocks with pl.when, so the causal waste is runtime-skipped, not just masked.

VMEM budget per core at the default (block_q=512, block_k=512, hd=128):
  q/k/v tiles: 3 x 512x128x2B = 384 KB; s/p: 512x512x4B = 1 MB
  acc + m + l scratch: 512x128x4 + 2x512x4 = 260 KB           << 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import use_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int,
                  kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip blocks strictly above the diagonal (runtime skip).
    run = (qi + 1) * bq - 1 >= ki * bk if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0]                                   # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kpos < kv_len
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            valid = valid & (qpos >= kpos)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _out():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _flash_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, **kw):
    """Forward that also emits logsumexp rows (for the custom backward)."""
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, **kw)
    ki = pl.program_id(3)

    @pl.when(ki == kw["nk"] - 1)
    def _emit_lse():
        lse_ref[0, 0] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, block_q: int = 512,
                           block_k: int = 512) -> jax.Array:
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd), H % KV == 0.
    Returns (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    Sq_orig, Sk_orig = Sq, Sk
    if Sq % bq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, (-Sq) % bq), (0, 0)))
        Sq = q.shape[2]
    if Sk % bk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, (-Sk) % bk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, (-Sk) % bk), (0, 0)))
        Sk = k.shape[2]
    nq, nk = Sq // bq, Sk // bk
    kern = functools.partial(
        _flash_kernel, scale=1.0 / np.sqrt(hd), causal=causal,
        bq=bq, bk=bk, nk=nk, kv_len=Sk_orig)
    out = pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running denom
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=use_interpret(),
    )(q, k, v)
    return out[:, :, :Sq_orig]


# --------------------------------------------------------------------------
# backward kernels (flash bwd: recompute p from q, k and the saved lse)
# --------------------------------------------------------------------------

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, scale, causal, bq, bk, nk,
                         kv_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = (qi + 1) * bq - 1 >= ki * bk if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kpos < kv_len
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            valid = valid & (qpos >= kpos)
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _out():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                          bq, bk, nq, kv_len):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = (qi + 1) * bq - 1 >= ki * bk if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kpos < kv_len
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            valid = valid & (qpos >= kpos)
        p = jnp.where(valid, jnp.exp(s - lse[:, None]), 0.0)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _out():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention_fwd_kernel(q, k, v, causal=True, block_q=512,
                               block_k=512):
    """Like flash_attention_kernel but also returns lse (B, H, Sq)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    Sq_orig, Sk_orig = Sq, Sk
    if Sq % bq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, (-Sq) % bq), (0, 0)))
        Sq = q.shape[2]
    if Sk % bk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, (-Sk) % bk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, (-Sk) % bk), (0, 0)))
        Sk = k.shape[2]
    nq, nk = Sq // bq, Sk // bk
    kern = functools.partial(
        _flash_kernel_lse, scale=1.0 / np.sqrt(hd), causal=causal,
        bq=bq, bk=bk, nk=nk, kv_len=Sk_orig)
    o, lse = pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=use_interpret(),
    )(q, k, v)
    return o[:, :, :Sq_orig], lse[:, :, :Sq_orig]


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention_bwd_kernel(q, k, v, o, lse, do, causal=True,
                               block_q=512, block_k=512):
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    Sq_orig, Sk_orig = Sq, Sk
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    if Sq % bq:
        pq = (-Sq) % bq
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, 0), (0, pq), (0, 0)))
        # padded lse rows = +inf -> p = exp(-inf) = 0: no phantom gradients
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pq)),
                      constant_values=jnp.inf)
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pq)))
        Sq = q.shape[2]
    if Sk % bk:
        pk = (-Sk) % bk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
        Sk = k.shape[2]
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / np.sqrt(hd)
    common = dict(scale=scale, causal=causal, bq=bq, bk=bk, kv_len=Sk_orig)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, nk=nk, **common),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, qi, ki: (b, h, qi)),
            pl.BlockSpec((1, 1, bq), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=use_interpret(),
    )(q, k, v, do, lse, delta)

    # per-q-head dk/dv, then reduce over the GQA group
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq=nq, **common),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, ki, qi, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, ki, qi, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, ki, qi: (b, h, qi)),
            pl.BlockSpec((1, 1, bq), lambda b, h, ki, qi: (b, h, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Sk, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=use_interpret(),
    )(q, k, v, do, lse, delta)
    dk = dk_h.reshape(B, KV, G, Sk, hd).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, KV, G, Sk, hd).sum(axis=2).astype(v.dtype)
    return dq[:, :, :Sq_orig], dk[:, :, :Sk_orig], dv[:, :, :Sk_orig]


# --------------------------------------------------------------------------
# differentiable public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_hm(q, k, v, causal, block_q, block_k):
    return flash_attention_kernel(q, k, v, causal=causal,
                                  block_q=block_q, block_k=block_k)


def _flash_hm_fwd(q, k, v, causal, block_q, block_k):
    o, lse = flash_attention_fwd_kernel(q, k, v, causal=causal,
                                        block_q=block_q, block_k=block_k)
    return o, (q, k, v, o, lse)


def _flash_hm_bwd(causal, block_q, block_k, res, do):
    q, k, v, o, lse = res
    return flash_attention_bwd_kernel(q, k, v, o, lse, do, causal=causal,
                                      block_q=block_q, block_k=block_k)


_flash_hm.defvjp(_flash_hm_fwd, _flash_hm_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512) -> jax.Array:
    """Differentiable flash attention. Layout-adapting wrapper:
    q (B, S, H, hd), k/v (B, S, KV, hd) — the model-side layout —
    transposed to head-major for blocking.

    Under an axis_rules mesh context the kernel runs inside a shard_map
    (batch -> dp axes, heads -> model): a pallas_call is opaque to GSPMD, so
    without manual partitioning every chip would execute the FULL grid
    (observed: 2800x flops blowup on the dry run). KV heads are expanded to
    the q-head count first so the head sharding needs no cross-shard GQA
    indexing — the extra k/v HBM reads (G x) are orders of magnitude smaller
    than the score traffic this kernel eliminates."""
    from repro.dist.sharding import current_mesh, resolve_spec
    mesh = current_mesh()
    qt = q.transpose(0, 2, 1, 3)        # (B, H, S, hd)
    B, H, Sq, hd = qt.shape
    KV = k.shape[2]
    G = H // KV
    if mesh is None:
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        out = _flash_hm(qt, kt, vt, causal, block_q, block_k)
        return out.transpose(0, 2, 1, 3)

    kt = k.transpose(0, 2, 1, 3)        # (B, KV, S, hd)
    vt = v.transpose(0, 2, 1, 3)
    spec = resolve_spec((B, H, Sq, hd), ("batch", "heads", None, None), mesh)
    # k/v: same batch sharding, heads replicated across model (KV < model
    # size); each shard slices out just the kv heads its q heads map to.
    kv_spec = jax.sharding.PartitionSpec(spec[0], None, None, None)
    h_axes = spec[1]
    h_shards = 1
    if h_axes is not None:
        names = h_axes if isinstance(h_axes, tuple) else (h_axes,)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        for a in names:
            h_shards *= sizes[a]
    H_loc = H // h_shards

    def _local(a, b, c):
        if h_shards > 1:
            # local q heads are a contiguous [idx*H_loc, ...) range; their
            # kv group is contiguous too when H_loc divides G or G divides
            # H_loc (always true for powers-of-two GQA configs).
            idx = jax.lax.axis_index(h_axes if isinstance(h_axes, str)
                                     else list(h_axes))
            kv_start = (idx * H_loc) // G
            kv_count = max(1, H_loc // G)
            b = jax.lax.dynamic_slice_in_dim(b, kv_start, kv_count, axis=1)
            c = jax.lax.dynamic_slice_in_dim(c, kv_start, kv_count, axis=1)
        # custom_vjp takes nondiff args positionally (no kwargs allowed)
        return _flash_hm(a, b, c, causal, block_q, block_k)

    f = jax.shard_map(
        _local, mesh=mesh, in_specs=(spec, kv_spec, kv_spec),
        out_specs=spec, axis_names=set(mesh.axis_names), check_vma=False)
    return f(qt, kt, vt).transpose(0, 2, 1, 3)
