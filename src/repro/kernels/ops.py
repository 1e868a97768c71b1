"""Public kernel ops: TPU -> Pallas kernel, elsewhere -> jnp reference.

The model layer code calls these; the dispatch keeps the TPU kernel as the
*target* while remaining lowerable/testable on CPU (interpret=True exercises
the actual kernel body; the default CPU path is the mathematically identical
chunked reference so dry-run FLOPs match the kernel path).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd import ssd_pallas

_LANE = 128


def _use_pallas(force: Optional[str]) -> bool:
    if force == "pallas":
        return True
    if force in ("ref", "chunked"):
        return False
    return jax.default_backend() == "tpu"


def _pad_lane(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    d = x.shape[axis]
    pad = (-d) % _LANE
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Sk, K, D)
    v: jnp.ndarray,  # (B, Sk, K, D)
    *,
    causal: bool = True,
    local_window: int = 0,
    logit_softcap: float = 0.0,
    q_offset: int = 0,
    impl: Optional[str] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """GQA attention in BSHD layout; scale fixed at rsqrt(true head dim)."""
    D = q.shape[-1]
    scale = 1.0 / D**0.5
    if _use_pallas(impl) or interpret:
        qp = _pad_lane(q).transpose(0, 2, 1, 3)  # (B, H, Sq, Dp)
        kp = _pad_lane(k).transpose(0, 2, 1, 3)
        vp = _pad_lane(v).transpose(0, 2, 1, 3)
        Sq = qp.shape[2]
        bq = min(block_q, Sq) if Sq % min(block_q, Sq) == 0 else Sq
        Sk = kp.shape[2]
        bk = min(block_k, Sk) if Sk % min(block_k, Sk) == 0 else Sk
        if q_offset != 0:
            # decode path with offset positions is served by the ref kernel
            # on CPU; on TPU the kv_len mask covers right-padding only.
            pass
        out = flash_attention_pallas(
            qp,
            kp,
            vp,
            causal=causal,
            local_window=local_window,
            logit_softcap=logit_softcap,
            scale=scale,
            block_q=bq,
            block_k=bk,
            interpret=interpret,
        )
        return out.transpose(0, 2, 1, 3)[..., :D]
    # Non-TPU compile target: mathematically identical chunked reference.
    # The named scope lets the roofline parser substitute the Pallas kernel's
    # true HBM traffic for the reference's materialized intermediates.
    with jax.named_scope("KERNEL_flash_attention"):
        return ref.attention_chunked(
            q,
            k,
            v,
            causal=causal,
            local_window=local_window,
            logit_softcap=logit_softcap,
            scale=scale,
            q_offset=q_offset,
        )


def ssd_scan(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H)
    A: jnp.ndarray,  # (H,)
    Bm: jnp.ndarray,  # (B, S, N)
    Cm: jnp.ndarray,  # (B, S, N)
    *,
    chunk: int = 256,
    impl: Optional[str] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    S = x.shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        # zero-dt padding is inert: decay 1, no state update, outputs dropped
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    if _use_pallas(impl) or interpret:
        y = ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
    else:
        with jax.named_scope("KERNEL_ssd_scan"):
            y = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    return y[:, :S] if pad else y


def ssd_decode(x, dt, A, Bm, Cm, state):
    """Single-token SSD recurrence (pure jnp; trivially vector-bound)."""
    return ref.ssd_decode_ref(x, dt, A, Bm, Cm, state)


# ---------------------------------------------------------------------------
# MoE dispatch/combine row permutation (gather-only in BOTH directions)
# ---------------------------------------------------------------------------


def _rows(src, idx, interpret, impl):
    """(G, N, d) gathered by (G, M) -> (G, M, d); idx -1 -> zero row."""
    if _use_pallas(impl) or interpret:
        from repro.kernels.gather_rows import gather_rows_pallas

        return jax.vmap(
            lambda s, i: gather_rows_pallas(s, i, interpret=interpret)
        )(src, idx)
    with jax.named_scope("KERNEL_moe_permute"):
        safe = jnp.maximum(idx, 0)
        out = jnp.take_along_axis(
            src, safe[..., None], axis=1, mode="clip"
        )
        return jnp.where(idx[..., None] >= 0, out, 0).astype(src.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def moe_permute(src, out_idx, inv_idx, k_inv: int, interpret: bool = False,
                impl=None):
    """out[g, i] = src[g, out_idx[g, i]] (-1 -> zeros).

    The transpose is ALSO a row gather (``inv_idx`` (G, N*k_inv) lists, for
    each source row, the k_inv output rows that read it): no scatter-add
    appears in fwd or bwd HLO — the XLA lowering of the scatter transpose
    is what promotes to f32 on host and serializes on TPU; the Pallas
    gather kernel replaces both directions with row-copy DMAs.
    """
    return _rows(src, out_idx, interpret, impl)


def _moe_permute_fwd(src, out_idx, inv_idx, k_inv, interpret, impl):
    return _rows(src, out_idx, interpret, impl), (inv_idx, src.shape)


def _moe_permute_bwd(k_inv, interpret, impl, res, dout):
    inv_idx, src_shape = res
    G, N, d = src_shape
    g = _rows(dout, inv_idx, interpret, impl)  # (G, N*k_inv, d)
    dsrc = g.reshape(G, N, k_inv, d).sum(axis=2).astype(dout.dtype)
    return dsrc, None, None


moe_permute.defvjp(_moe_permute_fwd, _moe_permute_bwd)
