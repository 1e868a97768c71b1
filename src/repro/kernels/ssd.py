"""Mamba-2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

TPU-native design (HW adaptation): the GPU SSD kernel in the Mamba-2 paper
leans on warp-level shuffles for the intra-chunk scan; on TPU we instead
express the intra-chunk term as two MXU matmuls (C B^T masked by the decay
matrix L, then @ X) and carry the inter-chunk recurrent state (P x N, f32) in
VMEM scratch across the sequential chunk grid dimension — the TPU grid's
last-dim sequential guarantee replaces the GPU's inter-block atomics.

grid = (B, H, S/chunk); chunk dim sequential. The kernel runs over a
head-major ``(B, H, S, P)`` layout so every block's last two dimensions
obey the TPU tiling (a multiple of (8, 128) or the whole dimension):
x (chunk, P), dt (chunk, 1) over ``(B, H, S, 1)``, B/C (chunk, N); A
rides whole in SMEM. The in-chunk cumulative sum of the log-decays is a
triangular-mask matmul (Mosaic has no cumsum). With chunk=256, P=64..128,
N=64..128 everything (inputs + L matrix (chunk x chunk f32) + state
scratch) is « 1 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EXACT = jax.lax.Precision.HIGHEST  # the decay cumsum must stay f32-exact


def _ssd_kernel(
    a_ref,  # (H,)              — A per head (SMEM, whole array)
    x_ref,  # (chunk, P)        — dt-weighted input block
    dt_ref,  # (chunk, 1)
    b_ref,  # (chunk, N)
    c_ref,  # (chunk, N)
    y_ref,  # (chunk, P)
    state_scr,  # (P, N) f32 VMEM scratch — inter-chunk recurrent state
    *,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[...].astype(jnp.float32)  # (cs, P) — already dt-weighted
    dA = dt_ref[...].astype(jnp.float32) * a_ref[pl.program_id(1)]  # (cs, 1)
    bm = b_ref[...].astype(jnp.float32)  # (cs, N)
    cm = c_ref[...].astype(jnp.float32)  # (cs, N)

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = li >= lj
    # in-chunk cumulative log-decay cum[i] = sum_{k<=i} dA[k], broadcast
    # across N lanes (cum_lanes) and laid out as a row (cum_row)
    dA_lanes = jnp.broadcast_to(dA, (chunk, bm.shape[1]))  # (cs, N)
    cum_lanes = jax.lax.dot_general(
        causal.astype(jnp.float32), dA_lanes, (((1,), (0,)), ((), ())),
        precision=_EXACT, preferred_element_type=jnp.float32,
    )  # (cs, N)
    cum = cum_lanes[:, :1]  # (cs, 1)
    cum_row = jax.lax.dot_general(
        dA_lanes, (li <= lj).astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=_EXACT, preferred_element_type=jnp.float32,
    )[:1, :]  # (1, cs)

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for j <= i
    L = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)  # (cs, cs)
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (cs, cs) = C B^T
    y_intra = jax.lax.dot_general(
        scores * L, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (cs, P)

    # inter-chunk: contribution of carried state
    y_inter = (
        jax.lax.dot_general(
            cm, state_scr[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * jnp.exp(cum)
    )  # (cs, P)

    y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: state' = e^{sum dA} state + X^T (B * decay_to_end)
    total = cum_lanes[chunk - 1:, :]  # (1, N)
    decay_to_end = jnp.exp(total - cum_lanes)  # (cs, N)
    upd = jax.lax.dot_general(
        x, bm * decay_to_end, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (P, N)
    state_scr[...] = state_scr[...] * jnp.exp(total) + upd


def ssd_pallas(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H) post-softplus
    A: jnp.ndarray,  # (H,) negative
    Bm: jnp.ndarray,  # (B, S, N)
    Cm: jnp.ndarray,  # (B, S, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    # head-major layouts: (B, H, S, P) and (B, H, S, 1)
    xw = (x * dt[..., None]).astype(x.dtype).transpose(0, 2, 1, 3)
    dth = dt.transpose(0, 2, 1)[..., None]

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, None, chunk, P), lambda b, h, c: (b, h, c, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(A.astype(jnp.float32), xw, dth, Bm, Cm)
    return y.transpose(0, 2, 1, 3)
