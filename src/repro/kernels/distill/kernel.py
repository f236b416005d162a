"""Fused master-slave KD loss kernel (Pallas, TPU target).

Computes, per row, in ONE streaming sweep over vocab blocks (never
materializing a (N, V) softmax — V is 151936 for the Qwen archs):

  loss = α·CE(student, label) + (1-α)·T²·KL(softmax(t/T) ‖ softmax(s/T))

Online-rescaled running statistics per row (all VMEM scratch, fp32):
  teacher-T:  running max m_t, denom l_t, A = Σp·(t/T), B = Σp·(s/T)
  student-T:  m_sT, l_sT (logsumexp)
  student-1:  m_s1, l_s1, picked-label logit
so  KL = A/l_t - (m_t+log l_t) + (m_sT+log l_sT) - B/l_t
    CE = (m_s1+log l_s1) - picked.

Inputs may be padded along V with a large-negative FINITE value (e.g. -3e4):
exp underflows to exactly 0 and 0·finite = 0, keeping the sums exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kd_kernel(s_ref, t_ref, lbl_ref, o_ref, st, *, T: float, alpha: float,
               block_n: int, block_v: int, n_v: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        st[...] = jnp.zeros_like(st)
        st[0, :] = jnp.full((block_n,), -1e30)   # m_t
        st[4, :] = jnp.full((block_n,), -1e30)   # m_sT
        st[6, :] = jnp.full((block_n,), -1e30)   # m_s1

    s = s_ref[...].astype(jnp.float32)           # (bn, bv)
    t = t_ref[...].astype(jnp.float32)
    sT, tT = s / T, t / T
    v_idx = j * block_v + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_n, block_v), 1)
    lbl = lbl_ref[...]                           # (bn, 1)

    # --- teacher-temperature statistics (for the KL) -----------------------
    m_t, l_t, A, B = st[0, :], st[1, :], st[2, :], st[3, :]
    m_t_new = jnp.maximum(m_t, jnp.max(tT, axis=1))
    sc = jnp.exp(m_t - m_t_new)
    p = jnp.exp(tT - m_t_new[:, None])
    st[0, :] = m_t_new
    st[1, :] = l_t * sc + jnp.sum(p, axis=1)
    st[2, :] = A * sc + jnp.sum(p * tT, axis=1)
    st[3, :] = B * sc + jnp.sum(p * sT, axis=1)

    # --- student logsumexp at temperature T --------------------------------
    m_sT, l_sT = st[4, :], st[5, :]
    m_sT_new = jnp.maximum(m_sT, jnp.max(sT, axis=1))
    st[4, :] = m_sT_new
    st[5, :] = l_sT * jnp.exp(m_sT - m_sT_new) + jnp.sum(
        jnp.exp(sT - m_sT_new[:, None]), axis=1)

    # --- student logsumexp at T=1 + picked label logit (for the CE) --------
    m1, l1 = st[6, :], st[7, :]
    m1_new = jnp.maximum(m1, jnp.max(s, axis=1))
    st[6, :] = m1_new
    st[7, :] = l1 * jnp.exp(m1 - m1_new) + jnp.sum(
        jnp.exp(s - m1_new[:, None]), axis=1)
    st[8, :] = st[8, :] + jnp.sum(
        jnp.where(v_idx == lbl, s, 0.0), axis=1)

    @pl.when(j == n_v - 1)
    def _final():
        z_t = st[0, :] + jnp.log(st[1, :])
        z_sT = st[4, :] + jnp.log(st[5, :])
        z_s1 = st[6, :] + jnp.log(st[7, :])
        kl = st[2, :] / st[1, :] - z_t + z_sT - st[3, :] / st[1, :]
        ce = z_s1 - st[8, :]
        loss = alpha * ce + (1.0 - alpha) * (T ** 2) * kl
        o_ref[...] = loss.reshape(1, block_n).astype(o_ref.dtype)


def kd_loss_rows(student, teacher, labels, *, T: float = 2.0,
                 alpha: float = 0.3, block_n: int = 128, block_v: int = 512,
                 interpret: bool = True):
    """student/teacher: (N, V); labels: (N,) int32 → per-row loss (N,)."""
    N, V = student.shape
    block_n = min(block_n, N)
    block_v = min(block_v, V)
    assert N % block_n == 0 and V % block_v == 0, (N, V, block_n, block_v)
    kern = functools.partial(_kd_kernel, T=T, alpha=alpha, block_n=block_n,
                             block_v=block_v, n_v=V // block_v)
    return pl.pallas_call(
        kern,
        grid=(N // block_n, V // block_v),
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((9, block_n), jnp.float32)],
        interpret=interpret,
    )(student, teacher, labels.reshape(N, 1))[0]
