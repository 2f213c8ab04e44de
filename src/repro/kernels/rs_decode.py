"""Pallas TPU kernel: batched Reed-Solomon Berlekamp-Welch decode.

The paper keeps RS on the CPU because the classical decoder is branchy;
jax_rs.py already made it branch-free, and this kernel takes the last
step for the serving hot path: one pallas_call decodes a whole block of
codewords in VMEM with *zero gathers* —

* GF(2^4) multiply is computed CARRY-LESSLY (4 AND/shift/XOR partial
  products + 3 reduction steps mod x^4+x+1) instead of log/exp table
  lookups: gathers are the slow path on the TPU VPU, bitwise ops
  vectorise perfectly across the elimination state.
* inverse(a) by a select chain over the 15 nonzero field elements.
* Berlekamp-Welch = masked-pivot Gaussian elimination, unrolled over
  the static 15 columns of the (15, 15) system.
* the "pick k error-free positions" step replaces argsort with a
  running rank and selects (branch-free).

Layout: codewords on the lane axis, up to 128 per grid step.  A
per-codeword scalar is a (1, 128) row, a length-15 vector a (15, 128)
array, the (15, 15) system a (15, 15, 128) array indexed column-first,
so every block is lane-dense and every step is elementwise VPU work,
static slices, or a float32 sublane reduction (the TPU compiler reduces
float32 only; GF(16) symbols and indices are exact in it).  The
elimination state is 15 x 16 x 128 int32 (sublane-padded) = 120 KB.  Oracle:
repro.core.rs.jax_rs (itself validated against the numpy codec).

Default code only (GF(16), n=15, k=12, t=1 — the paper's 48-bit
configuration); other codes fall back to jax_rs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.rs.codec import RSCode, DEFAULT_CODE
from repro.core.rs import gf as gf_np

M, N, K = 4, 15, 12
T = (N - K) // 2  # = 1
NQ = T + 1        # deg(Q) <= t      -> t+1   = 2 coefficients
NN = T + K        # deg(Nu) <= t+k-1 -> t+k   = 13 coefficients
COLS = NQ + NN    # unknowns x = [q_0..q_t, nu_0..nu_{t+k-1}], 15 total
# Berlekamp-Welch: the key equation R_i * Q(x_i) = Nu(x_i) at each of the
# N = 15 evaluation points gives a HOMOGENEOUS linear system A x = 0 with
# shape (N rows, NQ+NN = 15 unknowns).  Whenever <= t symbol errors
# occurred, the true (Q, Nu) pair is a nonzero solution, so rank(A) < 15
# and a nontrivial nullspace vector exists; the kernel runs masked-pivot
# RREF and reads that vector off the first free column — the same
# construction (and tie-breaking rule) as jax_rs, its oracle.


def _gf16_mul(a, b):
    """Carry-less GF(16) multiply, branch-free, elementwise (operands
    broadcast)."""
    res = jnp.zeros(jnp.broadcast_shapes(a.shape, b.shape), jnp.int32)
    for i in range(M):
        res = res ^ jnp.where((b >> i) & 1 != 0, a << i, 0)
    # reduce bits 6..4 mod x^4 + x + 1 (0b10011)
    for j in (6, 5, 4):
        res = jnp.where((res >> j) & 1 != 0, res ^ (0b10011 << (j - 4)),
                        res)
    return res


def _gf16_inv(a):
    """a^-1 by table (a select chain over the 15 nonzero elements);
    inv(0) := 0."""
    g = gf_np.GF(M)
    out = jnp.zeros_like(a)
    for v in range(1, 1 << M):
        out = jnp.where(a == v, int(g.inv(v)), out)
    return out


def _kernel(bits_ref, msg_ref, cw_ref, stat_ref):
    """Codewords on the lane axis.  A per-codeword scalar is a (1, blk)
    row, a length-N vector a (N, blk) array with its entries on
    sublanes, and the B-W system A a (COLS, N, blk) array whose leading
    index is the column: a column is a leading-index slice and a row
    across columns is a sublane reduction.  Reductions run in float32
    (every value is a GF(16) symbol or an index below 16, exact in
    float32)."""
    blk = bits_ref.shape[-1]
    sub = jax.lax.broadcasted_iota(jnp.int32, (N, blk), 0)
    subf = sub.astype(jnp.float32)

    def row_max(x, axis):
        """Sublane max of a non-negative int array, kept as an axis."""
        return jnp.max(x.astype(jnp.float32), axis=axis,
                       keepdims=True).astype(jnp.int32)

    # bits (M, N, blk) -> received symbols R (N, blk), MSB first
    R = bits_ref[0]
    for m in range(1, M):
        R = (R << 1) | bits_ref[m]

    # evaluation points alpha^i (N, blk)
    exp, _ = gf_np.tables(M)
    xs = jnp.zeros((N, blk), jnp.int32)
    for i in range(N):
        xs = jnp.where(sub == i, int(exp[i]), xs)

    # the B-W system: column j < NQ is R_i x_i^j, column NQ + j is x_i^j
    pows = [jnp.ones((N, blk), jnp.int32)]
    for _ in range(1, max(NQ, NN)):
        pows.append(_gf16_mul(pows[-1], xs))
    A = jnp.stack([_gf16_mul(R, pows[j]) for j in range(NQ)]
                  + [pows[j] for j in range(NN)])  # (COLS, N, blk)

    # masked-pivot RREF over the static COLS columns (jax_rs's order)
    pivot_col = jnp.full((N, blk), COLS, jnp.int32)
    r = jnp.zeros((1, blk), jnp.int32)
    for c in range(COLS):
        elig = (sub >= r) & (A[c] != 0)
        pr = jnp.min(jnp.where(elig, subf, float(N)), axis=0,
                     keepdims=True).astype(jnp.int32)
        has = pr < N                          # (1, blk)
        on_r = sub == r                       # (N, blk)
        on_p = sub == pr
        Ar = row_max(jnp.where(on_r, A, 0), 1)   # (COLS, 1, blk)
        Ap = row_max(jnp.where(on_p, A, 0), 1)
        # after the swap row r holds Ap; normalise it by its pivot
        piv_row = _gf16_mul(Ap, _gf16_inv(Ap[c]))
        A = jnp.where(has & on_r, piv_row,
                      jnp.where(has & on_p, Ar, A))
        # eliminate column c from every other row
        f = jnp.where(has & ~on_r, A[c], 0)
        A = A ^ _gf16_mul(piv_row, f)
        pivot_col = jnp.where(on_r & has, c, pivot_col)
        r = jnp.minimum(r + has.astype(jnp.int32), N)

    # nullspace vector: first free column f; x[f] = 1 and
    # x[pivot_col[row]] = A[row, f] (char 2: -a == a).  Only Q = x[:NQ]
    # is needed.
    free = jnp.full((1, blk), COLS, jnp.int32)
    for j in range(COLS - 1, -1, -1):
        is_piv = row_max((pivot_col == j).astype(jnp.int32), 0) > 0
        free = jnp.where(is_piv, free, j)
    A_free = jnp.zeros((N, blk), jnp.int32)
    for j in range(COLS):
        A_free = jnp.where(free == j, A[j], A_free)
    Q = [(free == j).astype(jnp.int32)
         ^ row_max(jnp.where(pivot_col == j, A_free, 0), 0)
         for j in range(NQ)]

    # Q(x_s) via Horner at every evaluation point
    qx = jnp.zeros((N, blk), jnp.int32)
    for j in range(NQ - 1, -1, -1):
        qx = _gf16_mul(qx, xs) ^ Q[j]
    q_nonzero = Q[0] != 0
    for j in range(1, NQ):
        q_nonzero = q_nonzero | (Q[j] != 0)
    err = (qx == 0) & q_nonzero

    # pick the first K error-free positions (running rank): row k of
    # xs_sel / ys_sel holds the point / received symbol of the k-th
    ok_pos = (~err).astype(jnp.int32)
    rank = jnp.zeros((1, blk), jnp.int32)
    xs_sel = jnp.zeros((N, blk), jnp.int32)
    ys_sel = jnp.zeros((N, blk), jnp.int32)
    for s_ in range(N):
        ok_s = ok_pos[s_: s_ + 1] > 0
        take = ok_s & (rank < K) & (sub == rank)
        xs_sel = jnp.where(take, xs[s_: s_ + 1], xs_sel)
        ys_sel = jnp.where(take, R[s_: s_ + 1], ys_sel)
        rank = rank + ok_pos[s_: s_ + 1]

    # Lagrange re-interpolation through the K selected points,
    # evaluated at all N points: wgt_i = y_i / prod_{j!=i}(X_i ^ X_j)
    denom = jnp.ones((N, blk), jnp.int32)
    for j in range(K):
        d = jnp.where(sub == j, 1, xs_sel ^ xs_sel[j: j + 1])
        denom = _gf16_mul(denom, d)
    wgt = _gf16_mul(ys_sel, _gf16_inv(denom))
    # P(x_s) = sum_i wgt_i * prod_{j!=i}(x_s ^ X_j), the products from
    # prefix and suffix runs
    diffs = [xs ^ xs_sel[j: j + 1] for j in range(K)]
    pre = [jnp.ones((N, blk), jnp.int32)]
    for j in range(K - 1):
        pre.append(_gf16_mul(pre[-1], diffs[j]))
    suf = jnp.ones((N, blk), jnp.int32)
    P_at = jnp.zeros((N, blk), jnp.int32)
    for i in range(K - 1, -1, -1):
        P_at = P_at ^ _gf16_mul(_gf16_mul(pre[i], suf), wgt[i: i + 1])
        suf = _gf16_mul(suf, diffs[i])

    n_err = jnp.sum((P_at != R).astype(jnp.float32), axis=0,
                    keepdims=True).astype(jnp.int32)
    ok = (n_err <= T) & q_nonzero
    cw = jnp.where(ok, P_at, R)
    for m in range(M):  # symbols -> bits
        bit = (cw >> (M - 1 - m)) & 1
        cw_ref[m] = bit
        msg_ref[m] = bit[:K]
    stat_ref[0:1, :] = ok.astype(jnp.int32)
    stat_ref[1:2, :] = jnp.where(ok, n_err, -1)


def rs_decode_batch(bits, *, code: RSCode = DEFAULT_CODE,
                    block: int = 128, interpret: bool = True):
    """bits (B, n*m) int -> dict(message_bits, codeword_bits, ok,
    n_corrected).  Pallas kernel for the default (15,12) GF(16) code.

    The kernel works on the transpose: bit m of symbol s of codeword b
    at [m, s, b], codewords on the lane axis, ``block`` of them per grid
    step (a batch of at most ``block`` is one step whose block is the
    whole array, so small batches carry no padding)."""
    if (code.m, code.n, code.k) != (M, N, K):
        from repro.core.rs import jax_rs
        return jax_rs.make_batch_decoder(code)(bits)
    B = bits.shape[0]
    block = min(block, B)
    Bp = -(-B // block) * block
    bits_t = jnp.pad(bits.astype(jnp.int32), ((0, Bp - B), (0, 0)))
    bits_t = bits_t.reshape(Bp, N, M).transpose(2, 1, 0)

    def spec(*lead):
        return pl.BlockSpec(lead + (block,),
                            lambda i: (0,) * len(lead) + (i,))

    msg, cw, stat = pl.pallas_call(
        _kernel,
        grid=(Bp // block,),
        in_specs=[spec(M, N)],
        out_specs=[spec(M, K), spec(M, N), spec(2)],
        out_shape=[
            jax.ShapeDtypeStruct((M, K, Bp), jnp.int32),
            jax.ShapeDtypeStruct((M, N, Bp), jnp.int32),
            jax.ShapeDtypeStruct((2, Bp), jnp.int32),
        ],
        interpret=interpret,
        name="rs_decode",
    )(bits_t)

    def unplanar(x):
        return x.transpose(2, 1, 0).reshape(Bp, -1)[:B]

    return {"message_bits": unplanar(msg), "codeword_bits": unplanar(cw),
            "ok": stat[0, :B].astype(bool), "n_corrected": stat[1, :B]}
