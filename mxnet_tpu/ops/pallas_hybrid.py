"""Pallas TPU kernels of the hybrid language-model family
(``models/hybrid_lm.py``): the KDA recurrence, one token against a
stream's state (``kda_step``) and a whole prompt in the chunk (WY) form
(``kda_chunk``); the Mamba-2 recurrence the same two ways
(``mamba2_step``, ``mamba2_chunk``: their own section below); power retention the same two
ways (``retention_step``, ``retention_chunk``: their own section); and
the grouped matmuls of the routed experts (``moe_gmm_gate_up``,
``moe_gmm_down``).  Helpers and conventions are
``pallas_kernels``'s: every ``pallas_call`` carries a ``name=``, which is
what a device trace shows.

The KDA state of one head is held TRANSPOSED, ``St = S^T`` (d_v, d_k):
the decay then scales lanes by a row vector, and both contractions with
``k`` and ``q`` are lane reductions that give columns —

    St <- St * alpha                      (alpha, k, q: (1, d_k) rows)
    u   = sum_lanes(St * k)               ((d_v, 1) column = S^T k)
    St <- St + (beta (v - u)) * k         (column x row: the rank-1 term)
    o   = sum_lanes(St * q)               ((d_v, 1) column = S^T q)

which is ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``,
``o_t = S_t^T q_t`` exactly, in float32 on the VPU; no matmul, no
transpose.  That is ``kda_step``, one token a stream.

``kda_chunk`` is the same recurrence over a prompt, rearranged so that
the work is matrix products (Kimi Linear, arXiv 2510.26692; the gated
delta rule's WY form with a per-channel decay).  With g_t = log alpha_t
<= 0 and G_t the sum of g over a span of tokens up to t, the tokens of
a span that starts from the state S0 obey

    (I + Diag(beta) tril(A, -1)) Delta = Diag(beta) (V - (K . e^G) S0)
    A_ij = sum_d k_i k_j e^(G_i - G_j)                      (j < i)
    B_ij = sum_d q_i k_j e^(G_i - G_j)                      (j <= i)
    O    = (Q . e^G) S0 + B Delta
    S_end = Diag(e^G_end) S0 + (K . e^(G_end - G))^T Delta

(Delta_i = beta_i (v_i - S_{i-1}^T Diag(alpha_i) k_i), the value the
delta rule really writes).  Solved for Delta = U - W S0 that is
U = T V, W = T (K . e^G), T = (I + Diag(beta) tril(A, -1))^-1
Diag(beta).  Three kernels, two levels:

* ``kda_chunk_intra``: every SUB-BLOCK of 16 tokens as a span of its
  own.  Its A and B are formed directly, pair by pair, on the VPU:
  e^(G_i - G_j) with j and i 1..15 tokens apart has no factoring
  e^(G_i - r) e^(r - G_j) with both exponents <= 0, and e^(-G) alone
  overflows float32 within a few tokens at the decays a model can
  draw (g down to -128 a token).  The sub-blocks sit on the LANES
  (token i of sub-block b is element [i][d, b]), so the 16 x 16
  algebra — A, B, T by forward substitution, U = T V, W = T (K . e^G),
  and what the span's outputs need, B U and Q . e^G - B W — is scalar
  code over whole registers, and a sum over d is a sum of registers.
* ``kda_chunk_wy``: the four sub-blocks of a CHUNK of 64 joined.
  Sub-block s starts from S_s = Diag(e^(G to s)) S0 + sum_{s' < s}
  Kd_{s'->s}^T Delta_s', with Kd the keys decayed from their token to
  the start of s; putting that into Delta_s = U_s - W_s S_s gives U, W
  of the chunk by block forward substitution with the 16 x 16 blocks
  W_s Kd^T, and the strictly-lower blocks of B as (Q . e^G - B W)_s
  Kd^T.  Here the reference IS between j and i — the sub-block
  boundaries — and every factor is an exponent of a sum of log-decays
  over a span of tokens, each summed as such.
* ``kda_chunk_state``: the only pass that is sequential, chunk after
  chunk against the (d_v, d_k) state, eight heads a grid step:
  Delta = U - W S0, O = B U + (Q' . e^G) S0 + B_lower Delta, S_end.

EVERY EXPONENT FORMED IS <= 0: each is the sum of g over a span of
tokens (inside a sub-block, over whole sub-blocks, or to a chunk's
end), computed as that sum and never as a difference of two longer
ones; the kernels take g itself, never log(alpha) (an alpha that
underflowed to 0 has no logarithm), and clamp nothing.  EVERY PRODUCT
IS FLOAT32: the VPU's are, and each MXU contraction runs at
``Precision.HIGHEST`` with float32 operands — the state, Delta, U, W
and T never pass through bfloat16 (``state_dtype: float32``): the
speed comes from the form, not from the precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hybrid import RETENTION_EPS
from .pallas_kernels import (_VMEM_LIMIT, _compiler_params, _interpret,
                             _vmem_spec)


def _kda_token(st, q, k, a, v_col, b):
    """One token of one head on the transposed state (module doc)."""
    st = st * a
    u = jnp.sum(st * k, axis=1, keepdims=True)
    st = st + (b * (v_col - u)) * k
    return st, jnp.sum(st * q, axis=1, keepdims=True)


def _eye(n):
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (r == c).astype(jnp.float32)


# ---------------------------------------------------------------------------
# kda_step: one token per stream against the slot's state, in place
# ---------------------------------------------------------------------------

def _kda_step_kernel(slots_ref, q_ref, k_ref, a_ref, v_ref, b_ref, s_ref,
                     o_ref, so_ref, *, hb):
    del slots_ref  # used by the index maps
    eye = _eye(s_ref.shape[-1])
    for i in range(hb):
        # v arrives as a row; the update wants it as a column, and
        # gives o as a column where the output wants a row: both
        # through the identity mask and a reduction, on the VPU
        v_col = jnp.sum(eye * v_ref[0, i:i + 1, :], axis=1, keepdims=True)
        st, o_col = _kda_token(
            s_ref[0, i], q_ref[0, i:i + 1, :], k_ref[0, i:i + 1, :],
            a_ref[0, i:i + 1, :], v_col, b_ref[0, i:i + 1, :1])
        so_ref[0, i] = st
        o_ref[0, i:i + 1, :] = jnp.sum(eye * o_col, axis=0, keepdims=True)


def kda_step(q, k, alpha, v, beta, state, slots):
    """q, k, alpha, v, beta (B, H, D) float32 (q, k normalised and
    scaled; beta repeated over D); state (S, H, D, D) float32, head
    states transposed; slots (B,) int32 -> (o (B, H, D) float32, the
    state with the B slots advanced).  The state operand is aliased to
    its output: donated under jit, the update is in place."""
    B, H, D = q.shape
    hb = 8 if H % 8 == 0 else H
    row = _vmem_spec((1, hb, D), lambda b, h, sl: (b, h, 0))
    st = _vmem_spec((1, hb, D, D), lambda b, h, sl: (sl[b], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, H // hb),
        in_specs=[row, row, row, row, row, st], out_specs=[row, st])
    o, new_state = pl.pallas_call(
        functools.partial(_kda_step_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        compiler_params=_compiler_params("arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="kda_step",
    )(slots.astype(jnp.int32), q, k, alpha, v, beta, state)
    return o, new_state


# ---------------------------------------------------------------------------
# slot_rows_write: a batch's rows into their slots, in place
# ---------------------------------------------------------------------------

def _slot_rows_kernel(slots_ref, rows_ref, pool_ref, out_ref):
    del slots_ref, pool_ref  # the index maps' and the alias's
    out_ref[...] = rows_ref[...]


def slot_rows_write(pool, rows, slots):
    """pool (S, 8, W), rows (B, 8, W), slots (B,) int32 -> the pool with
    row b written to slot ``slots[b]`` (padded rows share the scratch
    slot; the last writer wins).  One whole-tile DMA a row into the
    aliased pool: as an XLA scatter this is a loop of B row updates
    (2.3 ms for 128 rows of 295 KB on the chip, PERF.md section 6).
    ``pallas_kernels.kv_pages_write`` is the sibling that writes a
    prompt's K/V rows into the paged pools, a page a copy."""
    B = rows.shape[0]
    blk = (1,) + tuple(rows.shape[1:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B,),
        in_specs=[_vmem_spec(blk, lambda b, sl: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],  # aliased, unread
        out_specs=_vmem_spec(blk, lambda b, sl: (sl[b], 0, 0)))
    return pl.pallas_call(
        _slot_rows_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        compiler_params=_compiler_params("arbitrary"),
        interpret=_interpret(),
        name="slot_rows_write",
    )(slots.astype(jnp.int32), rows.astype(pool.dtype), pool)


# ---------------------------------------------------------------------------
# kda_chunk: a prompt in the chunk (WY) form of the recurrence
# ---------------------------------------------------------------------------

_SUB = 16      # tokens of a sub-block: the diagonal blocks, on the VPU
_CHUNK = 64    # tokens of a chunk: one step of the sequential pass


def _dot(a, b, dims=((1,), (0,))):
    """A float32 contraction at float32 precision on the MXU."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _unit(x, total):
    """x over its L2 norm along d (``total`` sums over d), as
    ``ops/hybrid.py kda_qkv`` normalises q and k."""
    return x * jax.lax.rsqrt(total(x * x) + 1e-6)


def _bsum(x):
    """(D/8, 8, L) -> (8, L): the sum over d, on every sublane (whole
    registers added, then one sublane reduction)."""
    s = jnp.sum(x, axis=0)
    return jnp.broadcast_to(jnp.sum(s, axis=0, keepdims=True), s.shape)


def _kda_intra_kernel(q_ref, k_ref, g_ref, v_ref, b_ref,
                      ul_ref, wl_ref, qp_ref, ou_ref,
                      kT, qT, vT, gT, kgT, uT, wT, A, Bm, Tm, stage,
                      *, hpt, nbt, D):
    """Every sub-block of 16 tokens from ITS OWN start, the sub-blocks
    on the lanes: token i of sub-block b is element [i][d, b], so the
    16 x 16 algebra of a sub-block is scalar code over whole registers
    and a sum over d is a sum of registers."""
    L, Dg = hpt * nbt, D // 8
    f32 = jnp.float32

    # a strided read or write wants a ref one head wide, so a head's
    # slab goes through ``stage``
    def bring(ref, into):
        for hh in range(hpt):
            stage[hh] = ref[0, :, hh * D:(hh + 1) * D].astype(f32)
        for i in range(_SUB):
            x = jnp.concatenate(
                [stage[hh, pl.ds(i, nbt, stride=_SUB), :]
                 for hh in range(hpt)], axis=0)
            into[i] = x.T.reshape(Dg, 8, L)

    def send(of, ref):
        for i in range(_SUB):
            y = of[i].reshape(D, L).T
            for hh in range(hpt):
                stage[hh, pl.ds(i, nbt, stride=_SUB), :] = \
                    y[hh * nbt:(hh + 1) * nbt]
        for hh in range(hpt):
            ref[0, :, hh * D:(hh + 1) * D] = stage[hh]

    bring(k_ref, kT)
    bring(q_ref, qT)
    bring(v_ref, vT)
    bring(g_ref, gT)
    for i in range(_SUB):            # ops/hybrid.py kda_qkv, in here
        kT[i] = _unit(kT[i], _bsum)
        qT[i] = _unit(qT[i], _bsum) * (float(D) ** -0.5)
    zero = jnp.zeros((Dg, 8, L), f32)

    def ab_row(i, _):
        ki, qi = kT[i], qT[i]
        Bm[i * _SUB + i] = _bsum(qi * ki)

        def ab_col(jj, d):
            # j = i-1 .. 0; d = G_i - G_j = g_{j+1} + .. + g_i <= 0,
            # summed as such (G_i - G_j from two long sums would lose
            # the digits that matter when both are large)
            j = i - 1 - jj
            d = d + gT[j + 1]
            p = kT[j] * jnp.exp(d)
            A[i * _SUB + j] = _bsum(ki * p)
            Bm[i * _SUB + j] = _bsum(qi * p)
            return d

        jax.lax.fori_loop(0, i, ab_col, zero)
        return 0

    jax.lax.fori_loop(0, _SUB, ab_row, 0)
    for i in range(1, _SUB):         # from here on gT is the running sum
        gT[i] = gT[i - 1] + gT[i]
    for i in range(_SUB):
        kgT[i] = kT[i] * jnp.exp(gT[i])

    def t_row(i, _):
        # (I + Diag(beta) tril(A, -1)) T = Diag(beta), row i by forward
        # substitution: the recurrence's own order, no power of A
        bi = jnp.broadcast_to(b_ref[0, 0, 0, pl.ds(i, 1), :], (8, L))

        def t_col(c, _):
            acc = jax.lax.fori_loop(
                c, i, lambda j, a: a + A[i * _SUB + j] * Tm[j * _SUB + c],
                jnp.zeros((8, L), f32))
            Tm[i * _SUB + c] = -bi * acc
            return 0

        jax.lax.fori_loop(0, i, t_col, 0)
        Tm[i * _SUB + i] = bi
        return 0

    jax.lax.fori_loop(0, _SUB, t_row, 0)

    def uw_row(i, _):
        def uw_j(j, c):
            t = Tm[i * _SUB + j]
            return c[0] + t * vT[j], c[1] + t * kgT[j]

        uT[i], wT[i] = jax.lax.fori_loop(0, i + 1, uw_j, (zero, zero))
        return 0

    jax.lax.fori_loop(0, _SUB, uw_row, 0)

    def o_row(i, _):
        def o_j(j, c):
            b = Bm[i * _SUB + j]
            return c[0] + b * uT[j], c[1] + b * wT[j]

        ou, ow = jax.lax.fori_loop(0, i + 1, o_j, (zero, zero))
        kgT[i] = ou                  # kgT and vT are read no more
        vT[i] = qT[i] * jnp.exp(gT[i]) - ow
        return 0

    jax.lax.fori_loop(0, _SUB, o_row, 0)
    send(uT, ul_ref)
    send(wT, wl_ref)
    send(kgT, ou_ref)
    send(vT, qp_ref)


def _rows16(parts):
    """``len(parts)`` rows (1, D), each repeated over its sub-block's 16
    rows -> (16 * len(parts), D)."""
    return jnp.concatenate(
        [jnp.broadcast_to(p, (_SUB, p.shape[-1])) for p in parts], axis=0)


def _kda_wy_kernel(k_ref, g_ref, ul_ref, wl_ref, qp_ref,
                   u_ref, w_ref, qg_ref, kd_ref, bs_ref, eg_ref, *, cb, D):
    """A chunk's four sub-blocks joined: what each sub-block's start
    state owes the chunk's, by block forward substitution."""
    ns = _CHUNK // _SUB
    f32 = jnp.float32
    r = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, _CHUNK), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, _CHUNK), 1)
    after = ((c > r) & (c // _SUB == r // _SUB)).astype(f32)
    whole = (c[:8] // _SUB == r[:8]).astype(f32)     # rows ns.. are empty
    zrow = jnp.zeros((1, D), f32)
    for ci in range(cb):
        at = slice(ci * _CHUNK, (ci + 1) * _CHUNK)
        k = _unit(k_ref[0, at].astype(f32),
                  lambda t: jnp.sum(t, axis=1, keepdims=True))
        ul, wl, qp = ul_ref[0, at], wl_ref[0, at], qp_ref[0, at]
        # the sum of g over each whole sub-block, and inside it after
        # each row (0/1 weights: the products are exact, the sums
        # float32): every one a sum of log-decays, <= 0
        g = g_ref[0, at]
        sums = _dot(whole, g)
        tot = [sums[s:s + 1] for s in range(ns)]

        def span(lo, hi):            # sum of tot[lo:hi], a (1, D) row <= 0
            out = zrow
            for s in range(lo, hi):
                out = out + tot[s]
            return out

        # k_j decayed from j to the end of its own sub-block
        ksrc = k * jnp.exp(_dot(after, g))
        x = [jnp.concatenate([ul[:_SUB], wl[:_SUB]], axis=1)]
        bst = [jnp.zeros((_SUB, _CHUNK), f32)]
        for s in range(1, ns):
            rows = slice(s * _SUB, (s + 1) * _SUB)
            # ... and on over the whole sub-blocks between it and s;
            # rows of s and after it count nothing
            on = jnp.concatenate(
                [jnp.exp(_rows16([span(sp + 1, s) for sp in range(s)])),
                 jnp.zeros(((ns - s) * _SUB, D), f32)], axis=0)
            p = _dot(jnp.concatenate([wl[rows], qp[rows]], axis=0),
                     ksrc * on, ((1,), (1,)))          # (32, 64)
            bst.append(p[_SUB:])
            have = jnp.concatenate(
                x + [jnp.zeros(((ns - s) * _SUB, 2 * D), f32)], axis=0)
            rhs = jnp.concatenate(
                [ul[rows], wl[rows] * jnp.exp(span(0, s))], axis=1)
            x.append(rhs - _dot(p[:_SUB], have))
        x = jnp.concatenate(x, axis=0)
        u_ref[0, at] = x[:, :D]
        w_ref[0, at] = x[:, D:]
        qg_ref[0, at] = qp * jnp.exp(
            _rows16([span(0, s) for s in range(ns)]))
        kd_ref[0, at] = ksrc * jnp.exp(
            _rows16([span(s + 1, ns) for s in range(ns)]))
        bs_ref[0, 0, at] = jnp.concatenate(bst, axis=0)
        eg_ref[0, ci] = jnp.exp(span(0, ns))


def _kda_state_kernel(u_ref, w_ref, qg_ref, kd_ref, ou_ref, bs_ref, eg_ref,
                      o_ref, sT_ref, s_scr, *, hb, nt, D):
    """The only pass that is sequential over chunks: three products a
    chunk and head against the (d_v, d_k) state, ``hb`` heads a grid
    step so that their chains interleave."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    for h in range(hb):
        at = slice(h * D, (h + 1) * D)
        st = s_scr[h]
        wq = _dot(jnp.concatenate([w_ref[0, :, at], qg_ref[0, :, at]],
                                  axis=0), st, ((1,), (1,)))
        delta = u_ref[0, :, at] - wq[:_CHUNK]
        o_ref[0, :, at] = (ou_ref[0, :, at] + wq[_CHUNK:] +
                           _dot(bs_ref[0, h], delta)).astype(o_ref.dtype)
        s_scr[h] = st * eg_ref[0, 0, :, at] + \
            _dot(delta, kd_ref[0, :, at], ((0,), (0,)))

    @pl.when(j == nt - 1)
    def _last():
        sT_ref[0] = s_scr[...]


def _divisor_at_most(n, most):
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def kda_chunk(c, g, beta):
    """A whole prompt from the zero state, in the chunk (WY) form of
    the recurrence (module doc: the equations, the three kernels, why
    every exponent is <= 0 and every product float32).

    c (B, T, 3*H*D): the short convolution's output, q | k | v, each H
    heads of D, of any float type (q and k are L2-normalised per head
    and q scaled by D^-1/2 in here, in float32, as ``kda_qkv`` does);
    g (B, T, H*D) float32: the per-channel LOG-decay, <= 0, as
    ``kda_gates`` has it before its exp; beta (B, T, H) float32.  A
    padded position carries g 0 and beta 0, which leaves the state as
    it is and makes its row of T zero; T is padded in here with more
    such positions up to a whole number of chunk PAIRS (128 tokens: a
    tile's rows are whole registers) -> (o (B, T, H*D) of c's type, the
    last state (B, H, D, D) float32, transposed as the module doc
    says)."""
    B, live, H = beta.shape
    D = g.shape[-1] // H
    if D % 8:
        raise ValueError(f"kda_chunk wants D a multiple of 8; got {D}")
    pad = ((0, 0), (0, -live % (2 * _CHUNK)), (0, 0))
    c, g, beta = jnp.pad(c, pad), jnp.pad(g, pad), jnp.pad(beta, pad)
    T = beta.shape[1]
    f32 = jnp.float32
    wide = jax.ShapeDtypeStruct((B, T, H * D), f32)

    # -- sub-blocks, each from its own start: 128 of them on the lanes
    nb = T // _SUB
    nbt = min(nb, 128)
    hpt = _divisor_at_most(H, 128 // nbt)
    L = hpt * nbt
    beta_l = jnp.transpose(
        beta.reshape(B, nb // nbt, nbt, _SUB, H // hpt, hpt),
        (0, 4, 1, 3, 5, 2)).reshape(B, H // hpt, nb // nbt, _SUB, L)

    def tile(first):                 # head h of q (0), k (1) or v (2)
        return _vmem_spec((1, nbt * _SUB, hpt * D),
                          lambda b, h, j: (b, j, first * (H // hpt) + h))

    big = pltpu.VMEM((_SUB, D // 8, 8, L), f32)
    small = pltpu.VMEM((_SUB * _SUB, 8, L), f32)
    ul, wl, qp, ou = pl.pallas_call(
        functools.partial(_kda_intra_kernel, hpt=hpt, nbt=nbt, D=D),
        grid=(B, H // hpt, nb // nbt),
        in_specs=[tile(0), tile(1), tile(0), tile(2),
                  _vmem_spec((1, 1, 1, _SUB, L),
                             lambda b, h, j: (b, h, j, 0, 0))],
        out_specs=[tile(0)] * 4, out_shape=[wide] * 4,
        scratch_shapes=[big] * 7 + [small] * 3 + [
            pltpu.VMEM((hpt, nbt * _SUB, D), f32)],
        compiler_params=_compiler_params(
            "parallel", "parallel", "parallel",
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="kda_chunk_intra",
    )(c, c, g, c, beta_l)

    # -- chunks, each from its own start
    nt = T // _CHUNK
    cb = _divisor_at_most(nt, 4)
    rows = _vmem_spec((1, cb * _CHUNK, D), lambda b, h, j: (b, j, h))
    k_rows = _vmem_spec((1, cb * _CHUNK, D), lambda b, h, j: (b, j, H + h))
    u, w, qg, kd, bst, eg = pl.pallas_call(
        functools.partial(_kda_wy_kernel, cb=cb, D=D),
        grid=(B, H, nt // cb),
        in_specs=[k_rows] + [rows] * 4,
        out_specs=[rows] * 4 + [
            _vmem_spec((1, 1, cb * _CHUNK, _CHUNK),
                       lambda b, h, j: (b, h, j, 0)),
            _vmem_spec((1, cb, 1, D), lambda b, h, j: (b, j, 0, h))],
        out_shape=[wide] * 4 + [
            jax.ShapeDtypeStruct((B, H, T, _CHUNK), f32),
            jax.ShapeDtypeStruct((B, nt, 1, H * D), f32)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "parallel"),
        interpret=_interpret(),
        name="kda_chunk_wy",
    )(c, g, ul, wl, qp)

    # -- the state, chunk after chunk
    hb = 8 if H % 8 == 0 else H
    rows = _vmem_spec((1, _CHUNK, hb * D), lambda b, h, j: (b, j, h))
    o, last = pl.pallas_call(
        functools.partial(_kda_state_kernel, hb=hb, nt=nt, D=D),
        grid=(B, H // hb, nt),
        in_specs=[rows] * 5 + [
            _vmem_spec((1, hb, _CHUNK, _CHUNK),
                       lambda b, h, j: (b, h, j, 0)),
            _vmem_spec((1, 1, 1, hb * D), lambda b, h, j: (b, j, 0, h))],
        out_specs=[rows, _vmem_spec((1, hb, D, D),
                                    lambda b, h, j: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * D), c.dtype),
                   jax.ShapeDtypeStruct((B, H, D, D), f32)],
        scratch_shapes=[pltpu.VMEM((hb, D, D), f32)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=_interpret(),
        name="kda_chunk_state",
    )(u, w, qg, kd, ou, bst, eg)
    return o[:, :live], last


# ---------------------------------------------------------------------------
# mamba2: S_t = a_t S_{t-1} + dx_t B_t^T, y_t = S_t C_t — a head's state
# (P, N) float32 with the state dimension N on the lanes, a_t a number a
# head, B_t and C_t rows of N shared by every head (one group).
#
# ``mamba2_step``: one token a stream, on the VPU in float32.  dx comes
# as a row and scales the state's ROWS, y leaves as a row and is a sum
# over the state's LANES: both turns go through the identity mask and a
# reduction, as in ``kda_step``.
#
# ``mamba2_chunk_scan``: a prompt in chunks of Q tokens (the SSD form,
# ``ops/hybrid.py mamba2_chunked``): per chunk ONE product C B^T for all
# heads, per head the decay mask exp(l_i - l_j)[i >= j] from the running
# sum l of log a inside the chunk (given as rows; the column by the
# identity mask), then three products — (C B^T . mask) dX, C S_prev^T,
# dX^T (exp(l_Q - l) . B) — in the model's type with float32 sums; the
# (P, N) float32 state is carried chunk to chunk in VMEM.  Every
# exponent is a sum of log-decays over a span of tokens, <= 0.
# ---------------------------------------------------------------------------

def _mamba2_step_kernel(slots_ref, a_ref, dx_ref, b_ref, c_ref, s_ref,
                        y_ref, so_ref, *, hb):
    del slots_ref  # used by the index maps
    b, h = pl.program_id(0), pl.program_id(1)
    eye = _eye(s_ref.shape[-2])
    b_row, c_row = b_ref[0], c_ref[0]                  # (1, N)
    for i in range(hb):
        dx_col = jnp.sum(eye * dx_ref[0, i:i + 1, :], axis=1, keepdims=True)
        # the decay is a NUMBER a head: a scalar, from SMEM
        st = s_ref[0, i] * a_ref[b, h * hb + i] + dx_col * b_row
        so_ref[0, i] = st
        y_col = jnp.sum(st * c_row, axis=1, keepdims=True)
        y_ref[0, i:i + 1, :] = jnp.sum(eye * y_col, axis=0, keepdims=True)


def mamba2_step(dx, a, bm, cm, state, slots):
    """dx (B, H, P) = Delta . x, a (B, H) the decay, bm, cm (B, N), all
    float32; state (S, H, P, N) float32; slots (B,) int32 -> (y (B, H,
    P) float32 = S_new C, the state with the B slots advanced).  The
    state operand is aliased to its output: donated under jit, the
    update is in place."""
    B, H, P = dx.shape
    N = state.shape[-1]
    hb = _divisor_at_most(H, 16)
    if hb % 8 and hb != H:
        hb = H
    row = _vmem_spec((1, hb, P), lambda b, h, sl, a: (b, h, 0))
    vec = _vmem_spec((1, 1, N), lambda b, h, sl, a: (b, 0, 0))
    st = _vmem_spec((1, hb, P, N), lambda b, h, sl, a: (sl[b], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, H // hb),
        in_specs=[row, vec, vec, st], out_specs=[row, st])
    y, new_state = pl.pallas_call(
        functools.partial(_mamba2_step_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={5: 1},
        compiler_params=_compiler_params("arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="mamba2_step",
    )(slots.astype(jnp.int32), a, dx, bm[:, None], cm[:, None], state)
    return y, new_state


MAMBA2_CHUNK = 128    # tokens of a chunk: a row of l fills the lanes


def _mamba2_chunk_kernel(dx_ref, b_ref, c_ref, l_ref, y_ref, s_ref, s_scr,
                         *, hb, nt, P):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    f32 = jnp.float32
    Q = dx_ref.shape[1]
    mm = dx_ref.dtype
    prec = jax.lax.Precision.HIGHEST if mm == f32 else None

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                                   preferred_element_type=f32)

    bq, cq = b_ref[0], c_ref[0]                        # (Q, N)
    cb = dot(cq, bq, ((1,), (1,)))                     # (Q, Q), all heads'
    r = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    eye = (r == c).astype(f32)
    last = c == Q - 1
    last_p = jax.lax.broadcasted_iota(jnp.int32, (P, Q), 1) == Q - 1
    bf = bq.astype(f32)
    for i in range(hb):
        at = slice(i * P, (i + 1) * P)
        l_row = l_ref[0, i:i + 1, :]                   # (1, Q): l_j
        l_col = jnp.sum(eye * l_row, axis=1, keepdims=True)      # l_i
        decay = jnp.where(r >= c,
                          jnp.exp(jnp.minimum(l_col - l_row, 0.0)), 0.0)
        xh = dx_ref[0, :, at]                          # (Q, P)
        st = s_scr[i]                                  # (P, N)
        y_ref[0, :, at] = dot((cb * decay).astype(mm), xh, ((1,), (0,))) \
            + jnp.exp(l_col) * dot(cq, st.astype(mm), ((1,), (1,)))
        # l_Q, the chunk's whole sum, on every row of a column (Q rows
        # for the keys, P for the state)
        tot = jnp.sum(jnp.where(last, l_row, 0.0), axis=1, keepdims=True)
        tot_p = jnp.sum(jnp.where(last_p, l_row, 0.0), axis=1,
                        keepdims=True)
        wb = (jnp.exp(tot - l_col) * bf).astype(mm)    # (Q, N)
        s_scr[i] = jnp.exp(tot_p) * st + dot(xh, wb, ((0,), (0,)))

    @pl.when(j == nt - 1)
    def _last():
        s_ref[0] = s_scr[...]


def mamba2_chunk(dx, xbc, la):
    """A whole prompt from the zero state in the chunk (SSD) form.

    dx (B, T, H*P) = Delta . x in the model's type; xbc (B, T, H*P +
    2*N): the short convolution's output, whose last 2 N columns are B
    and C (one group; read in place, a block of N columns each); la
    (B, T, H) float32: log a <= 0.  A padded position carries la 0 and
    dx 0, which leaves the state as it is; T is padded in here with
    more such positions up to whole chunks -> (y (B, T, H*P) float32,
    the last state (B, H, P, N) float32)."""
    B, live, H = la.shape
    di = dx.shape[-1]
    P, N = di // H, (xbc.shape[-1] - di) // 2
    Q = MAMBA2_CHUNK
    pad = ((0, 0), (0, -live % Q), (0, 0))
    dx, xbc, la = jnp.pad(dx, pad), jnp.pad(xbc, pad), jnp.pad(la, pad)
    T = la.shape[1]
    nt = T // Q
    # the running sum of log a inside each chunk, a head's on a row
    cum = jnp.cumsum(la.reshape(B, nt, Q, H), axis=2)
    cum = jnp.transpose(cum.reshape(B, T, H), (0, 2, 1))
    hb = _divisor_at_most(H, 8)
    if (hb % 8 or (hb * P) % 128) and hb != H:
        hb = H
    if di % N == 0:
        bm = cm = xbc
        b_at, c_at = di // N, di // N + 1
    else:   # B and C do not start on a block of N columns: cut them out
        bm, cm = xbc[..., di:di + N], xbc[..., di + N:]
        b_at = c_at = 0
    rows = _vmem_spec((1, Q, hb * P), lambda b, h, j: (b, j, h))
    y, last = pl.pallas_call(
        functools.partial(_mamba2_chunk_kernel, hb=hb, nt=nt, P=P),
        grid=(B, H // hb, nt),
        in_specs=[rows,
                  _vmem_spec((1, Q, N), lambda b, h, j: (b, j, b_at)),
                  _vmem_spec((1, Q, N), lambda b, h, j: (b, j, c_at)),
                  _vmem_spec((1, hb, Q), lambda b, h, j: (b, h, j))],
        out_specs=[rows, _vmem_spec((1, hb, P, N),
                                    lambda b, h, j: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, di), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=_interpret(),
        name="mamba2_chunk_scan",
    )(dx, bm, cm, cum)
    return y[:, :live], last


# ---------------------------------------------------------------------------
# Power retention (``ops/hybrid.py`` RetentionChunk / RetentionStep): gated
# degree-2 linear attention, ONE state a KV head read by its G query
# heads.  The state is the symmetric square of the key, packed by lane
# rolls (``ops/hybrid.py retention_phi``): block delta of D / 2 + 1 holds
# ``w k[a] k[(a + delta) % D]`` on lane a, and is held TRANSPOSED —
# ``St[delta]`` (D, D) with the VALUE's lane l on the rows and the key's
# lane a on the lanes, a slot's (D / 2 + 1) * D rows of D lanes back to
# back — so that phi is always a ROW: one roll of the lanes and one
# product, broadcast down the rows, and a read of the state is a sum over
# lanes.  The normaliser is ``Z = sum decay c k k^T`` (D, D): ``q^T Z q``
# is the sum of a query's weights, 64 KB a KV head beside the state's
# 4.26 MB.
#
# ``retention_step``: one token a stream on the VPU in float32, grid
# (rows, KV heads): a head's whole state is one block in, one block out
# (aliased, through the ``slots`` scalar operand), ``St <- a St + v_col
# phi(k)_row`` and ``acc_i += St phi(q_i)_row`` a query head as it passes
# — 3 + 2 G operations a register for 2 x 4.26 MB of traffic.  THE
# COPIES BIND, at what the chip gives a read and a write of one size:
# alone (PERF.md §6, PR 46; us a grid step at the gen cell's shapes) a
# head's block read and not written takes 6.15, written and not read
# 7.07, both 13.4 through the BlockSpec pipeline and 13.0-13.4 by the
# kernel's own copies however they are cut and ordered — 77-80% of 819
# GB/s, not the pipeline's doing — so the pipeline stays.  The walk is
# kept well under that: the D / 2 + 1 rows of phi, a query head each,
# are made once a head (one roll, two products a block for all the rows
# of x) and kept as ROWS in VMEM; the walk goes through the blocks once
# for ``groups`` groups of 8 value rows at a time
# (``_retention_step_blocks``), the groups' G accumulators each and
# their value columns held in registers, so a row of phi is loaded once
# (and broadcast down a register by its products) for ``groups``
# registers of state: 1 + (G + 1) / groups loads a register where a group a pass had
# 2 + G.  On a block that is not fetched again the whole body reads 4.4
# us a grid step (the walk 1.1), where value rows outside and blocks
# inside, phi broadcast into VMEM and the sums of weights on the VPU read
# 15.4 — above the copies, which is what the kernel cost then.  ``q^T Z
# q`` is one product of x's rows with Z for all the heads; ``y`` leaves
# as whole rows, a division and a store a query head.
#
# ``retention_chunk``: a prompt in chunks of ``RETENTION_CHUNK`` tokens
# (``ops/hybrid.py retention_chunked``), grid (rows, KV heads, chunks),
# the float32 state carried chunk to chunk in VMEM.  A chunk's own
# tokens in the attention form, a query head at a time: ``(c Q K^T)^2 .
# decay`` (the decay mask from the running log-gate, one a KV head), its
# row sums, its product with V.  The chunks before it through the state,
# the G query heads STACKED as rows: block by block ``phi_delta(Q)`` (one
# roll, one product, in float32, rounded to the model's type) times
# ``St[delta]^T``, and ``q^T Z q`` as one more product.  Then the state:
# ``St[delta] <- e^(l_Q) St[delta] + (decayed V)^T phi_delta(K)``.  A
# chunk that starts at or past ``lengths[b]`` does nothing (its rows
# leave as 0): the first chunk of a prompt skips the state's part, which
# is zero.  Products in the model's type with float32 sums, the state
# float32; every exponent a sum of log-gates over a span, <= 0.
# ---------------------------------------------------------------------------

RETENTION_CHUNK = 256   # tokens of a chunk


def retention_enabled(D):
    """The kernels take the op?  On the chip a head of whole 128-lane
    rows (another width gets the lax body); interpreted, any."""
    from . import pallas_kernels as pk

    return pk.enabled() and (_interpret() or D % 128 == 0)


def _phi_scale(delta, D):
    """``w[delta] * D^-1/2`` (``ops/hybrid.py retention_weights``)."""
    one = (delta == 0) | (delta == D // 2)
    return jnp.where(one, 1.0, 2.0 ** 0.5).astype(jnp.float32) \
        * (float(D) ** -0.5)


def _lane_roll(x, delta, D):
    """``out[..., a] = x[..., (a + delta) % D]``."""
    return pltpu.roll(x, (D - delta) % D, x.ndim - 1)


def _retention_step_blocks(G, D):
    """(groups, blocks) of ``retention_step``'s walk over a head's state:
    ``groups`` groups of 8 value rows are walked in one pass and
    ``blocks`` of the D / 2 + 1 blocks in one turn of its loops.  The
    shapes decide, nothing else; each choice is a row of the kernel-alone
    table in PERF.md §6, PR 46.  A pass keeps in registers, for each of
    its groups, G accumulators and the group's value column, beside the
    G + 1 rows of phi of the block it is at: as many groups as leave a
    quarter of the 64 registers to the products in flight (at G = 5 two
    groups read 8.1 us a head, four 6.7, eight 6.3 with registers
    spilt).  A turn holds at least 48 accumulations, in whole blocks: a
    turn of 20 fills and drains its chains more than it works (3.4 us a
    head's walk against 1.1 at 100)."""
    nd = D // 2 + 1
    groups = _divisor_at_most(D // 8, max(1, (48 - (G + 1)) // (G + 1)))
    blocks = min(u for u in range(1, nd + 1)
                 if nd % u == 0 and (groups * G * u >= 48 or u == nd))
    return groups, blocks


def _retention_step_kernel(slots_ref, a_ref, x_ref, v_ref, s_ref, z_ref,
                           y_ref, so_ref, zo_ref, phi_scr, vcol_scr,
                           acc_scr, *, G, D, groups, blocks):
    del slots_ref  # used by the index maps
    b, j = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32
    nd = D // 2 + 1
    c = float(D) ** -0.5
    a = a_ref[b, j]                    # the gate: a scalar, from SMEM
    eye = _eye(D)
    x = x_ref[0, 0]                    # rows: k, q_0 .. q_(G-1), zeros
    k_row = x[0:1]
    k_col = jnp.sum(eye * k_row, axis=1, keepdims=True)
    v_col = jnp.sum(eye * v_ref[0, 0], axis=1, keepdims=True)
    vcol_scr[...] = jnp.broadcast_to(v_col, (D, D))
    # the normaliser, and every row's x^T Z x on its sublane: one
    # product for all the query heads
    z = a * z_ref[0, 0] + (c * k_col) * k_row
    zo_ref[0, 0] = z
    xz = jax.lax.dot_general(x, z, (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=f32)
    den = c * jnp.sum(xz * x, axis=1, keepdims=True)         # (rows, 1)

    # phi, every block: one roll and one product for all the rows of x
    # (``blocks`` a turn: a roll is long in coming, several are on
    # their way at once)
    def make(turn, carry):
        for u in range(blocks):
            delta = turn * blocks + u
            phi_scr[delta] = x * _lane_roll(x, delta, D) \
                * _phi_scale(delta, D)
        return carry

    jax.lax.fori_loop(0, nd // blocks, make, 0)

    span = 8 * groups

    def one_pass(p, carry):
        base = pl.multiple_of(p * span, span)
        vcs = [vcol_scr[pl.ds(base + g * 8, 8), :] for g in range(groups)]

        def body(turn, accs):
            accs = list(accs)
            for u in range(blocks):
                delta = turn * blocks + u
                # a row of phi, read once for the pass's groups (the
                # products broadcast it down a register)
                pk_, *pqs = (phi_scr[delta, pl.ds(n, 1), :]
                             for n in range(G + 1))
                for g in range(groups):
                    at = pl.multiple_of(delta * D + base + g * 8, 8)
                    st = a * s_ref[0, 0, pl.ds(at, 8), :] + vcs[g] * pk_
                    so_ref[0, 0, pl.ds(at, 8), :] = st
                    for i, pq in enumerate(pqs):
                        accs[g * G + i] = accs[g * G + i] + st * pq
            return tuple(accs)

        accs = jax.lax.fori_loop(
            0, nd // blocks, body,
            tuple(jnp.zeros((8, D), f32) for _ in range(groups * G)))
        for g in range(groups):
            for i in range(G):
                acc_scr[i, pl.ds(base + g * 8, 8), :] = accs[g * G + i]
        return carry

    jax.lax.fori_loop(0, (D // 8) // groups, one_pass, 0)
    # a query head's sums over the key's lanes, turned from a column to
    # the output's row: one division and one store a head
    for i in range(G):
        num = jnp.sum(acc_scr[i], axis=1, keepdims=True)     # (D, 1)
        row = jnp.sum(eye * num, axis=0, keepdims=True)      # (1, D)
        y_ref[0, 0, i:i + 1, :] = row / (den[1 + i:2 + i] + RETENTION_EPS)


def retention_step(q, k, v, a, state, norm, slots):
    """q (B, Hkv, G, D), k, v (B, Hkv, D), a (B, Hkv) the gate, all
    float32 (q and k unscaled: the kernel applies D^-1/2 to phi); state
    (S, Hkv, (D/2 + 1) * D, D) and norm (S, Hkv, D, D) float32; slots
    (B,) int32 -> (y (B, Hkv, G, D) float32, both pools with the B
    slots advanced).  The pools are aliased to their outputs: donated
    under jit, the update is in place."""
    from .. import profiler

    B, Hkv, G, D = q.shape
    R = state.shape[2]
    rows = -(-(G + 1) // 8) * 8
    groups, blocks = _retention_step_blocks(G, D)
    # the schedule a build chose, on ``/metrics`` beside ``flash.*``
    profiler.set_gauge("retention.step_row_groups", groups)
    profiler.set_gauge("retention.step_loads_per_register",
                       1 + (G + 1) / groups)
    x = jnp.concatenate([k[:, :, None], q], axis=2)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, rows - G - 1), (0, 0)))
    at = lambda b, j, sl, a: (b, j, 0, 0)
    slot = lambda b, j, sl, a: (sl[b], j, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, Hkv),
        in_specs=[_vmem_spec((1, 1, rows, D), at),
                  _vmem_spec((1, 1, 1, D), at),
                  _vmem_spec((1, 1, R, D), slot),
                  _vmem_spec((1, 1, D, D), slot)],
        out_specs=[_vmem_spec((1, 1, G, D), at),
                   _vmem_spec((1, 1, R, D), slot),
                   _vmem_spec((1, 1, D, D), slot)],
        scratch_shapes=[pltpu.VMEM((D // 2 + 1, rows, D), jnp.float32),
                        pltpu.VMEM((D, D), jnp.float32),
                        pltpu.VMEM((G, D, D), jnp.float32)])
    y, state, norm = pl.pallas_call(
        functools.partial(_retention_step_kernel, G=G, D=D, groups=groups,
                          blocks=blocks),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
        input_output_aliases={4: 1, 5: 2},
        compiler_params=_compiler_params("arbitrary", "arbitrary",
                                         vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="retention_step",
    )(slots.astype(jnp.int32), a, x, v[:, :, None], state, norm)
    return y, state, norm


def _retention_chunk_kernel(n_ref, q_ref, k_ref, v_ref, l_ref, y_ref,
                            s_ref, z_ref, s_scr, sm_scr, z_scr, q_scr,
                            num_scr, *, G, D, nt):
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32
    Q = k_ref.shape[1]
    nd = D // 2 + 1
    c = float(D) ** -0.5
    mm = k_ref.dtype
    hi = jax.lax.Precision.HIGHEST
    prec = hi if mm == f32 else None

    def dot(x, y, dims, precision=prec):
        return jax.lax.dot_general(x, y, (dims, ((), ())),
                                   precision=precision,
                                   preferred_element_type=f32)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)
        z_scr[...] = jnp.zeros_like(z_scr)

    live = j * Q < n_ref[b]

    @pl.when(jnp.logical_not(live))
    def _dead():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live)
    def _chunk():
        r = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        cc = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        l_row = l_ref[0, pl.ds(h, 1), :]                   # (1, Q): l_j
        l_col = jnp.sum(jnp.where(r == cc, l_row, 0.0), axis=1,
                        keepdims=True)                     # l_i
        decay = jnp.where(r >= cc,
                          jnp.exp(jnp.minimum(l_col - l_row, 0.0)), 0.0)
        tot = jnp.sum(jnp.where(cc[:1] == Q - 1, l_row, 0.0), axis=1,
                      keepdims=True)                       # (1, 1): l_Q
        el = jnp.exp(l_col)                                # (Q, 1)
        kq, vq = k_ref[0], v_ref[0]                        # (Q, D)
        # the chunk's own tokens, a query head at a time
        dens = []
        for i in range(G):
            qi = q_ref[0, :, i * D:(i + 1) * D]
            s = c * dot(qi, kq, ((1,), (1,)))
            w = s * s * decay
            dens.append(jnp.sum(w, axis=1, keepdims=True))
            num_scr[i * Q:(i + 1) * Q] = dot(w.astype(mm), vq,
                                             ((1,), (0,)))
            q_scr[i * Q:(i + 1) * Q] = qi.astype(f32)

        # the chunks before it, through the state: G heads stacked
        @pl.when(j > 0)
        def _inter():
            qs = q_scr[...]                                # (G Q, D)
            els = jnp.concatenate([el] * G, axis=0)

            def block(delta, carry):
                p = (qs * _lane_roll(qs, delta, D)
                     * _phi_scale(delta, D)).astype(mm)
                num_scr[...] += els * dot(p, sm_scr[delta],
                                          ((1,), (1,)))
                return carry

            jax.lax.fori_loop(0, nd, block, 0)

        qz = dot(q_scr[...], z_scr[...], ((1,), (0,)), hi)
        den_z = c * jnp.sum(qz * q_scr[...], axis=1, keepdims=True)
        for i in range(G):
            at = slice(i * Q, (i + 1) * Q)
            den = dens[i] + el * den_z[at]
            y_ref[0, :, i * D:(i + 1) * D] = (
                num_scr[at] / (den + RETENTION_EPS)).astype(y_ref.dtype)

        # the state the chunk leaves
        kf = kq.astype(f32)
        wk = jnp.exp(tot - l_col)                          # (Q, 1)
        vd = (wk * vq.astype(f32)).astype(mm)
        e = jnp.exp(tot)

        def build(delta, carry):
            p = (kf * _lane_roll(kf, delta, D)
                 * _phi_scale(delta, D)).astype(mm)
            at = pl.multiple_of(delta * D, D)
            st = e * s_scr[pl.ds(at, D), :] + dot(vd, p, ((0,), (0,)))
            s_scr[pl.ds(at, D), :] = st
            sm_scr[delta] = st.astype(mm)
            return carry

        jax.lax.fori_loop(0, nd, build, 0)
        z_scr[...] = e * z_scr[...] + c * dot(wk * kf, kf, ((0,), (0,)),
                                              hi)

    @pl.when(j == nt - 1)
    def _last():
        s_ref[0, 0] = s_scr[...]
        z_ref[0, 0] = z_scr[...]


def retention_chunk(q, k, v, la, lengths):
    """A whole prompt from the zero state in the chunk form.

    q (B, T, Hkv*G*D), k, v (B, T, Hkv*D) in the model's type, rotated,
    KV head j's G query heads side by side; la (B, T, Hkv) float32: the
    log-gate <= 0.  A padded position carries la 0 and k = v = 0, which
    leaves the state as it is; T is padded in here with more such
    positions up to whole chunks; lengths (B,) int32: a chunk at or past
    it is not walked -> (y (B, T, Hkv*G*D) in q's type, the last state
    (B, Hkv, (D/2 + 1) * D, D) and the last normaliser (B, Hkv, D, D),
    float32)."""
    B, live, Hkv = la.shape
    D = k.shape[-1] // Hkv
    G = q.shape[-1] // (Hkv * D)
    Q = RETENTION_CHUNK
    R = (D // 2 + 1) * D
    pad = ((0, 0), (0, -live % Q), (0, 0))
    q, k, v, la = (jnp.pad(t, pad) for t in (q, k, v, la))
    T = la.shape[1]
    nt = T // Q
    # the running sum of the log-gate inside each chunk, a head's on a row
    cum = jnp.cumsum(la.reshape(B, nt, Q, Hkv), axis=2)
    cum = jnp.transpose(cum.reshape(B, T, Hkv), (0, 2, 1))
    rows = lambda w: _vmem_spec((1, Q, w), lambda b, h, j, n: (b, j, h))
    head = lambda r: _vmem_spec((1, 1, r, D), lambda b, h, j, n: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, Hkv, nt),
        in_specs=[rows(G * D), rows(D), rows(D),
                  _vmem_spec((1, Hkv, Q), lambda b, h, j, n: (b, 0, j))],
        out_specs=[rows(G * D), head(R), head(D)],
        scratch_shapes=[pltpu.VMEM((R, D), jnp.float32),
                        pltpu.VMEM((D // 2 + 1, D, D), k.dtype),
                        pltpu.VMEM((D, D), jnp.float32),
                        pltpu.VMEM((G * Q, D), jnp.float32),
                        pltpu.VMEM((G * Q, D), jnp.float32)])
    y, last, z = pl.pallas_call(
        functools.partial(_retention_chunk_kernel, G=G, D=D, nt=nt),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, T, Hkv * G * D), q.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, R, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hkv, D, D), jnp.float32)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary",
                                         vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="retention_chunk",
    )(lengths.astype(jnp.int32), q, k, v, cum)
    return y[:, :live], last, z


# ---------------------------------------------------------------------------
# moe_gmm: grouped matmuls over rows sorted by expert
#
# Rows arrive sorted by expert, each expert's run padded to whole tiles
# of ``tm`` rows (``ops/hybrid.py`` lays them out), so a tile belongs to
# one expert: ``tile_expert[t]``, scalar-prefetched, picks the weight
# block.  The grid covers the worst case (every pair routed here); only
# ``n_used[0]`` tiles hold rows.  A tile past them does nothing, and its
# index maps name the LAST block a used tile touched, so no block is
# fetched or written for it.  K is cut in blocks of whole rows of the
# (E, K, N) weights: each block is one contiguous piece of HBM.
#
# BY INDEX (a prompt's tiles of 128 rows): ``moe_gmm_gate_up(...,
# row_token=)`` takes the TOKEN rows and copies a tile's 128 into VMEM
# itself, a copy a row, started a tile ahead of the matmul that uses
# them, for the used tiles only: the dispatched rows are never laid out
# in HBM.  ``moe_gmm_down(..., slabs=True)`` leaves each output row so
# that ``moe_gmm_combine`` can copy it the same way, weigh it and add a
# token's rows.  Mosaic copies no single row of a tiled (rows, d) array,
# so a row that is copied alone is a SLAB: (d / 128, 128) float32 in
# whole (8, 128) tiles (``token_slabs``), one contiguous piece of HBM,
# and row i of a tile is sublanes i·R.. of a flat (tile·R, 128) buffer:
# lane chunk c of every row is one strided read or write of it.
# ---------------------------------------------------------------------------

_LANES = 128
# row copies started (or awaited) a turn of the loop over a tile's rows
_ROW_UNROLL = 4


def slab_rows(d):
    """Sublanes of one row of ``d`` values as a slab of whole float32
    tiles; 0 where ``d`` is no whole number of lane tiles (no slabs)."""
    return 0 if d % _LANES else -(-d // _LANES // 8) * 8


def token_slabs(x):
    """x (N, d) -> (N, R, 128) float32, R = ``slab_rows(d)``: row n's
    values in reading order, zeros after them."""
    n, d = x.shape
    return jnp.pad(x.astype(jnp.float32).reshape(n, d // _LANES, _LANES),
                   ((0, 0), (0, slab_rows(d) - d // _LANES), (0, 0)))


def _gmm_maps(nk):
    def tile(t, nu):
        return jnp.minimum(t, jnp.maximum(nu[0] - 1, 0))

    def kblock(t, kk, nu):
        return jnp.where(t < nu[0], kk, nk - 1)

    x_map = lambda t, kk, te, nu, *_: (tile(t, nu), kblock(t, kk, nu))
    w_map = lambda t, kk, te, nu, *_: (te[tile(t, nu)], kblock(t, kk, nu), 0)
    o_map = lambda t, kk, te, nu, *_: (tile(t, nu), 0)
    return x_map, w_map, o_map


def _gate_up_steps(accg, accu, o_ref, x, wg_ref, wu_ref, used, kk, nk, act):
    @pl.when(used & (kk == 0))
    def _init():
        accg[...] = jnp.zeros_like(accg)
        accu[...] = jnp.zeros_like(accu)

    @pl.when(used)
    def _mul():
        xk = x()
        accg[...] += jnp.dot(xk, wg_ref[0],
                             preferred_element_type=jnp.float32)
        accu[...] += jnp.dot(xk, wu_ref[0],
                             preferred_element_type=jnp.float32)

    @pl.when(used & (kk == nk - 1))
    def _out():
        g = accg[...]
        g = jnp.maximum(g, 0.0) if act == "relu" else g * jax.nn.sigmoid(g)
        o_ref[...] = (g * accu[...]).astype(o_ref.dtype)


def _gmm_gate_up_kernel(te_ref, nu_ref, x_ref, wg_ref, wu_ref, o_ref,
                        accg, accu, *, nk, act):
    del te_ref
    t, kk = pl.program_id(0), pl.program_id(1)
    _gate_up_steps(accg, accu, o_ref, lambda: x_ref[...], wg_ref, wu_ref,
                   t < nu_ref[0], kk, nk, act)


def _gmm_gate_up_rows_kernel(te_ref, nu_ref, rt_ref, x_hbm, wg_ref, wu_ref,
                             o_ref, xbuf, sem, accg, accu, *, nk, tm, rp,
                             act):
    del te_ref
    t, kk = pl.program_id(0), pl.program_id(1)
    nu = nu_ref[0]
    slot = t % 2

    def copies(tile, slot, op):
        # the tile's tm rows, each token's slab to its sublanes of the
        # slot's buffer (a wait names any slab: it counts one's bytes),
        # a few a turn of a loop the compiler keeps rolled
        def rows(g, _):
            for u in range(_ROW_UNROLL):
                i = g * _ROW_UNROLL + u
                token = rt_ref[tile * tm + i] if op == "start" else 0
                getattr(pltpu.make_async_copy(
                    x_hbm.at[token],
                    xbuf.at[slot, pl.ds(pl.multiple_of(i * rp, 8), rp)],
                    sem.at[slot]), op)()

        jax.lax.fori_loop(0, tm // _ROW_UNROLL, rows, None)

    # who starts whose copies: a tile the NEXT tile's, under its own
    # matmuls; tile 0 its own as well
    @pl.when((kk == 0) & (t == 0) & (nu > 0))
    def _first():
        copies(0, 0, "start")

    @pl.when((kk == 0) & (t + 1 < nu))
    def _ahead():
        copies(t + 1, 1 - slot, "start")

    @pl.when((kk == 0) & (t < nu))
    def _arrived():
        copies(t, slot, "wait")

    chunks = wg_ref.shape[1] // _LANES

    def x():
        # lane chunk c of the K block: sublane c of every row's slab
        return jnp.concatenate(
            [xbuf[slot, pl.ds(kk * chunks + c, tm, stride=rp), :]
             for c in range(chunks)], axis=1).astype(wg_ref.dtype)

    _gate_up_steps(accg, accu, o_ref, x, wg_ref, wu_ref, t < nu, kk, nk,
                   act)


def _gmm_down_kernel(te_ref, nu_ref, x_ref, w_ref, o_ref, acc, *, nk, rp):
    del te_ref
    t, kk = pl.program_id(0), pl.program_id(1)
    used = t < nu_ref[0]

    @pl.when(used & (kk == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(used)
    def _mul():
        acc[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(used & (kk == nk - 1))
    def _out():
        if not rp:
            o_ref[...] = acc[...]
            return
        tm, n = acc.shape
        for c in range(n // _LANES):
            o_ref[pl.ds(c, tm, stride=rp), :] = \
                acc[:, c * _LANES:(c + 1) * _LANES]


def _gmm_combine_kernel(at_ref, list_ref, ys_hbm, pairs_ref, wts_ref, o_ref,
                        buf, sem, weight, summed, *, tn, rp, ib, jb):
    t = pl.program_id(0)
    slot = t % 2

    def copies(tile, slot, op):
        # the tile's pairs that are here, ``list_ref[at[tile]:at[tile +
        # 1]]``, each [row | token's place in the tile | j] in one
        # number: pair j of token i to token i's sublanes of plane j
        # (every slab is as long: a wait names any)
        def pair(p, _):
            v = list_ref[p] if op == "start" else 0
            i, j = (v >> jb) & ((1 << ib) - 1), v & ((1 << jb) - 1)
            getattr(pltpu.make_async_copy(
                ys_hbm.at[v >> (ib + jb)],
                buf.at[slot, j, pl.ds(pl.multiple_of(i * rp, 8), rp)],
                sem.at[slot]), op)()

        jax.lax.fori_loop(at_ref[tile], at_ref[tile + 1], pair, None)

    @pl.when(t == 0)
    def _first():
        copies(0, 0, "start")

    @pl.when(t + 1 < pl.num_programs(0))
    def _ahead():
        copies(t + 1, 1 - slot, "start")

    copies(t, slot, "wait")
    # a pair's weight over its token's lanes, 0 where it is not here:
    # broadcast once a tile, not once a lane chunk
    k = pairs_ref.shape[1]
    wts = jnp.where(pairs_ref[...] >= 0, wts_ref[...], 0.0)   # (tn, k)
    for j in range(k):
        weight[j] = jnp.broadcast_to(wts[:, j:j + 1], (tn, _LANES))
    chunks = o_ref.shape[1] // _LANES

    def chunk(c, _):
        # lane chunk c of the tile's tokens: the pairs in j's order;
        # what a plane holds where no pair is here is masked, not
        # multiplied (it may be anything)
        y = jnp.zeros((tn, _LANES), jnp.float32)
        for j in range(k):
            w = weight[j]
            y = y + jnp.where(
                w != 0.0, buf[slot, j, pl.ds(c, tn, stride=rp), :] * w, 0.0)
        summed[c] = y

    jax.lax.fori_loop(0, chunks, chunk, None)
    for c in range(chunks):
        o_ref[:, c * _LANES:(c + 1) * _LANES] = \
            summed[c].astype(o_ref.dtype)


def _k_block(K, want):
    return want if K % want == 0 else K


@functools.partial(jax.jit, static_argnames=("tm", "act", "interpret"))
def _gate_up(x, w_gate, w_up, tile_expert, n_used, row_token, *, tm, act,
             interpret):
    """``moe_gmm_gate_up``'s call, jitted: a model's layers make it with
    one shape, so the kernel is traced and lowered once a program
    (``pallas_kernels._flash_mha_packed_fn`` says why ``interpret`` is in
    the key)."""
    by_index = row_token is not None
    M, K = (row_token if by_index else x).shape[0], x.shape[1]
    N = w_gate.shape[2]
    tk = _k_block(K, 512)
    nk = K // tk
    x_map, w_map, o_map = _gmm_maps(nk)
    weights = [_vmem_spec((1, tk, N), w_map)] * 2
    acc = [pltpu.VMEM((tm, N), jnp.float32)] * 2
    if by_index:
        rp = slab_rows(K)
        kernel = functools.partial(_gmm_gate_up_rows_kernel, nk=nk, tm=tm,
                                   rp=rp, act=act)
        scalars, rows = (tile_expert, n_used, row_token), token_slabs(x)
        in_specs = [pl.BlockSpec(memory_space=pl.ANY)] + weights
        scratch = [pltpu.VMEM((2, tm * rp, _LANES), jnp.float32),
                   pltpu.SemaphoreType.DMA((2,))] + acc
    else:
        kernel = functools.partial(_gmm_gate_up_kernel, nk=nk, act=act)
        scalars, rows = (tile_expert, n_used), x
        in_specs = [_vmem_spec((tm, tk), x_map)] + weights
        scratch = acc
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=(M // tm, nk),
        in_specs=in_specs, out_specs=_vmem_spec((tm, N), o_map),
        scratch_shapes=scratch)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=_compiler_params(
            "arbitrary", "arbitrary", vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_gate_up" if act == "silu" else f"moe_gmm_gate_up_{act}",
    )(*scalars, rows, w_gate, w_up)


def moe_gmm_gate_up(x, w_gate, w_up, tile_expert, n_used, tm, act="silu",
                    row_token=None):
    """x (M, K) rows sorted by expert in tiles of ``tm``; w_gate, w_up
    (E, K, N) -> act(x W_gate[e]) * (x W_up[e]), (M, N) in x.dtype,
    for the rows of the first ``n_used[0]`` tiles (the rest is not
    written).  ``act``: ``silu`` or ``relu`` (static).

    ``row_token`` (M,) int32 given: x is the TOKEN rows (N, K), K whole
    lane tiles, and row m of the result is token ``row_token[m]``'s; the
    kernel fetches the used tiles' rows itself (section comment)."""
    return _gate_up(x, w_gate, w_up, tile_expert, n_used, row_token, tm=tm,
                    act=act, interpret=_interpret())


def moe_gmm_down(x, w_down, tile_expert, n_used, tm, slabs=False):
    """x (M, K) as :func:`moe_gmm_gate_up` gives it; w_down (E, K, N)
    -> x W_down[e], float32, for the rows of the used tiles: (M, N), or
    with ``slabs`` (M, R, 128), each row a slab ``moe_gmm_combine``
    copies whole (what lies past a slab's N values is not written)."""
    M, K = x.shape
    N = w_down.shape[2]
    tk = _k_block(K, 256)
    nk = K // tk
    rp = slab_rows(N) if slabs else 0
    x_map, w_map, o_map = _gmm_maps(nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(M // tm, nk),
        in_specs=[_vmem_spec((tm, tk), x_map),
                  _vmem_spec((1, tk, N), w_map)],
        out_specs=_vmem_spec((tm * rp, _LANES) if rp else (tm, N), o_map),
        scratch_shapes=[pltpu.VMEM((tm, N), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_gmm_down_kernel, nk=nk, rp=rp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (M * rp, _LANES) if rp else (M, N), jnp.float32),
        compiler_params=_compiler_params(
            "arbitrary", "arbitrary", vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="moe_gmm_down",
    )(tile_expert, n_used, x, w_down)
    return out.reshape(M, rp, _LANES) if rp else out


# tokens a grid step of the combine: top_k planes of their slabs, twice
# buffered (10 x 64 x 16 KB x 2 = 21 MB at the widest cell)
_COMBINE_TOKENS = 64


@functools.partial(jax.jit, static_argnames=("d", "dtype", "interpret"))
def _combine(ys, pairs, wts, *, d, dtype, interpret):
    n, k = pairs.shape
    m, rp = ys.shape[:2]
    tn = _COMBINE_TOKENS if n % _COMBINE_TOKENS == 0 else n
    ib, jb = (tn - 1).bit_length(), (k - 1).bit_length()
    if (m - 1).bit_length() + ib + jb > 31:
        raise ValueError(
            f"moe_gmm_combine: a row of {m}, a place of {tn} and a pair of "
            f"{k} do not fit one int32")
    # the pairs that are here, in pair order (a tile's are consecutive:
    # ``at[t]`` says from where), each [row | place in its tile | j];
    # a sort, not a scan of every pair by the kernel's scalar core
    flat = pairs.reshape(-1)
    place = jnp.arange(n * k, dtype=jnp.int32)
    packed = (flat << (ib + jb)) | (place // k % tn << jb) | place % k
    listed = jax.lax.sort_key_val((flat < 0).astype(jnp.int32), packed)[1]
    at = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(jnp.sum(
        (pairs >= 0).reshape(n // tn, tn * k).astype(jnp.int32), axis=1))])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n // tn,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  _vmem_spec((tn, k), lambda t, *_: (t, 0)),
                  _vmem_spec((tn, k), lambda t, *_: (t, 0))],
        out_specs=_vmem_spec((tn, d), lambda t, *_: (t, 0)),
        scratch_shapes=[pltpu.VMEM((2, k, tn * rp, _LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((k, tn, _LANES), jnp.float32),
                        pltpu.VMEM((d // _LANES, tn, _LANES), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_gmm_combine_kernel, tn=tn, rp=rp, ib=ib, jb=jb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), dtype),
        compiler_params=_compiler_params(
            "arbitrary", vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_gmm_combine",
    )(at, listed, ys, pairs, wts.astype(jnp.float32))


def moe_gmm_combine(ys, pair_row, here, wts, d, dtype):
    """ys (M, R, 128) float32 slabs (``moe_gmm_down(..., slabs=True)``);
    pair_row, here, wts (N, k) -> (N, d) in ``dtype``: token n's rows
    ``ys[pair_row[n, j]]`` times ``wts[n, j]`` over the pairs that are
    ``here``, added in j's order, float32; exactly 0 for a token with
    none.  Only those rows are read: no (N, k, d) array is made."""
    pairs = jnp.where(here, pair_row, -1).astype(jnp.int32)
    return _combine(ys, pairs, wts, d=d, dtype=jnp.dtype(dtype),
                    interpret=_interpret())
