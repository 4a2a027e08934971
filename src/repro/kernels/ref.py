"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the *semantic definition* the kernels are tested against
(tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle).  These are
also the fallback execution path on backends without Pallas.  ``eval_expr``
is the general case: a direct jnp evaluator for any ``repro.core.expr``
expression (the DNF semantics, before any normal-form derivation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import expr as E
from repro.core import semiring


def gemm_ref(a: jax.Array, b: jax.Array, out_dtype=None) -> jax.Array:
    """C = A @ B with f32 accumulation (the MoA inner product on matrices)."""
    out_dtype = out_dtype or a.dtype
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)


def hadamard_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    return a * b


def outer_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """MoA outer product of two matrices: shape (m, n, p, q)."""
    return jnp.einsum("mn,pq->mnpq", a, b)


def kron_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """Kronecker product via the MoA lemma: transpose+reshape of the outer."""
    m, n = a.shape
    p, q = b.shape
    return outer_ref(a, b).transpose(0, 2, 1, 3).reshape(m * p, n * q)


def expert_gemm_ref(x: jax.Array, w: jax.Array, out_dtype=None) -> jax.Array:
    """Grouped (capacity-padded) expert GEMM: (E, cap, d) x (E, d, f)."""
    out_dtype = out_dtype or x.dtype
    return jnp.einsum("ecd,edf->ecf", x, w,
                      preferred_element_type=jnp.float32).astype(out_dtype)


def _combine_fn(name: str):
    return getattr(jnp, semiring.combine_def(name).jnp_name)


def _reducer_fn(name: str):
    return getattr(jnp, semiring.reduce_def(name).jnp_reducer)


def eval_expr(expr: "E.Expr", *arrays: jax.Array) -> jax.Array:
    """Evaluate a MoA expression directly with jnp (f32 accumulation) —
    the semantic oracle / XLA fallback for ``ops.apply``.  ``arrays`` bind
    leaves in composition order."""
    it = iter(arrays)

    def ev(e: "E.Expr") -> jax.Array:
        if isinstance(e, E.Arr):
            # leaves bind by STORAGE shape (same contract as ops.apply):
            # a column-major leaf takes the reversed-shape row-major buffer
            x = next(it)
            storage = e.shape if e.layout == "row" else tuple(reversed(e.shape))
            if tuple(x.shape) != storage:
                raise ValueError(f"leaf {e.name!r} expects storage shape "
                                 f"{storage}, got {tuple(x.shape)}")
            if e.layout == "col":
                x = jnp.transpose(x, tuple(reversed(range(x.ndim))))
            return x.astype(jnp.float32)
        if isinstance(e, E.Transpose):
            return jnp.transpose(ev(e.x), e.perm)
        if isinstance(e, E.Psi):
            return ev(e.x)[e.idx]
        if isinstance(e, E.Combine):
            return _combine_fn(e.op)(ev(e.a), ev(e.b))
        if isinstance(e, E.Reduce):
            return _reducer_fn(e.op)(ev(e.x), axis=e.axis)
        if isinstance(e, E.Inner):
            a, b = ev(e.a), ev(e.b)
            nb = e.batch
            if (e.plus, e.times) == ("add", "mul"):
                # linear contraction (batched or not): dot_general, so the
                # XLA fallback never materializes the broadcast intermediate
                return jax.lax.dot_general(
                    a, b, (((a.ndim - 1,), (nb,)),
                           (tuple(range(nb)), tuple(range(nb)))))
            # general semiring: broadcast-pair then fold the contraction
            ar = a.reshape(a.shape + (1,) * (b.ndim - nb - 1))
            br = b.reshape(b.shape[:nb] + (1,) * (a.ndim - nb - 1)
                           + b.shape[nb:])
            return _reducer_fn(e.plus)(_combine_fn(e.times)(ar, br),
                                       axis=a.ndim - 1)
        raise TypeError(f"not an Expr node: {e!r}")

    out = ev(expr)
    if next(it, None) is not None:
        raise ValueError("more arrays than expression leaves")
    return out


def eval_nf(nf: "E.NormalForm", *arrays: jax.Array) -> jax.Array:
    """jnp oracle for a *normal form* (not an Expr): the XLA execution path
    of a per-shard computation, where only the local ``NormalForm`` exists.

    Binds leaves by storage shape (col-major leaves take the reversed
    buffer, constant dims are indexed out), then evaluates the semiring:
    an einsum for (mul, add), broadcast-pair-and-fold otherwise — f32
    accumulation either way, matching the emitted kernels.
    """
    if len(arrays) != len(nf.leaves):
        raise ValueError(f"normal form has {len(nf.leaves)} leaves, got "
                         f"{len(arrays)}")
    bound: list[tuple[tuple[str, ...], jax.Array]] = []
    for leaf, x in zip(nf.leaves, arrays):
        storage = leaf.storage_shape()
        if tuple(x.shape) != storage:
            raise ValueError(f"leaf {leaf.array!r} expects storage shape "
                             f"{storage}, got {tuple(x.shape)}")
        if leaf.layout == "col":
            x = jnp.transpose(x, tuple(reversed(range(x.ndim))))
        idx = tuple(t if isinstance(t, int) else slice(None)
                    for t, _ in leaf.dims)
        x = x[idx]
        syms = tuple(t for t, _ in leaf.dims if isinstance(t, str))
        if len(set(syms)) != len(syms):
            raise NotImplementedError(
                f"leaf {leaf.array!r} repeats an index (diagonal access)")
        bound.append((syms, x.astype(jnp.float32)))

    joint = tuple(nf.out_axes) + tuple(nf.reduce_axes)
    if (nf.combine, nf.reduce_op) == ("mul", "add"):
        letters = {s: chr(ord("a") + i) for i, s in enumerate(joint)}
        spec = ",".join("".join(letters[s] for s in syms)
                        for syms, _ in bound)
        spec += "->" + "".join(letters[s] for s in nf.out_axes)
        return jnp.einsum(spec, *(x for _, x in bound),
                          preferred_element_type=jnp.float32)
    # general semiring: align every operand to (out + reduce) axes, pair
    # with the combine op, fold the reduce axes — same shape discipline as
    # the emitted block body
    aligned = []
    for syms, x in bound:
        perm = sorted(range(len(syms)), key=lambda d: joint.index(syms[d]))
        x = jnp.transpose(x, perm)
        have = [syms[p] for p in perm]
        for pos, ax in enumerate(joint):
            if ax not in have:
                x = jnp.expand_dims(x, pos)
        aligned.append(x)
    out = functools.reduce(_combine_fn(nf.combine), aligned)
    if nf.reduce_axes:
        red = tuple(range(len(nf.out_axes), len(joint)))
        out = _reducer_fn(nf.reduce_op)(out, axis=red)
    return out


# ---------------------------------------------------------------------------
# carried-state recurrence oracles (the jnp semantics of emit_recurrent's
# registered kinds; also the VJP recompute bodies of ops.scan_ssd /
# ops.gated_scan and their XLA-entry execution path)
# ---------------------------------------------------------------------------

def ssd_scan_ref(xdt: jax.Array, dA: jax.Array, B: jax.Array, C: jax.Array,
                 init_state: jax.Array | None = None, *, chunk: int,
                 unroll: bool = False) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan oracle — the ``ssd`` monoid's jnp semantics.

    ``xdt (b,s,h,p)`` is the dt-folded input, ``dA (b,s,h)`` the per-token
    log decay (``dt * A``, <= 0), ``B/C (b,s,n)`` the state in/out
    projections.  Returns ``(y (b,s,h,p) f32, final state (b,h,p,n) f32)``.
    The per-chunk factoring mirrors the emitted kernel body step for step
    (per head, the same 2-D contractions in the same order, batched over
    ``b``), which is what makes the interpret-mode kernel bit-identical to
    this oracle.
    """
    b, s, h, p = xdt.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc, q = s // chunk, chunk
    xc = xdt.astype(jnp.float32).reshape(b, nc, q, h, p)
    dac = dA.astype(jnp.float32).reshape(b, nc, q, h)
    Bc = B.astype(jnp.float32).reshape(b, nc, q, n)
    Cc = C.astype(jnp.float32).reshape(b, nc, q, n)
    tril = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    neg_inf = jnp.float32(semiring.MASK_NEG_INF)

    def dot(contract):
        return jax.vmap(lambda x, y: jax.lax.dot_general(
            x, y, (contract, ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))

    def step(h_prev, inp):
        xb, dab, Bb, Cb = inp                       # (b,q,h,p) (b,q,h) ...
        G = jnp.einsum("bin,bjn->bij", Cb, Bb,
                       preferred_element_type=jnp.float32)
        ys, hs = [], []
        for hh in range(h):
            csh = jnp.cumsum(dab[:, :, hh], axis=1)[..., None]   # (b,i,1)
            seg = csh - jnp.swapaxes(csh, 1, 2)                  # (b,i,j)
            L = jnp.exp(jnp.where(tril, seg, neg_inf))
            xh = xb[:, :, hh]                                    # (b,j,p)
            hp = h_prev[:, hh]                                   # (b,p,n)
            y = dot(((1,), (0,)))(G * L, xh)
            y = y + dot(((1,), (1,)))(Cb, hp) * jnp.exp(csh)
            total = csh[:, q - 1:q]                              # (b,1,1)
            xd = xh * jnp.exp(total - csh)
            hs.append(jnp.exp(total) * hp + dot(((0,), (0,)))(xd, Bb))
            ys.append(y)
        return jnp.stack(hs, axis=1), jnp.stack(ys, axis=2)

    init = (jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
            else init_state.astype(jnp.float32))
    final, ys = jax.lax.scan(
        step, init,
        (xc.transpose(1, 0, 2, 3, 4), dac.transpose(1, 0, 2, 3),
         Bc.transpose(1, 0, 2, 3), Cc.transpose(1, 0, 2, 3)),
        unroll=bool(unroll))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, s, h, p)
    return y, final


def gated_scan_ref(log_a: jax.Array, b_in: jax.Array,
                   init_state: jax.Array | None = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Gated linear scan oracle — the ``gated`` (RG-LRU) monoid's jnp
    semantics: ``h_t = a_t h_{t-1} + b_t`` with ``a = exp(log_a)``, via the
    log-depth associative scan over the sequence axis.  ``log_a/b_in``:
    (B, S, w) f32.  Returns ``(h (B,S,w) f32, final (B,w) f32)``."""
    a = jnp.exp(log_a.astype(jnp.float32))
    b = b_in.astype(jnp.float32)

    def comb(x, y):
        return (x[0] * y[0], y[0] * x[1] + y[1])

    aa, hh = jax.lax.associative_scan(comb, (a, b), axis=1)
    if init_state is not None:
        hh = hh + aa * init_state.astype(jnp.float32)[:, None, :]
    return hh, hh[:, -1]


def gated_chunk_ref(log_a: jax.Array, b_in: jax.Array, h0: jax.Array,
                    chunk: int) -> tuple[jax.Array, jax.Array]:
    """Chunked gated-scan mirror of the ``gated`` / ``gated_backward``
    kernel body (the bit-identity reference): per chunk the same
    within-chunk associative scan followed by the carry re-base
    ``hh + aa * h`` — the exact op order of the emitted kernel, so on the
    same operands the outputs match it bit for bit.  ``s`` must be a
    multiple of ``chunk``."""
    b, s, w = log_a.shape
    nc = s // chunk
    a = jnp.exp(log_a.astype(jnp.float32)).reshape(b, nc, chunk, w)
    bb = b_in.astype(jnp.float32).reshape(b, nc, chunk, w)

    def comb(x, y):
        return (x[0] * y[0], y[0] * x[1] + y[1])

    def step(h, inp):
        ac, bc = inp
        aa, hh = jax.lax.associative_scan(comb, (ac, bc), axis=1)
        hh = hh + aa * h[:, None]
        return hh[:, -1], hh

    hf, ys = jax.lax.scan(step, h0.astype(jnp.float32),
                          (a.transpose(1, 0, 2, 3), bb.transpose(1, 0, 2, 3)))
    return ys.transpose(1, 0, 2, 3).reshape(b, s, w), hf


def flash_dq_ref(q: jax.Array, k: jax.Array, v: jax.Array, do: jax.Array,
                 m: jax.Array, l: jax.Array, delta: jax.Array, *,
                 scale: float, causal: bool, bq: int, bk: int,
                 window: int = 0, prefix_len: int = 0,
                 logical_k: int | None = None) -> jax.Array:
    """Blocked flash-backward dQ oracle — the ``flash_dq`` monoid's jnp
    semantics on *padded* grouped layouts ``q/do (b, sqp, kv, g, ·)``,
    ``k/v (b, skp, kv, ·)``, ``m/l/delta (b, kv, g, sqp)``.

    Mirrors the emitted kernel step for step: the streamed key axis is
    walked sequentially in the kernel's exact ``bk`` blocks (summation
    order over the stream is what bit-identity requires — ``p = exp(·)``
    is irrational even on integer inputs), rows are vectorized (they are
    grid-parallel cells), and the full positional mask is always applied
    (a fully-masked block contributes exact zeros, matching the kernel's
    block-skip).  Returns padded ``dq (b, kv, g, sqp, hd)`` f32."""
    b, sqp, kv, g, hd = q.shape
    skp = k.shape[1]
    neg_inf = jnp.float32(semiring.MASK_NEG_INF)
    qt = q.transpose(0, 2, 3, 1, 4).astype(jnp.float32)    # (b,h,g,i,c)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)       # (b,h,j,c)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)       # (b,h,j,d)
    dot = do.transpose(0, 2, 3, 1, 4).astype(jnp.float32)  # (b,h,g,i,d)
    lse = m.astype(jnp.float32) + \
        jnp.log(jnp.maximum(l.astype(jnp.float32), 1e-30))
    delta = delta.astype(jnp.float32)
    lk = skp if logical_k is None else logical_k
    qpos = jnp.arange(sqp)[:, None]
    acc = jnp.zeros((b, kv, g, sqp, hd), jnp.float32)
    for ki in range(skp // bk):
        kb = kt[:, :, ki * bk:(ki + 1) * bk]
        vb = vt[:, :, ki * bk:(ki + 1) * bk]
        s = jnp.einsum("bhgic,bhjc->bhgij", qt, kb,
                       preferred_element_type=jnp.float32) * scale
        kpos = ki * bk + jnp.arange(bk)[None, :]
        mask = jnp.ones((sqp, bk), bool)
        if causal:
            mask = kpos <= qpos
            if window:
                mask = jnp.logical_and(mask, kpos > qpos - window)
            if prefix_len:
                mask = jnp.logical_or(
                    mask, jnp.logical_and(qpos < prefix_len,
                                          kpos < prefix_len))
        if lk < skp:
            mask = jnp.logical_and(mask, kpos < lk)
        if causal or lk < skp:
            s = jnp.where(mask, s, neg_inf)
        p = jnp.exp(s - lse[..., None])
        dp = jnp.einsum("bhgid,bhjd->bhgij", dot, vb,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])
        acc = acc + jnp.einsum("bhgij,bhjc->bhgic", ds, kb,
                               preferred_element_type=jnp.float32)
    return acc * scale


def flash_dkv_ref(q: jax.Array, k: jax.Array, v: jax.Array, do: jax.Array,
                  m: jax.Array, l: jax.Array, delta: jax.Array, *,
                  scale: float, causal: bool, bj: int, bi: int,
                  window: int = 0, prefix_len: int = 0,
                  logical_q: int | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Blocked flash-backward dK/dV oracle — the transposed weld's jnp
    semantics (rows = key positions, stream = query positions in ``bi``
    blocks), mirroring the ``flash_dkv`` kernel's summation order and its
    always-on padded-query mask.  Returns per-group padded
    ``(dk (b, kv, g, skp, hd), dv (b, kv, g, skp, vd))`` f32 — the GQA
    group reduction stays with the caller, as in the kernel path."""
    b, sqp, kv, g, hd = q.shape
    skp, vd = k.shape[1], v.shape[-1]
    neg_inf = jnp.float32(semiring.MASK_NEG_INF)
    qt = q.transpose(0, 2, 3, 1, 4).astype(jnp.float32)    # (b,h,g,i,c)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)       # (b,h,j,c)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)       # (b,h,j,d)
    dot = do.transpose(0, 2, 3, 1, 4).astype(jnp.float32)  # (b,h,g,i,d)
    lse = m.astype(jnp.float32) + \
        jnp.log(jnp.maximum(l.astype(jnp.float32), 1e-30))
    delta = delta.astype(jnp.float32)
    lq = sqp if logical_q is None else logical_q
    kpos = jnp.arange(skp)[:, None]
    dk = jnp.zeros((b, kv, g, skp, hd), jnp.float32)
    dv = jnp.zeros((b, kv, g, skp, vd), jnp.float32)
    for ki in range(sqp // bi):
        qb = qt[:, :, :, ki * bi:(ki + 1) * bi]
        dob = dot[:, :, :, ki * bi:(ki + 1) * bi]
        lseb = lse[..., ki * bi:(ki + 1) * bi]
        db = delta[..., ki * bi:(ki + 1) * bi]
        s = jnp.einsum("bhjc,bhgic->bhgji", kt, qb,
                       preferred_element_type=jnp.float32) * scale
        qpos = ki * bi + jnp.arange(bi)[None, :]
        mask = jnp.ones((skp, bi), bool)
        if causal:
            mask = kpos <= qpos
            if window:
                mask = jnp.logical_and(mask, kpos > qpos - window)
            if prefix_len:
                mask = jnp.logical_or(
                    mask, jnp.logical_and(qpos < prefix_len,
                                          kpos < prefix_len))
        if lq < sqp:
            mask = jnp.logical_and(mask, qpos < lq)
        if causal or lq < sqp:
            s = jnp.where(mask, s, neg_inf)
        p = jnp.exp(s - lseb[:, :, :, None, :])             # (b,h,g,j,bi)
        dp = jnp.einsum("bhgid,bhjd->bhgji", dob, vt,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - db[:, :, :, None, :])
        dk = dk + jnp.einsum("bhgji,bhgic->bhgjc", ds, qb,
                             preferred_element_type=jnp.float32)
        dv = dv + jnp.einsum("bhgji,bhgid->bhgjd", p, dob,
                             preferred_element_type=jnp.float32)
    return dk * scale, dv


def ssd_bwd_ref(C: jax.Array, B: jax.Array, dY: jax.Array, X: jax.Array,
                dA: jax.Array, Hin: jax.Array, dHf: jax.Array
                ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                           jax.Array]:
    """Chunked SSD backward oracle — the ``ssd_backward`` monoid's jnp
    semantics over kernel-order, *already chunk-reversed* operands
    ``C/B (b,nc,q,n)``, ``dY/X (b,nc,q,h,p)``, ``dA (b,nc,q,h)``,
    ``Hin (b,nc,h,p,n)`` (the saved per-chunk state checkpoints, reversed
    the same way) and ``dHf (b,h,p,n)``.  Mirrors the emitted kernel body
    step for step (same replay of the forward factoring, per head, same
    cotangent chaining order), one batch row at a time.  Returns
    ``(dX, dh0, dB, dC, ddA)`` f32 in the same reversed chunk order."""
    b, nc, q, n = C.shape
    h = X.shape[3]
    f32 = jnp.float32
    tril = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    tril_f = jnp.where(tril, 1.0, 0.0).astype(f32)
    neg_inf = f32(semiring.MASK_NEG_INF)
    last = (jnp.arange(q) == q - 1)[:, None]

    def dot(x, y, contract):
        return jax.lax.dot_general(x, y, (contract, ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=f32)

    def chunk(dh_all, Cb, Bb, dYb, Xb, dAb, Hc):
        """One batch row's chunk: (q, n) / (q, h, p) / (q, h) / (h, p, n)."""
        G = jnp.einsum("in,jn->ij", Cb, Bb, preferred_element_type=f32)

        def head(hh, carry):
            dB, dC, dG, dxs, ddas, dhs = carry
            csh = jnp.cumsum(dAb[:, hh])[:, None]                # (i,1)
            L = jnp.exp(jnp.where(tril, csh - csh.T, neg_inf))
            P = G * L
            in_decay = jnp.exp(csh)
            hc, dh = Hc[hh], dh_all[hh]                          # (p,n)
            t_off = dot(Cb, hc, ((1,), (1,)))
            total = csh[q - 1:q]                                 # (1,1)
            decay = jnp.exp(total - csh)
            xh, dyh = Xb[:, hh], dYb[:, hh]                      # (q,p)
            xd = xh * decay
            dtotal = jnp.sum(jnp.sum(dh * hc, axis=1, keepdims=True),
                             axis=0, keepdims=True) * jnp.exp(total)
            dh_prev = jnp.exp(total) * dh
            dB = dB + dot(xd, dh, ((1,), (0,)))
            dxd = dot(Bb, dh, ((1,), (1,)))
            dx = dxd * decay
            ddec = jnp.sum(dxd * xh, axis=1, keepdims=True)
            dtotal = dtotal + jnp.sum(ddec * decay, axis=0, keepdims=True)
            dcsh = -(ddec * decay)
            dt_off = dyh * in_decay
            dcsh = dcsh + jnp.sum(dyh * t_off, axis=1,
                                  keepdims=True) * in_decay
            dC = dC + dot(dt_off, hc, ((1,), (0,)))
            dh_prev = dh_prev + dot(dt_off, Cb, ((0,), (0,)))
            dP = dot(dyh, xh, ((1,), (1,)))
            dx = dx + dot(P, dyh, ((0,), (0,)))
            dG = dG + dP * L
            dseg = jnp.where(tril, dP * G * L, 0.0)
            dcsh = (dcsh + jnp.sum(dseg, axis=1, keepdims=True)
                    - dot(dseg, jnp.ones((q, 1), f32), ((0,), (0,))))
            dcsh = dcsh + jnp.where(last, dtotal, 0.0)
            # suffix sum over i >= j as the kernel runs it: dcsh against
            # the causal mask
            ddas = ddas.at[hh].set(dot(dcsh, tril_f, ((0,), (0,)))[0])
            return (dB, dC, dG, dxs.at[:, hh].set(dx), ddas,
                    dhs.at[hh].set(dh_prev))

        zeros = jnp.zeros((q, n), f32)
        dB, dC, dG, dX, ddaT, dh_new = jax.lax.fori_loop(
            0, h, head, (zeros, zeros, jnp.zeros((q, q), f32),
                         jnp.zeros(Xb.shape, f32), jnp.zeros((h, q), f32),
                         jnp.zeros(dh_all.shape, f32)))
        dC = dC + dot(dG, Bb, ((1,), (0,)))
        dB = dB + dot(dG, Cb, ((0,), (0,)))
        return dh_new, (dX, dB, dC, ddaT.T)

    def step(dh, inp):
        # batch rows one by one: the kernel's exact 2-D contractions
        outs = [chunk(dh[i], *(a[i] for a in inp)) for i in range(b)]
        return jax.tree.map(lambda *t: jnp.stack(t), *outs)

    dh0, (dX, dB, dC, ddA) = jax.lax.scan(
        step, dHf.astype(f32),
        tuple(jnp.moveaxis(a.astype(f32), 1, 0)
              for a in (C, B, dY, X, dA, Hin)))
    return (jnp.moveaxis(dX, 0, 1), dh0, jnp.moveaxis(dB, 0, 1),
            jnp.moveaxis(dC, 0, 1), jnp.moveaxis(ddA, 0, 1))


def ipophp_ref(a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    """The unified inner/outer/hadamard/kron operator (paper appendix)."""
    if mode == "ip":
        return gemm_ref(a, b)
    if mode == "hp":
        return hadamard_ref(a, b)
    if mode == "op":
        return outer_ref(a, b)
    if mode == "kp":
        return kron_ref(a, b)
    raise ValueError(f"unknown ipophp mode {mode!r}")
