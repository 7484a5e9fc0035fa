"""Fused Huber-residual contraction kernels (the DCF-PCA compute hot spot).

The inner solver needs, per sweep over a client block ``M_i`` (m x n):

    Psi   = clip(M - U V^T, [-lam, lam])      -- (m, n), never needed in HBM
    out_v = Psi^T U                           -- (n, r)
    out_u = Psi V                             -- (m, r)

A naive jnp implementation materializes R, S/Psi in HBM (>= 3 full m x n
transfers on top of the matmul reads).  On TPU both contractions are
flash-attention-shaped: two MXU matmuls with an elementwise clamp in
between, so we tile over (m, n), compute the Psi tile in VMEM, contract it
immediately, and accumulate the skinny output in place across the reduction
grid axis.  HBM traffic drops to one read of M (+ the skinny U/V/out).

Blocking: the full factor width ``r`` (padded to a lane multiple) is kept
resident; tiles default to 256 x 256 so the working set is
``bm*bn + (bm+bn)*r_pad + bn*r_pad`` floats ~= 1.3 MB at r=128, far under
the ~16 MB VMEM budget (see DESIGN.md Sec. 2).

Masked (robust matrix completion) variants: ``*_masked`` take an extra 0/1
observation mask ``W`` (same shape and tiling as ``M``) and compute

    Psi = W * clip(M - U V^T, [-lam, lam])

i.e. unobserved entries contribute exactly zero to both contractions.  The
mask tile rides the same (bm, bn) block pipeline as the data tile, so the
epilogue stays in VMEM and the only extra HBM traffic is the single read of
W itself (see DESIGN.md Sec. 9 for the working-set math).

Compact data plane (DESIGN.md Sec. 12): ``M`` may be stored bfloat16 (tiles
are upcast in VMEM; every accumulation stays f32 via
``preferred_element_type``), and the mask may arrive bit-packed -- a uint8
plane, 8 columns per byte (``kernels.bitmask``) -- streamed as
``(bm, bn//8)`` tiles and unpacked to the (bm, bn) float tile with VPU
shifts while the MXU runs the contraction.  Together they cut the
steady-state HBM bytes of a masked pass ~2.2x (8 bytes/entry -> 2.125).
The packed kernels run in interpret mode only: compiled for the TPU they
are refused (see :data:`PACKED_MASK_UNSUPPORTED`).

Dual contraction + epilogue diagnostics (the fused round primitive):
:func:`huber_dual_contract` emits ``Psi^T U``, ``Psi V``, the Huber
objective ``H_lam(R)`` and ``||Psi||_F^2`` from a *single* (bm, bn) tile
sweep -- one read of M (+ mask) does the work of three separate passes.
``out_u`` accumulates as a normal revisited output block; ``out_v`` is
grid-resident in VMEM (its block index is constant, so it is written back
once at the end), and the two scalars accumulate in SMEM.  VMEM working
set: ``n_pad*r_pad`` (resident out_v) + ``(bm + bn + bm)*r_pad`` +
``bm*bn`` data/mask tiles -- ~1.4 MB at n=2048, r=64, 256x256 tiles
(DESIGN.md Sec. 12 has the full table).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import bitmask, compat

Array = jax.Array

# MXU/VREG-aligned defaults.  The second-minor dim of every block is a
# multiple of 8 and the minor dim a multiple of 128 (f32 tiling).
DEFAULT_BM = 256
DEFAULT_BN = 256
LANE = 128

#: The shrinkage threshold rides in SMEM as a (1, 1) f32 operand: kernel
#: bodies read it as a scalar (``lam_ref[0, 0]``), and the TPU compiler only
#: allows scalar loads from VMEM/SMEM refs.  Two dims, so a vmapped call
#: with a per-problem ``lam`` (the serving lanes) still meets the block
#: tiling rule: the batched block is (squeezed, 1, 1) of (B, 1, 1).
_LAM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _lam_operand(lam: float | Array) -> Array:
    return jnp.reshape(jnp.asarray(lam, jnp.float32), (1, 1))


def _mxu(a: Array, b: Array) -> Array:
    """f32 MXU product at full precision: Mosaic's default contracts f32
    operands as one bf16 pass, ~3e-2 relative error on these contractions
    (measured on a v5e), which caps solver recovery near 3e-3."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _pad_to(x: Array, axis: int, mult: int) -> Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# out_v = Psi^T U  : grid (n/bn, m/bm), m is the reduction (last, "arbitrary")
# ---------------------------------------------------------------------------
def _contract_v_kernel(u_ref, v_ref, m_ref, lam_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...]  # (bm, r)
    v = v_ref[...]  # (bn, r)
    mt = m_ref[...]  # (bm, bn)
    lam = lam_ref[0, 0]
    low = _mxu(u, v.T)
    psi = jnp.clip(mt.astype(jnp.float32) - low, -lam, lam)
    out_ref[...] += _mxu(psi.T, u.astype(jnp.float32))


def _contract_v_masked_kernel(u_ref, v_ref, m_ref, w_ref, lam_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...]  # (bm, r)
    v = v_ref[...]  # (bn, r)
    mt = m_ref[...]  # (bm, bn)
    w = w_ref[...]  # (bm, bn) observation mask tile
    lam = lam_ref[0, 0]
    low = _mxu(u, v.T)
    psi = w.astype(jnp.float32) * jnp.clip(
        mt.astype(jnp.float32) - low, -lam, lam
    )
    out_ref[...] += _mxu(psi.T, u.astype(jnp.float32))


# ---------------------------------------------------------------------------
# out_u = Psi V  : grid (m/bm, n/bn), n is the reduction (last, "arbitrary")
# ---------------------------------------------------------------------------
def _contract_u_kernel(u_ref, v_ref, m_ref, lam_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...]  # (bm, r)
    v = v_ref[...]  # (bn, r)
    mt = m_ref[...]  # (bm, bn)
    lam = lam_ref[0, 0]
    low = _mxu(u, v.T)
    psi = jnp.clip(mt.astype(jnp.float32) - low, -lam, lam)
    out_ref[...] += _mxu(psi, v.astype(jnp.float32))


def _contract_u_masked_kernel(u_ref, v_ref, m_ref, w_ref, lam_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...]  # (bm, r)
    v = v_ref[...]  # (bn, r)
    mt = m_ref[...]  # (bm, bn)
    w = w_ref[...]  # (bm, bn) observation mask tile
    lam = lam_ref[0, 0]
    low = _mxu(u, v.T)
    psi = w.astype(jnp.float32) * jnp.clip(
        mt.astype(jnp.float32) - low, -lam, lam
    )
    out_ref[...] += _mxu(psi, v.astype(jnp.float32))


# Canonical resolution lives in kernels.compat (env-aware, one pattern
# for every entry point); this alias keeps existing importers working.
_should_interpret = compat.should_interpret


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "interpret")
)
def huber_contract_v(
    u: Array,
    v: Array,
    m: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> Array:
    """Psi^T U, Psi = clip(M - U V^T, +-lam).  Returns (n, r) in f32."""
    mm, r = u.shape
    n = v.shape[0]
    # Zero-padding is exact: padded rows/cols of U/V/M produce Psi == 0.
    u_p = _pad_to(_pad_to(u, 0, bm), 1, LANE)
    v_p = _pad_to(_pad_to(v, 0, bn), 1, LANE)
    m_p = _pad_to(_pad_to(m, 0, bm), 1, bn)
    r_pad = u_p.shape[1]
    lam_arr = _lam_operand(lam)

    grid = (m_p.shape[1] // bn, m_p.shape[0] // bm)  # (n-blocks, m-blocks)
    out = pl.pallas_call(
        _contract_v_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, r_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, r_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (j, i)),
            _LAM_SPEC,
        ],
        out_specs=pl.BlockSpec((bn, r_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((v_p.shape[0], r_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=_should_interpret(interpret),
    )(u_p, v_p, m_p, lam_arr)
    return out[:n, :r]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "interpret")
)
def huber_contract_u(
    u: Array,
    v: Array,
    m: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> Array:
    """Psi V, Psi = clip(M - U V^T, +-lam).  Returns (m, r) in f32."""
    mm, r = u.shape
    u_p = _pad_to(_pad_to(u, 0, bm), 1, LANE)
    v_p = _pad_to(_pad_to(v, 0, bn), 1, LANE)
    m_p = _pad_to(_pad_to(m, 0, bm), 1, bn)
    r_pad = u_p.shape[1]
    lam_arr = _lam_operand(lam)

    grid = (m_p.shape[0] // bm, m_p.shape[1] // bn)  # (m-blocks, n-blocks)
    out = pl.pallas_call(
        _contract_u_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, r_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            _LAM_SPEC,
        ],
        out_specs=pl.BlockSpec((bm, r_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((u_p.shape[0], r_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=_should_interpret(interpret),
    )(u_p, v_p, m_p, lam_arr)
    return out[:mm, :r]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "interpret")
)
def huber_contract_v_masked(
    u: Array,
    v: Array,
    m: Array,
    w: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> Array:
    """Psi^T U, Psi = W * clip(M - U V^T, +-lam).  Returns (n, r) in f32.

    ``W`` is the 0/1 observation mask, same shape as ``M``; zero-padding is
    exact (padded mask entries are 0, so padded Psi == 0 twice over).
    """
    mm, r = u.shape
    n = v.shape[0]
    u_p = _pad_to(_pad_to(u, 0, bm), 1, LANE)
    v_p = _pad_to(_pad_to(v, 0, bn), 1, LANE)
    m_p = _pad_to(_pad_to(m, 0, bm), 1, bn)
    w_p = _pad_to(_pad_to(w, 0, bm), 1, bn)
    r_pad = u_p.shape[1]
    lam_arr = _lam_operand(lam)

    grid = (m_p.shape[1] // bn, m_p.shape[0] // bm)  # (n-blocks, m-blocks)
    out = pl.pallas_call(
        _contract_v_masked_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, r_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, r_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (j, i)),
            pl.BlockSpec((bm, bn), lambda i, j: (j, i)),
            _LAM_SPEC,
        ],
        out_specs=pl.BlockSpec((bn, r_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((v_p.shape[0], r_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=_should_interpret(interpret),
    )(u_p, v_p, m_p, w_p, lam_arr)
    return out[:n, :r]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "interpret")
)
def huber_contract_u_masked(
    u: Array,
    v: Array,
    m: Array,
    w: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> Array:
    """Psi V, Psi = W * clip(M - U V^T, +-lam).  Returns (m, r) in f32."""
    mm, r = u.shape
    u_p = _pad_to(_pad_to(u, 0, bm), 1, LANE)
    v_p = _pad_to(_pad_to(v, 0, bn), 1, LANE)
    m_p = _pad_to(_pad_to(m, 0, bm), 1, bn)
    w_p = _pad_to(_pad_to(w, 0, bm), 1, bn)
    r_pad = u_p.shape[1]
    lam_arr = _lam_operand(lam)

    grid = (m_p.shape[0] // bm, m_p.shape[1] // bn)  # (m-blocks, n-blocks)
    out = pl.pallas_call(
        _contract_u_masked_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, r_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            _LAM_SPEC,
        ],
        out_specs=pl.BlockSpec((bm, r_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((u_p.shape[0], r_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=_should_interpret(interpret),
    )(u_p, v_p, m_p, w_p, lam_arr)
    return out[:mm, :r]


# ---------------------------------------------------------------------------
# Dual contraction + epilogue diagnostics: one sweep over M emits
#   out_v = Psi^T U, out_u = Psi V, obj = H_lam(R_W), psi2 = ||Psi||_F^2
# Grid (m/bm, n/bn), both axes "arbitrary" (sequential): out_u accumulates
# block-wise over consecutive j steps; out_v stays grid-resident in VMEM
# (constant block index) and is flushed once; the scalars live in SMEM.
# ---------------------------------------------------------------------------
#: Why the packed-mask kernels run in interpret mode only.  Compiled for
#: the TPU, the (bm, bn//8) uint8 tile breaks the (8, 128) block tiling,
#: Mosaic has no uint8 -> f32 cast, and the interleaved unpack (bit k of
#: byte j is column 8j + k) needs a lane shuffle it lowers only through a
#: 16x-padded VMEM temporary.  A bit-planar layout would lower; until the
#: layout changes, a compiled packed kernel is refused rather than unpacked
#: behind the caller's back.
PACKED_MASK_UNSUPPORTED = (
    "bit-packed masks are not supported by the compiled TPU kernels yet "
    "(the packed layout does not lower); pass a dense 0/1 mask "
    "(DCFConfig.pack_mask=False)"
)


def _unpack_w_tile(wp: Array, bn: int) -> Array:
    """(bm, bn//8) uint8 tile -> (bm, bn) f32 0/1 tile (VPU shifts).

    The canonical bit layout lives in ``bitmask.unpack_mask`` -- the same
    function unpacks tiles in VMEM (``bn`` is a PACK multiple, so the
    trailing column trim is a no-op)."""
    return bitmask.unpack_mask(wp, bn)


def _make_dual_kernel(mask_mode: str, bn: int, with_v: bool, with_u: bool,
                      with_diag: bool):
    """Kernel body factory; ``mask_mode`` in {'none', 'dense', 'packed'}."""

    def kernel(*refs):
        if mask_mode == "none":
            u_ref, v_ref, m_ref, lam_ref, *outs = refs
            w = None
        else:
            u_ref, v_ref, m_ref, w_ref, lam_ref, *outs = refs
            w = (
                _unpack_w_tile(w_ref[...], bn)
                if mask_mode == "packed"
                else w_ref[...].astype(jnp.float32)
            )
        outs = list(outs)
        out_v_ref = outs.pop(0) if with_v else None
        out_u_ref = outs.pop(0) if with_u else None
        obj_ref, psi2_ref = (outs if with_diag else (None, None))
        i, j = pl.program_id(0), pl.program_id(1)

        @pl.when((i == 0) & (j == 0))
        def _init_grid():
            if with_v:
                out_v_ref[...] = jnp.zeros_like(out_v_ref)
            if with_diag:
                obj_ref[0, 0] = jnp.float32(0)
                psi2_ref[0, 0] = jnp.float32(0)

        if with_u:
            @pl.when(j == 0)
            def _init_row():
                out_u_ref[...] = jnp.zeros_like(out_u_ref)

        u = u_ref[...]  # (bm, r)
        v = v_ref[...]  # (bn, r)
        lam = lam_ref[0, 0]
        low = _mxu(u, v.T)
        r = m_ref[...].astype(jnp.float32) - low
        rw = r if w is None else w * r
        psi = jnp.clip(rw, -lam, lam)
        if with_diag:
            # Epilogue diagnostics: Huber objective of the (masked) residual
            # and the clipped-residual energy, accumulated in SMEM scalars.
            a = jnp.abs(rw)
            obj_ref[0, 0] += jnp.sum(
                jnp.where(a <= lam, 0.5 * rw * rw, lam * a - 0.5 * lam * lam)
            )
            psi2_ref[0, 0] += jnp.sum(psi * psi)
        if with_u:
            out_u_ref[...] += _mxu(psi, v.astype(jnp.float32))
        if with_v:
            blk = pl.multiple_of(j * bn, bn)
            out_v_ref[pl.ds(blk, bn), :] += _mxu(psi.T, u.astype(jnp.float32))

    return kernel


def _dual_call(u, v, m, w, lam, bm, bn, interpret, with_v, with_u=True,
               with_diag=True):
    mm, r = u.shape
    n = v.shape[0]
    u_p = _pad_to(_pad_to(u, 0, bm), 1, LANE)
    v_p = _pad_to(_pad_to(v, 0, bn), 1, LANE)
    m_p = _pad_to(_pad_to(m, 0, bm), 1, bn)
    r_pad = u_p.shape[1]
    lam_arr = _lam_operand(lam)
    n_p = m_p.shape[1]

    if w is None:
        mask_mode = "none"
        operands = (u_p, v_p, m_p, lam_arr)
        w_specs = []
    elif bitmask.is_packed(w):
        if not _should_interpret(interpret):
            raise NotImplementedError(PACKED_MASK_UNSUPPORTED)
        if bn % bitmask.PACK:
            raise ValueError(f"bn={bn} must be a multiple of {bitmask.PACK} "
                             "for bit-packed masks")
        bnb = bn // bitmask.PACK
        # The packed plane must cover every padded data column (zero bytes
        # behave exactly like mask-zero padding).
        w_p = _pad_to(_pad_to(w, 0, bm), 1, n_p // bitmask.PACK)
        mask_mode = "packed"
        operands = (u_p, v_p, m_p, w_p, lam_arr)
        w_specs = [pl.BlockSpec((bm, bnb), lambda i, j: (i, j))]
    else:
        w_p = _pad_to(_pad_to(w, 0, bm), 1, bn)
        mask_mode = "dense"
        operands = (u_p, v_p, m_p, w_p, lam_arr)
        w_specs = [pl.BlockSpec((bm, bn), lambda i, j: (i, j))]

    grid = (m_p.shape[0] // bm, n_p // bn)  # (m-blocks, n-blocks)
    out_specs, out_shapes = [], []
    if with_v:
        # out_v is grid-resident: its block is the whole (n_p, r_pad) plane.
        out_specs.append(pl.BlockSpec((n_p, r_pad), lambda i, j: (0, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((n_p, r_pad), jnp.float32))
    if with_u:
        out_specs.append(pl.BlockSpec((bm, r_pad), lambda i, j: (i, 0)))
        out_shapes.append(
            jax.ShapeDtypeStruct((u_p.shape[0], r_pad), jnp.float32)
        )
    if with_diag:
        for _ in range(2):  # obj, psi2
            out_specs.append(pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                                          memory_space=pltpu.SMEM))
            out_shapes.append(jax.ShapeDtypeStruct((1, 1), jnp.float32))

    outs = pl.pallas_call(
        _make_dual_kernel(mask_mode, bn, with_v, with_u, with_diag),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, r_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            *w_specs,
            _LAM_SPEC,
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=_should_interpret(interpret),
    )(*operands)
    outs = list(outs)
    result = []
    if with_v:
        result.append(outs.pop(0)[:n, :r])
    if with_u:
        result.append(outs.pop(0)[:mm, :r])
    if with_diag:
        result.extend(o[0, 0] for o in outs)
    return tuple(result) if len(result) > 1 else result[0]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def huber_dual_contract(
    u: Array,
    v: Array,
    m: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> tuple[Array, Array, Array, Array]:
    """One streamed pass over M: ``(Psi^T U, Psi V, H_lam(R), ||Psi||_F^2)``.

    ``Psi = clip(M - U V^T, +-lam)``; all outputs f32.  Note the resident
    ``(n_pad, r_pad)`` out_v accumulator bounds ``n`` by the VMEM budget
    (~tens of thousands of columns at r<=128 -- see DESIGN.md Sec. 12); the
    DCF client blocks it serves are far below that.
    """
    return _dual_call(u, v, m, None, lam, bm, bn, interpret, with_v=True)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def huber_dual_contract_masked(
    u: Array,
    v: Array,
    m: Array,
    w: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> tuple[Array, Array, Array, Array]:
    """Masked dual contraction: ``Psi_W = clip(W*(M - U V^T), +-lam)`` with
    ``obj = H_lam(W * R)`` -- observed entries only.  ``w`` is a dense 0/1
    plane or a bit-packed uint8 plane (8 cols/byte), unpacked per-tile in
    VMEM."""
    return _dual_call(u, v, m, w, lam, bm, bn, interpret, with_v=True)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def huber_contract_u_diag(
    u: Array,
    v: Array,
    m: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> tuple[Array, Array, Array]:
    """``(Psi V, H_lam(R), ||Psi||_F^2)`` in one pass -- the U-step
    contraction with the round diagnostics for free (no out_v)."""
    return _dual_call(u, v, m, None, lam, bm, bn, interpret, with_v=False)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def huber_contract_u_diag_masked(
    u: Array,
    v: Array,
    m: Array,
    w: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> tuple[Array, Array, Array]:
    """Masked ``(Psi_W V, H_lam(W R), ||Psi_W||_F^2)`` in one pass."""
    return _dual_call(u, v, m, w, lam, bm, bn, interpret, with_v=False)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def huber_contract_v_packed(
    u: Array,
    v: Array,
    m: Array,
    w: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> Array:
    """Masked ``Psi_W^T U`` with a bit-packed uint8 mask plane: the inner
    sweep contraction of the compact data plane (mask bytes unpacked
    per-tile in VMEM; HBM mask traffic is 1 bit/entry)."""
    return _dual_call(u, v, m, w, lam, bm, bn, interpret,
                      with_v=True, with_u=False, with_diag=False)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def huber_contract_u_packed(
    u: Array,
    v: Array,
    m: Array,
    w: Array,
    lam: float | Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
) -> Array:
    """Masked ``Psi_W V`` with a bit-packed uint8 mask plane."""
    return _dual_call(u, v, m, w, lam, bm, bn, interpret,
                      with_v=False, with_u=True, with_diag=False)
