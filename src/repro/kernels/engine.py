"""Sharded + batched sparse execution engine: the "48 clusters" layer.

Occamy scales a single compute cluster to 48 by replicating it behind two
HBM stacks and a D2D link; each cluster sees the *same* index stream but a
different slice of the dense data.  The JAX translation is ``shard_map``
over a device mesh:

  * **SpMM**   -- the BCSR index stream + blocks are replicated to every
    device (the paper's per-cluster index-stream copy), the dense operand is
    partitioned along its N columns (each chiplet's HBM holds its slice),
    and every device runs the *same* Pallas kernel on its slice.  The
    result is N-partitioned; materializing it is the all-gather.
  * **Batched SpMM** -- a :class:`~repro.core.formats.BatchedBCSR` batch is
    partitioned along the batch dim (whole problems per device, MoE-style),
    with the shared index stream again replicated.
  * **SpMSpM** -- A's row streams are replicated, B's column streams are
    partitioned, so each device owns a column stripe of the output.

Because each device executes the identical kernel on the identical operand
values for its output tiles, sharded fp32 results are **bit-for-bit** equal
to the single-device kernel (verified in tests/test_sparse_engine.py).

Mesh resolution: explicit ``mesh=`` arg > ``repro.parallel.context.MESH``
(set by the step builders) > an automatic 1-D ("data",) mesh over all local
devices.  On CPU the kernels run in interpret mode automatically.
"""
from __future__ import annotations

import functools
import os
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.formats import BCSR, INVALID_KEY, BatchedBCSR
from repro.kernels import tuning
from repro.kernels.spmm import ops as spmm_ops
from repro.kernels.spmm.kernel import spmm_bcsr
from repro.kernels.spmspm.kernel import spmspm_ell
from repro.parallel.mesh import make_mesh


def ensure_virtual_devices(n: int = 4, *, strict: bool = False) -> None:
    """Force >= ``n`` virtual CPU devices (tests / CLI demos on one host).

    Must run before the first jax backend touch; a no-op if XLA_FLAGS
    already forces a count or a real multi-device backend exists.  The env
    flag cannot take effect once the backend has initialized, so if that
    already happened with fewer than ``n`` devices this *warns* (or raises
    under ``strict=True``) instead of silently leaving sharded tests running
    on a single device."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    # The flag is exported first, so a backend that initializes here honors
    # it; a short count can only mean the backend predates this call.
    if jax.local_device_count() < n:
        msg = (f"ensure_virtual_devices({n}): the JAX backend already "
               f"initialized with {jax.local_device_count()} device(s); the "
               "XLA_FLAGS override cannot take effect in this process. "
               "Sharded code will run on fewer devices than requested -- "
               "call ensure_virtual_devices() before any jax API that "
               "touches the backend (or set XLA_FLAGS in the environment).")
        if strict:
            raise RuntimeError(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=2)


def _interpret_default(interpret: Optional[bool]) -> bool:
    return (not tuning.on_tpu()) if interpret is None else interpret


_MESH_INTERN: dict = {}


def _intern_mesh(mesh: Mesh) -> Mesh:
    """Canonicalize equal meshes to one object so the lru-cached sharded
    functions key on *mesh value semantics* -- (device assignment, axis
    names) -- not on whatever ``Mesh.__hash__`` does on the installed jax.
    Step builders recreate meshes freely; the caches must not depend on a
    version-specific Mesh identity/equality contract to stay hot.  The
    intern table is bounded by the number of distinct topologies a process
    ever builds (a handful)."""
    key = (tuple(mesh.devices.flat), mesh.devices.shape, mesh.axis_names)
    return _MESH_INTERN.setdefault(key, mesh)


def auto_mesh(mesh: Optional[Mesh] = None) -> Tuple[Mesh, str]:
    """Resolve (mesh, shard-axis): arg > parallel-context mesh > all devices.

    The resolved mesh is interned (see :func:`_intern_mesh`), so two equal
    meshes resolve to the same object and downstream lru caches hit."""
    if mesh is None:
        from repro.parallel import context as pctx
        mesh = pctx.MESH
    if mesh is None:
        mesh = make_mesh((jax.device_count(),), ("data",))
    mesh = _intern_mesh(mesh)
    axis = "data" if "data" in mesh.axis_names else mesh.axis_names[0]
    return mesh, axis


def stream_bucket(nnzb: int, *, minimum: int = 8) -> int:
    """Snap a routed nonzero-block count to its power-of-two bucket.

    A stream padded to ``stream_bucket(nnzb)`` entries keys a compile cache
    on the bucket, not the raw data-dependent count: recompiles are bounded
    by ``log2(grid)`` buckets while the stream stays within
    ``max(2 * nnzb, minimum)`` -- the floor dominates on tiny streams, the
    2x law everywhere else."""
    n = max(int(nnzb), int(minimum), 1)
    return 1 << (n - 1).bit_length()


def batch_bucket(n: int, *, minimum: int = 1, cap: Optional[int] = None) -> int:
    """The stream-bucket law applied to the *batch* dimension.

    Continuous-batching serving (``launch.serve.ServeScheduler``) runs each
    decode step at ``batch_bucket(active_rows)`` so batch-composition
    changes (join/evict between steps) hit a bounded set of compiled step
    shapes -- one per power-of-two bucket -- instead of one per occupancy
    count.  ``cap`` clamps to the allocated slot count (itself bucketed at
    allocation time, so the clamp never produces a non-bucket shape)."""
    b = stream_bucket(n, minimum=minimum)
    return min(b, cap) if cap is not None else b


def _pad_dim(x: jax.Array, dim: int, multiple: int, value=0) -> jax.Array:
    pad = (-x.shape[dim]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[dim] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# SpMM: N-column partitioning (replicated index stream, sliced dense HBM).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_spmm_fn(mesh: Mesh, axis: str, gm: int, bn: int, nt: int,
                     out_dtype: str, interpret: bool, quant: bool = False):
    kern = functools.partial(spmm_bcsr, n_block_rows=gm, bn=bn, nt=nt,
                             out_dtype=jnp.dtype(out_dtype), interpret=interpret)
    if quant:
        # BlockQuant stream: per-block scales replicated alongside the index
        # stream (every device dequantizes the same narrow blocks).
        return jax.jit(jax.shard_map(
            lambda rows, cols, blocks, scales, dense: kern(
                rows, cols, blocks, dense, scales=scales),
            mesh=mesh,
            in_specs=(P(), P(), P(), P(), P(None, axis)),
            out_specs=P(None, axis),
            check_vma=False,
        ))
    return jax.jit(jax.shard_map(
        lambda rows, cols, blocks, dense: kern(rows, cols, blocks, dense),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False,  # pallas_call has no vma rule
    ))


def shard_spmm(a: BCSR, dense: jax.Array, *, mesh: Optional[Mesh] = None,
               bn: Optional[int] = None, nt: Optional[int] = None,
               out_dtype=jnp.float32,
               interpret: Optional[bool] = None) -> jax.Array:
    """C = A @ dense with dense's N-tiles partitioned across the mesh.

    Handles uneven splits: N is zero-padded up to ``n_dev * nt * bn``
    granularity and the pad is stripped after the gather, so any N works on
    any mesh.  ``nt`` is the per-device output-residency width (each device
    re-walks the replicated index stream ``ceil(N_local / (nt*bn))``
    times)."""
    mesh, axis = auto_mesh(mesh)
    n_dev = mesh.shape[axis]
    interpret = _interpret_default(interpret)
    a = spmm_ops.pad_empty_rows(a)
    K, N = dense.shape
    assert K == a.shape[1], (a.shape, dense.shape)
    n_local = max(1, N // n_dev)
    tile_dtype = a.blocks.dtype if a.scales is not None else dense.dtype
    bn = spmm_ops._resolve_bn(bn, n_local, tile_dtype, a.block[1])
    nt = spmm_ops._resolve_nt(nt, bn, n_local, tile_dtype, a.block[1])
    dense = _pad_dim(dense, 1, n_dev * nt * bn)
    gm, _ = a.grid_shape
    fn = _sharded_spmm_fn(mesh, axis, gm, bn, nt, jnp.dtype(out_dtype).name,
                          interpret, quant=a.scales is not None)
    if a.scales is not None:
        out = fn(a.block_rows, a.block_cols, a.blocks, a.scales, dense)
    else:
        out = fn(a.block_rows, a.block_cols, a.blocks, dense)
    return out[:, :N]


# ---------------------------------------------------------------------------
# Batched SpMM: batch partitioning (whole problems per device, MoE-style).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_spmm_batched_fn(mesh: Mesh, axis: str, gm: int, bn: int, nt: int,
                             out_dtype: str, interpret: bool,
                             quant: bool = False):
    kern = functools.partial(spmm_bcsr, n_block_rows=gm, bn=bn, nt=nt,
                             out_dtype=jnp.dtype(out_dtype), interpret=interpret)

    if quant:
        def local_q(rows, cols, blocks, scales, dense):
            # per-batch scales ride the batch partition with their blocks
            return jax.vmap(lambda bl, s, d: kern(rows, cols, bl, d, scales=s)
                            )(blocks, scales, dense)

        return jax.jit(jax.shard_map(
            local_q, mesh=mesh,
            in_specs=(P(), P(), P(axis), P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        ))

    def local(rows, cols, blocks, dense):
        # vmap over this device's slice of the batch; index stream shared.
        return jax.vmap(lambda bl, d: kern(rows, cols, bl, d))(blocks, dense)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    ))


def shard_spmm_batched_stream(a: BatchedBCSR, dense: jax.Array, *,
                              mesh: Optional[Mesh] = None,
                              bn: Optional[int] = None,
                              nt: Optional[int] = None,
                              out_dtype=jnp.float32,
                              interpret: Optional[bool] = None) -> jax.Array:
    """Trace-safe batched SpMM on a *pre-normalized* stream.

    Contract: every block-row of ``a`` already appears in the stream (e.g.
    the caller ran :func:`repro.kernels.spmm.ops.pad_empty_rows` or built
    the stream with row coverage, as ``BatchedBCSR.with_capacity`` padding
    preserves).  Unlike :func:`shard_spmm_batched` this never inspects the
    index stream host-side, so it can be called *under jit* with the stream
    arrays as traced arguments -- the compile cache then keys on the stream
    *shape* (a bucketed capacity), never on the concrete index values."""
    mesh, axis = auto_mesh(mesh)
    n_dev = mesh.shape[axis]
    interpret = _interpret_default(interpret)
    B = a.batch
    if dense.ndim == 2:
        dense = jnp.broadcast_to(dense, (B,) + dense.shape)
    assert dense.shape[0] == B and dense.shape[1] == a.shape[2], (
        a.shape, dense.shape)
    N = dense.shape[2]
    tile_dtype = a.blocks.dtype if a.scales is not None else dense.dtype
    bn = spmm_ops._resolve_bn(bn, N, tile_dtype, a.block[1])
    nt = spmm_ops._resolve_nt(nt, bn, N, tile_dtype, a.block[1])
    dense = _pad_dim(_pad_dim(dense, 2, nt * bn), 0, n_dev)
    blocks = _pad_dim(a.blocks, 0, n_dev)
    gm, _ = a.grid_shape
    fn = _sharded_spmm_batched_fn(mesh, axis, gm, bn, nt,
                                  jnp.dtype(out_dtype).name, interpret,
                                  quant=a.scales is not None)
    if a.scales is not None:
        scales = _pad_dim(a.scales, 0, n_dev, value=1.0)
        out = fn(jnp.asarray(a.block_rows), jnp.asarray(a.block_cols), blocks,
                 scales, dense)
    else:
        out = fn(jnp.asarray(a.block_rows), jnp.asarray(a.block_cols), blocks,
                 dense)
    return out[:B, :, :N]


def shard_spmm_batched(a: BatchedBCSR, dense: jax.Array, *,
                       mesh: Optional[Mesh] = None, bn: Optional[int] = None,
                       nt: Optional[int] = None, out_dtype=jnp.float32,
                       interpret: Optional[bool] = None) -> jax.Array:
    """C[b] = A[b] @ dense[b], batch dim partitioned across the mesh.

    ``dense``: (B, K, N) or (K, N) broadcast. The batch is zero-padded up to
    a device multiple (zero blocks x zero dense = zero work rows) and the
    pad stripped after.  Host-side entry: the index stream is inspected with
    numpy (empty-row padding), so call it eagerly; under jit use
    :func:`shard_spmm_batched_stream` on a pre-normalized stream."""
    a = spmm_ops.pad_empty_rows(a)
    return shard_spmm_batched_stream(a, dense, mesh=mesh, bn=bn, nt=nt,
                                     out_dtype=out_dtype, interpret=interpret)


def shard_spmm_batched_bucketed(a: BatchedBCSR, dense: jax.Array, *,
                                mesh: Optional[Mesh] = None,
                                bn: Optional[int] = None,
                                nt: Optional[int] = None,
                                min_bucket: int = 8,
                                out_dtype=jnp.float32,
                                interpret: Optional[bool] = None
                                ) -> jax.Array:
    """Like :func:`shard_spmm_batched`, but the stream is padded up to its
    power-of-two bucket (:func:`stream_bucket`) before the call, so a
    sequence of calls with *varying* nnzb hits a bounded set of compiled
    programs (one per bucket) instead of one per count."""
    a = spmm_ops.pad_empty_rows(a)
    a = a.with_capacity(stream_bucket(a.nnzb, minimum=min_bucket))
    return shard_spmm_batched_stream(a, dense, mesh=mesh, bn=bn, nt=nt,
                                     out_dtype=out_dtype, interpret=interpret)


# ---------------------------------------------------------------------------
# SpMSpM: B-column-stream partitioning (each device owns an output stripe).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_spmspm_fn(mesh: Mesh, axis: str, rt: int, ct: int, nt: int,
                       out_dtype: str, interpret: bool, quant: bool = False):
    kern = functools.partial(spmspm_ell, rt=rt, ct=ct, nt=nt,
                             out_dtype=jnp.dtype(out_dtype), interpret=interpret)
    if quant:
        # Per-row scales are replicated with A's row streams.
        return jax.jit(jax.shard_map(
            lambda ak, av, asc, bk, bv: kern(ak, av, bk, bv, a_scales=asc),
            mesh=mesh,
            in_specs=(P(), P(), P(), P(axis, None), P(axis, None)),
            out_specs=P(None, axis),
            check_vma=False,
        ))
    return jax.jit(jax.shard_map(
        lambda ak, av, bk, bv: kern(ak, av, bk, bv),
        mesh=mesh,
        in_specs=(P(), P(), P(axis, None), P(axis, None)),
        out_specs=P(None, axis),
        check_vma=False,
    ))


def shard_spmspm(a_keys, a_vals, b_keys, b_vals, *,
                 mesh: Optional[Mesh] = None, rt: Optional[int] = None,
                 ct: Optional[int] = None, nt: Optional[int] = None,
                 out_dtype=jnp.float32,
                 interpret: Optional[bool] = None,
                 a_scales: Optional[jax.Array] = None) -> jax.Array:
    """Sharded sorted-stream intersection: A's row streams replicated, B's
    column streams partitioned; device d computes output columns of its B
    stripe.  R is padded to ``rt`` and C to ``n_dev * nt * ct`` (INVALID
    keys, zero values -- they can never match) and both pads are stripped.
    ``nt`` is the per-device output-column residency width.  ``a_scales``
    ((R,) f32) carries BlockQuant per-row scales for narrow ``a_vals``."""
    mesh, axis = auto_mesh(mesh)
    n_dev = mesh.shape[axis]
    interpret = _interpret_default(interpret)
    ak, av = jnp.asarray(a_keys), jnp.asarray(a_vals)
    bk, bv = jnp.asarray(b_keys), jnp.asarray(b_vals)
    R, C = ak.shape[0], bk.shape[0]
    if rt is None or ct is None:
        trt, tct = tuning.spmspm_tiles(R, max(1, C // n_dev), ak.shape[1],
                                       bk.shape[1], av.dtype)
        rt, ct = rt or trt, ct or tct
    if nt is None:
        nt = tuning.spmspm_nt(max(1, C // n_dev), ct, bk.shape[1], av.dtype)
    elif int(nt) < 1:
        raise ValueError(f"nt={nt} must be >= 1")
    nt = int(nt)
    ak = _pad_dim(ak, 0, rt, value=INVALID_KEY)
    av = _pad_dim(av, 0, rt)
    bk = _pad_dim(bk, 0, n_dev * nt * ct, value=INVALID_KEY)
    bv = _pad_dim(bv, 0, n_dev * nt * ct)
    fn = _sharded_spmspm_fn(mesh, axis, rt, ct, nt, jnp.dtype(out_dtype).name,
                            interpret, quant=a_scales is not None)
    if a_scales is not None:
        asc = jnp.asarray(a_scales, jnp.float32).reshape(R, 1)
        asc = _pad_dim(asc, 0, rt, value=1.0)
        return fn(ak, av, asc, bk, bv)[:R, :C]
    return fn(ak, av, bk, bv)[:R, :C]


# ---------------------------------------------------------------------------
# Block-sparse attention: query-axis sharding of the BlockMask stream walk.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_attention_sparse_fn(mesh: Mesh, axis: str, s_loc: int,
                                 skv: int, window: Optional[int], bq: int,
                                 bk: int, scale: Optional[float],
                                 interpret: bool):
    from repro.kernels.flash_attention.kernel import flash_attention_sparse

    def local(q, k, v, rows, cols, kinds):
        # Per-shard absolute query offset keeps causal/window refinements
        # exact -- the sharded-flash q_offset recipe, stream-walk edition.
        off = jax.lax.axis_index(axis) * s_loc
        return flash_attention_sparse(q, k, v, rows[0], cols[0], kinds[0],
                                      skv=skv, window=window, scale=scale,
                                      bq=bq, bk=bk, q_offset=off,
                                      interpret=interpret)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, axis, None), P(), P(),
                  P(axis), P(axis), P(axis)),
        out_specs=P(None, None, axis, None),
        check_vma=False,
    ))


def shard_attention_sparse(q: jax.Array, k: jax.Array, v: jax.Array, mask, *,
                           mesh: Optional[Mesh] = None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Block-sparse flash attention with the query axis sharded.

    The ``shard_spmm_batched_stream`` recipe applied to attention: the
    BlockMask is split into per-shard row sub-masks (``mask.shard_rows``),
    each lowered to the common power-of-two bucket capacity so every device
    runs the same compiled stream shape; K/V are replicated, queries are
    partitioned, and a per-shard ``q_offset`` (from ``axis_index``) keeps
    the absolute-position causal/window refinements exact.

    ``mask`` must cover (Sq, Skv) with Sq % (n_dev * bq) == 0.
    """
    mesh, axis = auto_mesh(mesh)
    n_dev = mesh.shape[axis]
    interpret = _interpret_default(interpret)
    B, Hq, Sq, D = q.shape
    Skv = k.shape[2]
    assert mask.sq == Sq and mask.skv == Skv, (mask.sq, mask.skv, Sq, Skv)
    assert mask.q_offset == 0, "shard_attention_sparse wants the full mask"
    assert Sq % (n_dev * mask.bq) == 0, (Sq, n_dev, mask.bq)
    s_loc = Sq // n_dev
    kp = (-Skv) % mask.bk
    if kp:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, kp), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, kp), (0, 0)))
    subs = mask.shard_rows(n_dev)
    # Common bucketed capacity: same compiled shape on every device.
    cap = stream_bucket(max(s.lower(bucket=False).capacity for s in subs))
    streams = [s.lower(capacity=cap) for s in subs]
    rows = jnp.asarray(np.stack([s.rows for s in streams]))
    cols = jnp.asarray(np.stack([s.cols for s in streams]))
    kinds = jnp.asarray(np.stack([s.kinds for s in streams]))
    fn = _sharded_attention_sparse_fn(mesh, axis, s_loc, Skv, mask.window,
                                      mask.bq, mask.bk, scale, interpret)
    return fn(q, k, v, rows, cols, kinds)
