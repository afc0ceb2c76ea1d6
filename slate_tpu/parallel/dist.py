"""DistMatrix: 2D block-cyclic distributed tile-stack matrix.

TPU-native analogue of the reference's distributed ``slate::Matrix``
(BaseMatrix.hh:40 + MatrixStorage.hh:158): the global (m, n) matrix is split
into nb x nb tiles, tile (i, j) is owned by process (i % p, j % q)
(func.hh:154), and algorithms move tiles with broadcasts/reductions.

Here the tile map is one dense array of shape (mt, nt, nb, nb) stored in
*cyclic order* (tiling.to_cyclic) and sharded over a ``Mesh(('p','q'))`` with
``PartitionSpec('p','q')`` — device (r, c) then holds exactly the tiles
{(i, j) : i % p == r, j % q == c}, reproducing block-cyclic ownership with
zero bookkeeping.  Tile communication is XLA collectives over ICI inside
``shard_map`` kernels (summa.py, dist_chol.py, dist_lu.py): the reference's
``tileBcast`` along a process row/column becomes a masked ``psum`` over one
mesh axis (BaseMatrix.hh:1917 -> lax.psum), ``listReduce`` becomes ``psum``
proper, and MOSI/lifetime/tag machinery (MatrixStorage.hh) vanishes.

Tile-grid padding: mt and nt are rounded up to multiples of lcm(p, q) so
that every device holds the same local count (static shapes).  Pad tiles are
zero; ``diag_pad_one`` additionally sets the padded diagonal to 1 so that
factorizations (potrf/getrf) act as identity on the pad block —
diag(A, I) = diag(L, I) diag(L, I)^H — keeping padded runs exact.  The
``diag_pad`` flag records this so factorization kernels can refuse inputs
whose pad diagonal is zero (which would NaN-poison the trailing updates).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.grid import num_tiles
from ..core.tiling import from_cyclic, from_tiles, to_cyclic, to_tiles
from .mesh import COL_AXIS, ROW_AXIS, mesh_shape, tile_sharding


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class DistMatrix:
    """Block-cyclic distributed matrix: cyclic-ordered tile stack + metadata."""

    tiles: jax.Array  # (mt, nt, nb, nb) in cyclic storage order, sharded
    m: int  # logical rows
    n: int  # logical cols
    nb: int
    mesh: Mesh
    diag_pad: bool = False  # True if padded diagonal is identity (or no pad)

    def tree_flatten(self):
        return (self.tiles,), (self.m, self.n, self.nb, self.mesh, self.diag_pad)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (tiles,) = children
        m, n, nb, mesh, diag_pad = aux
        return cls(tiles=tiles, m=m, n=n, nb=nb, mesh=mesh, diag_pad=diag_pad)

    @property
    def mt(self) -> int:
        return self.tiles.shape[0]

    @property
    def nt(self) -> int:
        return self.tiles.shape[1]

    @property
    def grid(self) -> Tuple[int, int]:
        return mesh_shape(self.mesh)

    @property
    def dtype(self):
        return self.tiles.dtype

    def require_diag_pad(self, who: str) -> None:
        """Factorization/solve kernels call this: a zero pad diagonal would
        NaN-poison their triangular solves (see module docstring)."""
        if not self.diag_pad:
            raise ValueError(
                f"{who} needs an identity-padded diagonal; build the operand "
                "with from_dense(..., diag_pad_one=True)"
            )


def _pad_grid(mesh: Mesh) -> int:
    p, q = mesh_shape(mesh)
    return math.lcm(p, q)


def padded_tiles(extent: int, nb: int, mesh: Mesh) -> int:
    """Tile count along one dim after rounding up to the mesh lcm."""
    return _round_up(max(1, num_tiles(extent, nb)), _pad_grid(mesh))


def from_dense(
    a: jax.Array, mesh: Mesh, nb: int, diag_pad_one: bool = False
) -> DistMatrix:
    """Distribute a dense (m, n) array over ``mesh`` block-cyclically.

    Analogue of Matrix::fromLAPACK + insertLocalTiles + tile scatter
    (Matrix.hh:58-112); on TPU it is a reshape + permutation, compiled as
    one program whose output is sharded over the mesh.
    """
    m, n = a.shape
    mp, np_ = padded_tiles(m, nb, mesh) * nb, padded_tiles(n, nb, mesh) * nb
    no_pad = mp == m and np_ == n
    if (isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer)
            and a.committed and a.sharding.device_set != set(mesh.devices.flat)):
        # one program cannot take an operand committed to other devices:
        # move it onto the mesh first, split over it where the shape allows
        p, q = mesh_shape(mesh)
        spec = P(ROW_AXIS, COL_AXIS) if m % p == 0 and n % q == 0 else P()
        a = jax.device_put(a, NamedSharding(mesh, spec))
    return DistMatrix(
        tiles=_cyclic_tiles(a, mesh, nb, diag_pad_one), m=m, n=n, nb=nb,
        mesh=mesh, diag_pad=diag_pad_one or no_pad,
    )


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _cyclic_tiles(a: jax.Array, mesh: Mesh, nb: int, diag_pad_one: bool) -> jax.Array:
    """Dense -> sharded cyclic tile stack in ONE program.  Run op by op,
    the pad, reshape and permutation each materialise the whole matrix
    on one device: a 32768^2 f32 operand on a 2x2 v5e mesh ran out of HBM
    at the permutation (PR 21).  Compiled together, XLA partitions them
    and the result is born sharded.  Its ops sit under the
    ``redistribute`` stage scope."""
    m, n = a.shape
    mp, np_ = padded_tiles(m, nb, mesh) * nb, padded_tiles(n, nb, mesh) * nb
    with jax.named_scope("redistribute"):
        a = jnp.pad(a, ((0, mp - m), (0, np_ - n)))
        if diag_pad_one:
            d = jnp.arange(min(m, n), min(mp, np_))
            a = a.at[d, d].set(1)
        t = to_cyclic(to_tiles(a, nb), *mesh_shape(mesh))
        return lax.with_sharding_constraint(t, tile_sharding(mesh))


def to_dense(d: DistMatrix) -> jax.Array:
    """Gather back to a logically-ordered dense (m, n) array."""
    t = from_cyclic(d.tiles, *mesh_shape(d.mesh))
    return from_tiles(t, d.m, d.n)


def empty_like(d: DistMatrix, m: Optional[int] = None, n: Optional[int] = None) -> DistMatrix:
    m = d.m if m is None else m
    n = d.n if n is None else n
    mt = padded_tiles(m, d.nb, d.mesh)
    nt = padded_tiles(n, d.nb, d.mesh)
    t = jnp.zeros((mt, nt, d.nb, d.nb), d.dtype)
    t = jax.device_put(t, tile_sharding(d.mesh))
    return DistMatrix(tiles=t, m=m, n=n, nb=d.nb, mesh=d.mesh)


# ---------------------------------------------------------------------------
# Non-uniform block sizes (func.hh:39-203 parity; exercised by ref ex13)
# ---------------------------------------------------------------------------


def from_dense_nonuniform(
    a: jax.Array,
    mesh: Mesh,
    row_sizes,
    col_sizes,
) -> DistMatrix:
    """Distribute with PER-INDEX tile sizes (reference func.hh non-uniform
    block-size lambdas, ex13): non-uniform tile (i, j) of size
    (row_sizes[i], col_sizes[j]) keeps the reference's ownership rule
    (i % p, j % q) and is embedded top-left into a uniform
    max(sizes)-square padded tile — the TPU-idiomatic canonicalization
    (static shapes; XLA cannot trace ragged tiles).  The zero embedding is
    exact for multiply-class ops and norms: gemm's tile products align
    because row k of B and column k of A pad identically; factorizations
    require uniform tiling (interior pad would make diag tiles singular) —
    ``redistribute_nonuniform`` retiles onto a uniform nb for those.

    Returns a DistMatrix with nb = max of all sizes and the logical
    (m, n) = sums of sizes; recover the dense array with
    ``to_dense_nonuniform(d, row_sizes, col_sizes)``."""
    import numpy as _np

    row_sizes = [int(x) for x in row_sizes]
    col_sizes = [int(x) for x in col_sizes]
    m, n = a.shape
    if sum(row_sizes) != m or sum(col_sizes) != n:
        raise ValueError(
            f"non-uniform sizes must tile the matrix exactly: "
            f"sum(rows)={sum(row_sizes)} vs m={m}, sum(cols)={sum(col_sizes)} vs n={n}"
        )
    nb = max(row_sizes + col_sizes)
    mt = _round_up(max(1, len(row_sizes)), _pad_grid(mesh))
    nt = _round_up(max(1, len(col_sizes)), _pad_grid(mesh))
    roff = _np.concatenate([[0], _np.cumsum(row_sizes)])
    coff = _np.concatenate([[0], _np.cumsum(col_sizes)])
    # assemble on host (one device transfer), not per-tile .at[].set
    th = _np.zeros((mt, nt, nb, nb), _np.asarray(a).dtype)
    ah = _np.asarray(a)
    for i, mb in enumerate(row_sizes):
        for j, nbj in enumerate(col_sizes):
            th[i, j, :mb, :nbj] = ah[roff[i] : roff[i] + mb, coff[j] : coff[j] + nbj]
    t = to_cyclic(jnp.asarray(th), *mesh_shape(mesh))
    t = jax.device_put(t, tile_sharding(mesh))
    return DistMatrix(tiles=t, m=m, n=n, nb=nb, mesh=mesh, diag_pad=False)


def to_dense_nonuniform(d: DistMatrix, row_sizes, col_sizes) -> jax.Array:
    """Gather a from_dense_nonuniform matrix back to dense (m, n)."""
    import numpy as _np

    row_sizes = [int(x) for x in row_sizes]
    col_sizes = [int(x) for x in col_sizes]
    t = from_cyclic(d.tiles, *mesh_shape(d.mesh))
    roff = _np.concatenate([[0], _np.cumsum(row_sizes)])
    coff = _np.concatenate([[0], _np.cumsum(col_sizes)])
    out = jnp.zeros((d.m, d.n), d.dtype)
    for i, mb in enumerate(row_sizes):
        for j, nbj in enumerate(col_sizes):
            out = out.at[roff[i] : roff[i] + mb, coff[j] : coff[j] + nbj].set(
                t[i, j, :mb, :nbj]
            )
    return out


REDIST_IMPLS = ("auto", "eager", "shardmap")


def redistribute(
    d: DistMatrix, mesh: Mesh, nb: Optional[int] = None,
    impl: Optional[str] = None,
) -> DistMatrix:
    """Re-distribute between layouts (src/redistribute.cc analogue),
    entirely on device.  Two lowerings, selected by ``impl``:

    - ``eager``: the cyclic-order permutation + one device_put that XLA
      lowers to collective traffic — no host round trip (the reference
      moves tiles with point-to-point MPI, redistribute.cc:20).  Caveat:
      the permutation materializes a replicated intermediate (one full
      tile grid per device).
    - ``shardmap``: the ppermute ring all-to-all exchange — each device
      circulates its own 1/(p*q) source block around the linearized mesh
      ring (Cannon-style: q-1 column rotations per row step, p-1 row
      steps) and gathers the tiles it owns under the DESTINATION layout,
      so per-device residency stays at one source + one destination
      block.  Audited like any broadcast (``redistribute_wire_bytes`` is
      the analytic link-byte total, proven in tests/test_comm_audit.py);
      bitwise-identical to the eager path (moves exact bytes).  Requires
      an unchanged ``nb`` and a target mesh that re-arranges exactly the
      source mesh's devices.
    - ``auto`` (None, the default): shardmap when eligible, else eager.

    Pad-tile diagonal contract: a ``diag_pad`` source KEEPS its identity
    pad through any reshape — freshly grown pad tiles get their diagonal
    set to 1 (both lowerings), and an nb retile re-establishes it via
    ``from_dense(diag_pad_one=True)`` — so redistributed factorization
    operands stay factorizable (the round-trip bug class pinned by
    tests/test_parallel.py::test_redistribute_roundtrip_bitwise)."""
    nb2 = nb or d.nb
    impl = impl or "auto"
    if impl not in REDIST_IMPLS:
        raise ValueError(
            f"unknown redistribute impl {impl!r}; expected one of "
            f"{REDIST_IMPLS}"
        )
    p2, q2 = mesh_shape(mesh)
    if nb2 == d.nb and impl != "eager":
        if (p2, q2) == mesh_shape(d.mesh) and bool(
            (mesh.devices == d.mesh.devices).all()
        ):
            return d  # identical layout: nothing moves
        cmap = _shardmap_coord_map(d.mesh, mesh)
        if cmap is not None:
            return _redistribute_shardmap(d, mesh, cmap)
        if impl == "shardmap":
            raise ValueError(
                "shardmap redistribute needs the target mesh to re-arrange "
                "exactly the source mesh's devices; use impl='eager'/'auto'"
            )
    elif impl == "shardmap":
        raise ValueError(
            "shardmap redistribute cannot retile (nb change); use "
            "impl='eager'/'auto'"
        )
    if nb2 == d.nb:
        # pure ownership change: logical tile grid is unchanged
        t_log = from_cyclic(d.tiles, *mesh_shape(d.mesh))
        mt2 = padded_tiles(d.m, nb2, mesh)
        nt2 = padded_tiles(d.n, nb2, mesh)
        mt, nt = t_log.shape[:2]
        if (mt2, nt2) != (mt, nt):  # pad/crop the tile grid for the new lcm
            t_log = jnp.pad(
                t_log[: min(mt, mt2), : min(nt, nt2)],
                ((0, max(0, mt2 - mt)), (0, max(0, nt2 - nt)), (0, 0), (0, 0)),
            )
            start, stop = fresh_pad_diag_range(mt, nt, mt2, nt2)
            if d.diag_pad and stop > start:
                fresh = jnp.arange(start, stop)
                t_log = t_log.at[fresh, fresh].set(
                    jnp.eye(nb2, dtype=d.dtype))
        t2 = to_cyclic(t_log, p2, q2)
        t2 = jax.device_put(t2, tile_sharding(mesh))
        no_pad2 = mt2 * nb2 == d.m and nt2 * nb2 == d.n
        return DistMatrix(
            tiles=t2, m=d.m, n=d.n, nb=nb2, mesh=mesh,
            diag_pad=no_pad2 or d.diag_pad,
        )
    # nb change: retile through a device-resident (sharded) dense view,
    # re-establishing the identity pad diagonal when the source had one
    dense = from_tiles(from_cyclic(d.tiles, *mesh_shape(d.mesh)), d.m, d.n)
    return from_dense(dense, mesh, nb2, diag_pad_one=d.diag_pad)


def _shardmap_coord_map(mesh1: Mesh, mesh2: Mesh):
    """(r1, c1) -> (r2, c2) device-identity map between two meshes, or
    None when ``mesh2`` is not a re-arrangement of exactly ``mesh1``'s
    devices (the shardmap-eligibility test)."""
    import numpy as _np

    d1, d2 = mesh1.devices, mesh2.devices
    if d1.size != d2.size:
        return None
    pos2 = {dev: rc for rc, dev in _np.ndenumerate(d2)}
    cmap = []
    for r in range(d1.shape[0]):
        row = []
        for c in range(d1.shape[1]):
            got = pos2.get(d1[r, c])
            if got is None:
                return None
            row.append((int(got[0]), int(got[1])))
        cmap.append(tuple(row))
    return tuple(cmap)


def fresh_pad_diag_range(mt1: int, nt1: int, mt2: int, nt2: int):
    """Tile indices [start, stop) whose (t, t) pad tile is FRESH to a
    tile grid grown from (mt1, nt1) to (mt2, nt2): the source covers
    diagonal tiles below min(mt1, nt1); a diag_pad source needs every
    fresh one set to the identity (the from_dense(diag_pad_one=True)
    contract — their global diagonal indices all sit past min(m, n)).
    ONE source for the contract: the eager/shardmap redistribute
    lowerings and ft.elastic's host relayout all consume this."""
    return min(mt1, nt1), min(mt2, nt2)


def redistribute_wire_bytes(src_tiles_shape, p: int, q: int,
                            itemsize: int) -> int:
    """Analytic audited link bytes of the shardmap redistribution of a
    (mt, nt, nb, nb) cyclic stack off a (p, q) mesh: the ring schedule
    rotates each device's source block p*(q-1) times along the column
    axis (q link pairs per hop under comm.ppermute_a's convention) and
    p-1 times along the row axis (p pairs per hop).  The formula is the
    comm-audit acceptance bound (tests/test_comm_audit.py)."""
    mt, nt, nb, _ = src_tiles_shape
    block = (mt // p) * (nt // q) * nb * nb * itemsize
    return block * (p * (q - 1) * q + (p - 1) * p)


def _redist_shardmap_fn(at, mesh1, p1, q1, dims, cmap, diag_pad):
    """The ring-exchange program over the SOURCE mesh.  ``dims`` =
    (p2, q2, mt1, nt1, mt2, nt2, nb); ``cmap`` maps each source
    coordinate to the destination-mesh coordinate of the SAME physical
    device, so each device computes exactly the block it owns under the
    destination layout — the output reassembles onto the target mesh
    with zero further movement (_redistribute_shardmap).  Unjitted form
    so the comm-audit volume test traces it directly;
    ``_redist_shardmap_jit`` is the dispatch path."""
    p2, q2, mt1, nt1, mt2, nt2, nb = dims
    mtl2, ntl2 = mt2 // p2, nt2 // q2
    spec = P(ROW_AXIS, COL_AXIS)
    from .comm import ppermute_a, shard_map_compat

    r2m = jnp.asarray([[rc[0] for rc in row] for row in cmap])
    c2m = jnp.asarray([[rc[1] for rc in row] for row in cmap])

    def kernel(t_loc):
        mtl1, ntl1 = t_loc.shape[0], t_loc.shape[1]
        dtype = t_loc.dtype
        r1 = lax.axis_index(ROW_AXIS)
        c1 = lax.axis_index(COL_AXIS)
        r2 = r2m[r1, c1]
        c2 = c2m[r1, c1]
        # logical tile indices of MY destination slots (block-cyclic on
        # the target grid)
        i2 = r2 + jnp.arange(mtl2) * p2
        j2 = c2 + jnp.arange(ntl2) * q2
        dest = jnp.zeros((mtl2, ntl2, nb, nb), dtype)
        pad0, pad1 = fresh_pad_diag_range(mt1, nt1, mt2, nt2)
        if diag_pad and pad1 > pad0:
            # fresh pad tiles carry the identity diagonal; i2 == j2
            # already bounds the index below pad1 = min(mt2, nt2)
            fresh = ((i2[:, None] == j2[None, :])
                     & (i2[:, None] >= pad0))
            dest = jnp.where(
                fresh[:, :, None, None], jnp.eye(nb, dtype=dtype)[None, None],
                dest,
            )
        buf = t_loc
        off_p = off_q = 0
        for idx in range(p1 * q1):
            # buf currently holds the source block of coordinate (rs, cs)
            rs = (r1 + off_p) % p1
            cs = (c1 + off_q) % q1
            take_i = (i2 % p1 == rs) & (i2 < mt1)
            take_j = (j2 % q1 == cs) & (j2 < nt1)
            src_i = jnp.clip(i2 // p1, 0, mtl1 - 1)
            src_j = jnp.clip(j2 // q1, 0, ntl1 - 1)
            g = buf[src_i][:, src_j]
            m = (take_i[:, None] & take_j[None, :])[:, :, None, None]
            dest = jnp.where(m, g, dest)
            if idx == p1 * q1 - 1:
                break  # last block consumed: no trailing rotation
            if (idx + 1) % q1 == 0:
                buf = ppermute_a(buf, ROW_AXIS,
                                 [((i + 1) % p1, i) for i in range(p1)])
                off_p += 1
            else:
                buf = ppermute_a(buf, COL_AXIS,
                                 [((i + 1) % q1, i) for i in range(q1)])
                off_q += 1
        return dest

    return shard_map_compat(
        kernel, mesh=mesh1, in_specs=(spec,), out_specs=spec,
        check_vma=False,
    )(at)


_redist_shardmap_jit = functools.partial(
    jax.jit, static_argnums=(1, 2, 3, 4, 5, 6)
)(_redist_shardmap_fn)


def _redistribute_shardmap(d: DistMatrix, mesh: Mesh, cmap) -> DistMatrix:
    p1, q1 = mesh_shape(d.mesh)
    p2, q2 = mesh_shape(mesh)
    mt1, nt1 = d.tiles.shape[0], d.tiles.shape[1]
    mt2 = padded_tiles(d.m, d.nb, mesh)
    nt2 = padded_tiles(d.n, d.nb, mesh)
    dims = (p2, q2, mt1, nt1, mt2, nt2, d.nb)
    out = _redist_shardmap_jit(d.tiles, d.mesh, p1, q1, dims, cmap,
                               d.diag_pad)
    # each device already holds exactly its destination-layout block;
    # reassemble the shards under the TARGET mesh's sharding — a
    # metadata-level rebind, zero further data movement
    sh2 = tile_sharding(mesh)
    shards = {s.device: s.data for s in out.addressable_shards}
    arrs = [shards[dev] for dev in
            sh2.addressable_devices_indices_map(
                (mt2, nt2, d.nb, d.nb)).keys()]
    t2 = jax.make_array_from_single_device_arrays(
        (mt2, nt2, d.nb, d.nb), sh2, arrs)
    no_pad2 = mt2 * d.nb == d.m and nt2 * d.nb == d.n
    return DistMatrix(
        tiles=t2, m=d.m, n=d.n, nb=d.nb, mesh=mesh,
        diag_pad=no_pad2 or d.diag_pad,
    )


def redistribute_nonuniform(
    d: DistMatrix, row_sizes, col_sizes, nb: Optional[int] = None,
    diag_pad_one: bool = False,
) -> DistMatrix:
    """Re-distribute a ``from_dense_nonuniform`` matrix onto a UNIFORM
    nb tiling of the same mesh — the bridge that lets every factorization
    run on non-uniformly tiled input (reference ex13 runs algorithms on
    func.hh:39-78 non-uniform distributions; here the uniform retile is
    the algorithm-facing canonical form because interior tile padding
    would make diagonal tiles singular).  Entirely device-resident: the
    per-tile unpad/reassembly works on global (sharded) arrays, the
    analogue of redistribute.cc's tile-by-tile MPI moves.  Pass
    ``diag_pad_one=True`` when the result feeds a factorization (the
    from_dense padding contract)."""
    dense = to_dense_nonuniform(d, row_sizes, col_sizes)
    return from_dense(dense, d.mesh, nb or d.nb, diag_pad_one=diag_pad_one)
