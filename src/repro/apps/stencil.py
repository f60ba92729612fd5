"""HPC proxy apps run as native SPMD programs (paper §6.3 analogues).

* ``stencil`` — Jacobi relaxation with ring halo exchange (ppermute =
  Isend/Irecv): the LULESH / miniAMR communication pattern.
* ``cg_solver`` — matrix-free conjugate gradient on a 1-D Laplacian:
  Allreduce-dominated, the AMG pattern (dot products every iteration).

Both are written exactly like the paper's ported MPI apps (Fig. 10): the
function receives the framework communicator from the context — the
IGNIS_COMM_WORLD swap — and otherwise keeps its "native" structure. The
paper's Table 5 productivity claim corresponds to the @ignis_export +
context-parsing wrapper being the ONLY addition.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import comm, compat
from repro.core.native import ignis_export


def _spmd_plan(tag: str, mesh, axis: str, statics: tuple, prog, x):
    """Persistent plan for a whole SPMD program (comm.persistent_program):
    traced + compiled once per (program, statics, operand aval, mesh) and
    reused from the collective plan cache. The re-trace this avoids is
    pure-Python, GIL-bound work — hoisting it is what lets a native branch
    overlap a concurrently running dataflow branch (DESIGN.md §10)."""
    x = jnp.asarray(x)

    def builder():
        return compat.shard_map(prog, mesh=mesh, in_specs=(P(axis),),
                                out_specs=P(axis))

    return comm.persistent_program(
        tag, mesh, (axis, *statics, x.shape, str(x.dtype)), builder), x


# ---------------------------------------------------------------------------
# stencil (LULESH/miniAMR analogue)
# ---------------------------------------------------------------------------


def stencil_native(mesh, axis, grid, iters: int):
    """The 'native MPI' program: runs directly under shard_map (the
    benchmark's baseline — executing the app without the framework)."""
    p = mesh.shape[axis]
    perm_fwd = [(i, (i + 1) % p) for i in range(p)]
    perm_bwd = [((i + 1) % p, i) for i in range(p)]

    def prog(u):  # u: (rows_local, cols)
        def body(_, u):
            up = jax.lax.ppermute(u[-1:], axis, perm_fwd)  # halo from above
            dn = jax.lax.ppermute(u[:1], axis, perm_bwd)  # halo from below
            ext = jnp.concatenate([up, u, dn], axis=0)
            lap = (ext[:-2] + ext[2:] + jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1)) * 0.25
            return lap

        return jax.lax.fori_loop(0, iters, body, u)

    fn, grid = _spmd_plan("stencil", mesh, axis, (iters,), prog, grid)
    return fn(grid)


@ignis_export("stencil_app")
def stencil_app(ctx, data=None, valid=None):
    """Framework-wrapped version (paper Fig. 10): args from the context."""
    iters = int(ctx.var("iters", 10))
    mesh, axis = ctx.comm()  # ← the MPI_COMM_WORLD swap
    out = stencil_native(mesh, axis, data, iters)
    return out, valid


# ---------------------------------------------------------------------------
# CG solver (AMG analogue — Allreduce-heavy)
# ---------------------------------------------------------------------------


def cg_native(mesh, axis, b, iters: int):
    """Solve A x = b for the 1-D Laplacian A = tridiag(-1, 2, -1), rows
    sharded over the axis; halo ppermute in matvec, psum in dots."""
    p = mesh.shape[axis]
    perm_fwd = [(i, (i + 1) % p) for i in range(p)]
    perm_bwd = [((i + 1) % p, i) for i in range(p)]

    def prog(b):  # b: (n_local,)
        idx = jax.lax.axis_index(axis)

        def matvec(x):
            up = jax.lax.ppermute(x[-1:], axis, perm_fwd)
            dn = jax.lax.ppermute(x[:1], axis, perm_bwd)
            up = jnp.where(idx == 0, 0.0, up)  # Dirichlet boundaries
            dn = jnp.where(idx == p - 1, 0.0, dn)
            xm = jnp.concatenate([up, x, dn])
            return 2 * x - xm[:-2] - xm[2:]

        def dot(a, c):
            return jax.lax.psum(jnp.sum(a * c), axis)

        x = jnp.zeros_like(b)
        r = b - matvec(x)
        q = r
        rs = dot(r, r)

        def body(_, carry):
            x, r, q, rs = carry
            Aq = matvec(q)
            alpha = rs / jnp.maximum(dot(q, Aq), 1e-30)
            x = x + alpha * q
            r = r - alpha * Aq
            rs_new = dot(r, r)
            q = r + (rs_new / jnp.maximum(rs, 1e-30)) * q
            return x, r, q, rs_new

        x, r, q, rs = jax.lax.fori_loop(0, iters, body, (x, r, q, rs))
        return x

    fn, b = _spmd_plan("cg", mesh, axis, (iters,), prog, b)
    return fn(b)


@ignis_export("cg_app")
def cg_app(ctx, data=None, valid=None):
    iters = int(ctx.var("iters", 20))
    mesh, axis = ctx.comm()
    out = cg_native(mesh, axis, data, iters)
    # hand the in-flight result back as a nonblocking handle: the driver
    # layer chains the Block adaptation onto it and the engine awaits it
    # (docs/collectives.md — handle-returning native apps)
    return comm.CollHandle("spmd.cg", ctx, (out, valid))


def laplacian_matvec_ref(x):
    xm = jnp.pad(x, 1)
    return 2 * x - xm[:-2] - xm[2:]
