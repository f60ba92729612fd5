"""Wide (shuffle-backed) operators on the collective fabric (paper §3.6, §6.2).

* PSRS distributed sort — Parallel Sorting by Regular Sampling, exactly the
  algorithm the paper uses for TeraSort: local sort → regular samples →
  all-gather → global pivots → bucket → all_to_all → local merge.
* hash exchange — reduceByKey/join/partitionBy routing (MPI_Alltoall).
* sorted segmented reduce — log-depth associative_scan over key segments
  (the jnp oracle of kernels/segment_reduce).
* sort-merge / hash join with bounded fan-out.

All fixed-shape: buckets are capacity-padded, overflow is *detected* (psum),
never silently dropped — the price of static shapes on a systolic machine
(DESIGN.md §1). This module is sync-free: every stage returns device scalars
``(overflow, max_fill)`` alongside its data, and the adaptive shuffle engine
(shuffle_plan.py, DESIGN.md §6) performs one deferred host check per wide
node, retries with a capacity derived from the observed ``max_fill``, and
remembers the fit for the next action.

Stages take a ``post`` hook — a per-shard local transform fused into the same
shard_map body — so sort→segment-heads→segmented-reduce chains (reduceByKey,
distinct, groupByKey) execute as ONE wide stage instead of three dispatches.
Post hooks are valid because PSRS/hash routing sends equal keys to one shard:
no key segment ever spans a shard boundary.

Inside a stage the device work carries ``jax.named_scope`` names, which the
profiler's trace keeps on each op (docs/profiling.md §schema): ``ARGSORT``
(a sort of the keys: in the sort stage the one ``lax.sort`` that carries
the payload with the keys, see ``_sort_carry``), ``PERMUTE`` (a gather
that applies a sort's order to a leaf, ``x[order]``: in the sort stage only
the fallback for leaves with trailing dimensions, which cannot ride in the
sort), ``EXCHANGE`` (sampling, bucket packing and the ``all_to_all``),
``MERGE`` (the local merge after the exchange, and the join's sort-merge)
and ``POST`` (the post hook). Scopes name ops only; they cost nothing at
run time and leave the stage's executable as it was.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compat
from repro.core.context import IContext
from repro.core.partition import Block

_scope = jax.named_scope

# named scopes, not properties
ARGSORT = "ignis.argsort"  # props: ignore
PERMUTE = "ignis.permute"  # props: ignore
EXCHANGE = "ignis.exchange"  # props: ignore
MERGE = "ignis.merge"  # props: ignore
POST = "ignis.post"  # props: ignore


def _argsort(keys):
    with _scope(ARGSORT):
        return jnp.argsort(keys, stable=True)


def _permute(order, *trees):
    """Each leaf of ``trees`` gathered by ``order``: a sort's order applied."""
    with _scope(PERMUTE):
        out = jax.tree.map(lambda x: x[order], trees)
    return out if len(trees) > 1 else out[0]


def _rides(x) -> bool:
    """Whether a leaf can be an operand of ``lax.sort``: one value per row."""
    return x.ndim == 1


def _distinct(leaves):
    """``leaves`` with each array object once, in first-seen order, and for
    each leaf its position in that list."""
    first, uniq = {}, []
    for x in leaves:
        if id(x) not in first:
            first[id(x)] = len(uniq)
            uniq.append(x)
    return uniq, [first[id(x)] for x in leaves]


def key_leaves(keys) -> tuple:
    """A sort key as the tuple of its 1-D leaves, compared lexicographically:
    a tuple key is its own leaves, any other key one leaf."""
    if not isinstance(keys, tuple):
        return (keys,)
    if not keys or any(getattr(k, "ndim", None) != 1 for k in keys):
        raise TypeError("a tuple sort key needs one or more leaves with one "
                        "value per row")
    return keys


def row_bytes(x) -> int:
    """Bytes of one row of the array ``x``."""
    return math.prod(x.shape[1:]) * x.dtype.itemsize


def _layout(ks, leaves):
    """How a local sort that compares ``(~valid, *ks)`` moves ``leaves``,
    each distinct array once. ``slot[id(x)]`` is the sort operand that
    carries leaf ``x`` (a key leaf's own, or one of ``riders`` after the
    keys), or ``-1 - j`` for the j-th of ``gathered``: the leaves with
    trailing dimensions, which cannot ride in the sort and are gathered
    after it by its order."""
    slot = {}
    for i, k in enumerate(ks, 1):
        slot.setdefault(id(k), i)
    riders, gathered = [], []
    for x in leaves:
        if id(x) in slot:
            continue
        if _rides(x):
            slot[id(x)] = 1 + len(ks) + len(riders)
            riders.append(x)
        else:
            slot[id(x)] = -1 - len(gathered)
            gathered.append(x)
    return slot, riders, gathered


def sort_traffic(keys, data, p: int) -> dict:
    """What ``sort_stage`` on p executors compares and moves when it sorts
    ``data`` by ``keys`` (arrays or tracers, one row each): the key leaves,
    the leaves gathered after the local sort, the bytes a row of the keys
    and the data (each distinct array once) and of those gathered leaves,
    and at p > 1 the bucket slices that fill the send buffers (p for each
    distinct leaf the local sort returns, ``valid`` included)."""
    ks = key_leaves(keys)
    _, riders, gathered = _layout(ks, jax.tree.leaves(data))
    moved = [*_distinct(ks)[0], *riders, *gathered]
    sent = 1 + len(ks) + len(riders) + len(gathered)
    return dict(key_leaves=len(ks), gathers=len(gathered),
                row_bytes=sum(map(row_bytes, moved)),
                gather_row_bytes=sum(map(row_bytes, gathered)),
                bucket_slices=p * sent if p > 1 else 0)


def _sort_carry(keys, valid, *trees):
    """``(keys, valid, *trees)`` with their rows in one stable order: valid
    rows first, by key, then the invalid rows. ``keys`` is one array or a
    tuple of 1-D leaves, compared lexicographically, each in its dtype's
    order.

    One ``lax.sort`` compares ``(~valid, *key_leaves)`` lexicographically
    and carries every other 1-D leaf as an operand, each distinct array once
    (the keys are often leaves of the data); the sorted ``valid`` is the
    negated first key. A leaf with trailing dimensions cannot be an operand:
    an iota rides instead and those leaves are gathered by it (``_layout``).
    The valid rows come out in the order ``_permute(_argsort(where(valid,
    keys, _sentinel)), ...)`` gives them, and a valid key equal to the
    sentinel still sorts before every invalid row. With no payload beyond
    the keys, rows that compare equal are equal bit for bit, so the sort
    need not be stable (XLA:TPU carries one more operand, an iota, for a
    stable sort)."""
    ks = key_leaves(keys)
    leaves, treedef = jax.tree.flatten(trees)
    slot, riders, gathered = _layout(ks, leaves)
    operands = [~valid, *ks, *riders]
    num_keys = 1 + len(ks)
    with _scope(ARGSORT):
        if gathered:
            operands.append(jnp.arange(valid.shape[0], dtype=jnp.int32))
        out = list(jax.lax.sort(operands, num_keys=num_keys,
                                is_stable=len(operands) > num_keys))
    out[0] = ~out[0]
    if gathered:
        with _scope(PERMUTE):
            moved = [x[out[-1]] for x in gathered]

    def get(x):
        i = slot[id(x)]
        return out[i] if i >= 0 else moved[-1 - i]

    sk = tuple(out[1:num_keys]) if isinstance(keys, tuple) else out[1]
    return (sk, out[0], *jax.tree.unflatten(treedef, [get(x) for x in leaves]))


def _sentinel(dtype):
    """Largest value of dtype — sorts invalid rows to the tail."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def _sentinel_low(dtype):
    """Smallest value of dtype — masks invalid rows out of an argmax."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def _hash_u32(x):
    """splitmix-style avalanche on int keys → uint32."""
    h = x.astype(jnp.uint32)
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def capacity_for(factor: float, n_local: int, p: int) -> int:
    """Per-destination bucket capacity for a given capacity factor.

    ``factor = p`` is the worst case: C = n_local fits even when every row
    of a shard routes to one destination."""
    return max(int(math.ceil(factor * n_local / p)), 1)


# ---------------------------------------------------------------------------
# pack-by-destination + all_to_all  (shared by PSRS and hash exchange)
# ---------------------------------------------------------------------------


def _pack(dest, payload, p, C):
    """Rows packed into ``dest`` buckets of capacity C, on one shard.

    dest: (n,) int32 in [0, p); payload: pytree of (n, …) leaves (must
    include its own validity leaf). A stable argsort of ``dest`` ranks each
    row within its bucket; rows ranked past C drop to a scratch slot.
    Returns (pytree of (p·C, …), overflow, max_fill): overflow counts the
    dropped rows, and max_fill is the largest bucket demand observed — the
    capacity that *would* have fit, independent of C, so one retry sized
    from it always succeeds."""
    n = dest.shape[0]
    order = _argsort(dest)
    ds, payload = _permute(order, dest, payload)
    counts = jnp.bincount(ds, length=p)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n) - starts[ds]
    keep = pos < C
    slot = jnp.where(keep, ds * C + pos, p * C)  # overflow → scratch slot
    overflow = (n - keep.sum()).astype(jnp.int32)
    max_fill = counts.max().astype(jnp.int32)

    def pack(x):
        buf = jnp.zeros((p * C + 1, *x.shape[1:]), x.dtype)
        buf = buf.at[slot].set(x)
        return buf[: p * C]

    return jax.tree.map(pack, payload), overflow, max_fill


def _pack_exchange(dest, payload, axis, p, C):
    """Inside shard_map: route rows to ``dest`` buckets with capacity C
    (``_pack``) and exchange them. Returns (pytree of (p·C, …), overflow,
    max_fill). Dropped rows (bucket overflow) are counted, not silently
    lost."""
    with _scope(EXCHANGE):
        packed, overflow, max_fill = _pack(dest, payload, p, C)
        return _exchange(packed, axis, p, C), overflow, max_fill


def _fill_sorted(dest, payload, end, p, C):
    """``_pack`` for rows already in destination order: PSRS rows are
    sorted by key, so ``dest`` is non-decreasing and bucket d is the run of
    rows ``[starts[d], starts[d] + counts[d])``. The rows from ``end`` on
    (the invalid tail) are not sent and take no capacity: after a wide
    stage, padding fills half of a block, and routed to the last executor
    it overflowed that bucket.

    Each bucket is one contiguous window of C rows, masked past its count:
    p slices of each distinct leaf, padded by C rows so that no window
    starting near the end is clamped back. No gather, no second argsort
    and no scatter (the last two are each a sort on XLA:TPU). Returns
    (pytree of (p·C, …), overflow, max_fill), bit-identical to ``_pack``
    on the rows it keeps and zero in every other slot."""
    bounds = jnp.searchsorted(dest, jnp.arange(p + 1, dtype=dest.dtype), side="left")
    starts = bounds[:p].astype(jnp.int32)
    ends = jnp.minimum(bounds[1:].astype(jnp.int32), end)
    counts = jnp.maximum(ends - starts, 0)
    overflow = jnp.maximum(counts - C, 0).sum().astype(jnp.int32)
    max_fill = counts.max().astype(jnp.int32)
    keep = jnp.arange(C, dtype=jnp.int32) < counts[:, None]  # (p, C)

    def fill(x):
        x = jnp.pad(x, [(0, C)] + [(0, 0)] * (x.ndim - 1))
        rows = jnp.stack([jax.lax.dynamic_slice_in_dim(x, starts[d], C)
                          for d in range(p)])
        m = keep.reshape(keep.shape + (1,) * (x.ndim - 1))
        return jnp.where(m, rows, jnp.zeros((), x.dtype)).reshape(p * C, *x.shape[1:])

    leaves, treedef = jax.tree.flatten(payload)
    uniq, index = _distinct(leaves)
    filled = [fill(x) for x in uniq]
    return jax.tree.unflatten(treedef, [filled[i] for i in index]), overflow, max_fill


def _pack_sorted(dest, payload, end, axis, p, C):
    """``_pack_exchange`` for rows already in destination order: the
    buckets filled by ``_fill_sorted``, then exchanged."""
    with _scope(EXCHANGE):
        packed, overflow, max_fill = _fill_sorted(dest, payload, end, p, C)
        return _exchange(packed, axis, p, C), overflow, max_fill


def _exchange(packed, axis, p, C):
    """all_to_all of (p·C, …) buffers: bucket d of every shard to shard d.

    Leaves narrower than 32 bits travel widened to 32: XLA:TPU compiles an
    all_to_all of a large bool or int8 buffer in about a minute (one of
    2^25 rows per chip for v5e), and one of int32 in under a second."""

    def xchg(x):
        y = _widen(x).reshape(p, C, *x.shape[1:])
        y = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0, tiled=False)
        return _narrow(y.reshape(p * C, *x.shape[1:]), x.dtype)

    return jax.tree.map(xchg, packed)


def _widen(x):
    """``x`` as a 32-bit array that ``_narrow`` turns back, bit for bit."""
    if x.dtype == jnp.bool_:
        return x.astype(jnp.int32)
    if x.dtype.itemsize >= 4:
        return x
    bits = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))
    return bits.astype(jnp.uint32)


def _narrow(y, dtype):
    if dtype == jnp.bool_:
        return y != 0
    if jnp.dtype(dtype).itemsize >= 4:
        return y
    bits = y.astype(jnp.dtype(f"uint{8 * jnp.dtype(dtype).itemsize}"))
    return jax.lax.bitcast_convert_type(bits, dtype)


# ---------------------------------------------------------------------------
# fused wide stages (PSRS sort / hash exchange + local post-transform)
# ---------------------------------------------------------------------------


def _passthrough(k, v, d):
    return d, v


def _lt_eq(a, b):
    """``a < b`` and ``a == b`` in ``lax.sort``'s order: for floats, -0
    equals 0 and NaN equals NaN and sorts after every other value."""
    lt, eq = a < b, a == b
    if jnp.issubdtype(a.dtype, jnp.floating):
        na, nb = jnp.isnan(a), jnp.isnan(b)
        lt, eq = lt | (nb & ~na), eq | (na & nb)
    return lt, eq


def _route(samples, ks, p: int):
    """Destination of each row from the (p·p,) regular samples of each key
    leaf: the samples sorted lexicographically, the middle sample of
    quantile i's group is global pivot i, and a row goes to the number of
    pivot tuples at or below its key tuple, compared in the sort's order so
    that destinations never decrease along the sorted rows."""
    samples = jax.lax.sort(samples, num_keys=len(ks))
    le = jnp.ones((p - 1, ks[0].shape[0]), bool)  # pivot <= row, (p-1, n)
    for x, k in zip(reversed(samples), reversed(ks)):
        lt, eq = _lt_eq(x[p + p // 2 - 1 :: p][: p - 1, None], k)
        le = lt | (eq & le)
    return le.sum(0, dtype=jnp.int32)


def sort_stage(ctx: IContext, keys, valid, data, C: int, post=None):
    """One fused wide sort stage, no host syncs.

    PSRS exchange + local merge + ``post`` (a per-shard local transform;
    default returns ``(data, valid)``) traced as a single computation.
    Returns ``(post_out, overflow, max_fill)`` — the scalars are replicated
    int32 device values; the caller decides when (if ever) to sync on them.
    """
    post = post or _passthrough
    p = ctx.executors
    zero = jnp.zeros((), jnp.int32)
    if p == 1:
        ks, vs, ds = _sort_carry(keys, valid, data)
        with _scope(POST):
            out = post(ks, vs, ds)
        return out, zero, zero

    def f(*arrays):
        k, v, d = jax.tree.unflatten(treedef, [arrays[i] for i in index])
        korig, vs, ds = _sort_carry(k, v, d)
        ks = tuple(jnp.where(vs, x, _sentinel(x.dtype)) for x in key_leaves(korig))
        with _scope(EXCHANGE):
            # regular sampling: the valid rows (which sort first) at quantiles
            # 0, 1/p, …, (p-1)/p — sampling the padding's sentinels would drag
            # pivots to the top of the key range and starve the last executors
            n_valid = v.sum(dtype=jnp.int32)
            q, r = jnp.divmod(n_valid, p)
            j = jnp.arange(p, dtype=jnp.int32)
            idx = j * q + (j * r) // p  # j * n_valid // p, no overflow
            samples = [jax.lax.all_gather(x[idx], ctx.axis, tiled=True) for x in ks]
            # the invalid rows, which sort after every valid row, stay home:
            # routed past the last executor, they keep ``dest`` in order
            dest = jnp.where(vs, _route(samples, ks, p), p)
        payload = {"k": korig, "valid": vs, "data": ds}
        out, overflow, fill = _pack_sorted(dest, payload, n_valid, ctx.axis, p, C)
        with _scope(MERGE):
            rk, rv, rd = _sort_carry(out["k"], out["valid"], out["data"])
        with _scope(POST):
            out = post(rk, rv, rd)
        return (
            out,
            jax.lax.psum(overflow, ctx.axis),
            jax.lax.pmax(fill, ctx.axis),
        )

    # each distinct array enters the shard_map once, so a key that is also a
    # leaf of the data stays one operand of the sorts inside
    leaves, treedef = jax.tree.flatten((keys, valid, data))
    uniq, index = _distinct(leaves)
    fn = compat.shard_map(
        f,
        mesh=ctx.mesh,
        in_specs=(P(ctx.axis),) * len(uniq),
        out_specs=(P(ctx.axis), P(), P()),
    )
    return fn(*uniq)


def hash_stage(ctx: IContext, keys, valid, data, C: int, post=None):
    """One fused wide hash-exchange stage (partitionBy / reduce routing), no
    host syncs. Same contract as ``sort_stage``; equal keys land on one
    executor but arrive unsorted."""
    post = post or _passthrough
    p = ctx.executors
    zero = jnp.zeros((), jnp.int32)
    if p == 1:
        with _scope(POST):
            return post(keys, valid, data), zero, zero

    def f(k, v, d):
        with _scope(EXCHANGE):
            dest = (_hash_u32(k) % jnp.uint32(p)).astype(jnp.int32)
            dest = jnp.where(v, dest, p - 1)  # park invalid rows anywhere stable
        payload = {"k": k, "valid": v, "data": d}
        out, overflow, fill = _pack_exchange(dest, payload, ctx.axis, p, C)
        with _scope(POST):
            out = post(out["k"], out["valid"], out["data"])
        return (
            out,
            jax.lax.psum(overflow, ctx.axis),
            jax.lax.pmax(fill, ctx.axis),
        )

    fn = compat.shard_map(
        f,
        mesh=ctx.mesh,
        in_specs=(P(ctx.axis), P(ctx.axis), P(ctx.axis)),
        out_specs=(P(ctx.axis), P(), P()),
    )
    return fn(keys, valid, data)


def join_stage(ctx: IContext, lk, lvalid, lvals, rk, rvalid, rvals,
               Cl: int, Cr: int, M: int):
    """Both-side hash exchange + local sort-merge join in ONE wide stage.

    Returns ``(rows, ok, exch_overflow, lfill, rfill, fan_overflow)`` — four
    replicated int32 scalars fetched by the caller in a single deferred sync:
    exchange overflow retries with capacities sized from the fills; fan-out
    overflow retries with a doubled per-key match bound M.
    """
    p = ctx.executors
    zero = jnp.zeros((), jnp.int32)
    if p == 1:
        rows, ok, fovf = local_join(lk, lvalid, lvals, rk, rvalid, rvals, M)
        return rows, ok, zero, zero, zero, fovf.astype(jnp.int32)

    def f(lk_, lv_, ld_, rk_, rv_, rd_):
        with _scope(EXCHANGE):
            ldest = jnp.where(lv_, (_hash_u32(lk_) % jnp.uint32(p)).astype(jnp.int32), p - 1)
            rdest = jnp.where(rv_, (_hash_u32(rk_) % jnp.uint32(p)).astype(jnp.int32), p - 1)
        lout, lovf, lfill = _pack_exchange(
            ldest, {"k": lk_, "valid": lv_, "data": ld_}, ctx.axis, p, Cl)
        rout, rovf, rfill = _pack_exchange(
            rdest, {"k": rk_, "valid": rv_, "data": rd_}, ctx.axis, p, Cr)
        rows, ok, fovf = local_join(
            lout["k"], lout["valid"], lout["data"],
            rout["k"], rout["valid"], rout["data"], M)
        return (
            rows,
            ok,
            jax.lax.psum(lovf + rovf, ctx.axis),
            jax.lax.pmax(lfill, ctx.axis),
            jax.lax.pmax(rfill, ctx.axis),
            jax.lax.psum(fovf.astype(jnp.int32), ctx.axis),
        )

    fn = compat.shard_map(
        f,
        mesh=ctx.mesh,
        in_specs=(P(ctx.axis),) * 6,
        out_specs=(P(ctx.axis), P(ctx.axis), P(), P(), P(), P()),
    )
    return fn(lk, lvalid, lvals, rk, rvalid, rvals)


# ---------------------------------------------------------------------------
# sorted segmented reduce (jnp oracle of kernels/segment_reduce)
# ---------------------------------------------------------------------------


def segment_heads(keys, valid):
    prev = jnp.concatenate([keys[:1], keys[:-1]])
    first = jnp.arange(keys.shape[0]) == 0
    return valid & (first | (keys != prev) | ~jnp.concatenate([valid[:1], valid[:-1]]))


#: rows per step of ``chunked_scan``
SCAN_CHUNK = 1 << 14


def chunked_scan(comb, elems, chunk: int = SCAN_CHUNK):
    """Inclusive ``associative_scan`` along axis 0 of a pytree of arrays.

    XLA:TPU's compile time and memory grow with the length of a scan (a
    2^20-row ``associative_scan`` or ``cummin`` takes minutes to compile
    for v5e, and a 2^27-row stage crashed the compiler). So rows longer
    than ``chunk`` are scanned one chunk per ``lax.scan`` step, with the
    running total carried into the next chunk: one chunk-sized program at
    any length. ``comb`` must broadcast a ``(1, ...)`` carry against
    ``(chunk, ...)``. Exact for associative-exact data (ints, min, max);
    float sums associate differently from a plain scan.
    """
    n = jax.tree.leaves(elems)[0].shape[0]
    if n <= chunk:
        return jax.lax.associative_scan(comb, elems)
    R = -(-n // chunk)

    def chunks(x):  # (n, ...) → (R, chunk, ...); the padding trails every row
        x = jnp.pad(x, [(0, R * chunk - n)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape((R, chunk) + x.shape[1:])

    xs = jax.tree.map(chunks, elems)
    first = jax.lax.associative_scan(comb, jax.tree.map(lambda x: x[0], xs))

    def step(carry, x):
        s = comb(jax.tree.map(lambda c: c[None], carry),
                 jax.lax.associative_scan(comb, x))
        return jax.tree.map(lambda y: y[-1], s), s

    _, rest = jax.lax.scan(step, jax.tree.map(lambda y: y[-1], first),
                           jax.tree.map(lambda x: x[1:], xs))
    return jax.tree.map(
        lambda a, b: jnp.concatenate([a, b.reshape((-1,) + b.shape[2:])])[:n],
        first, rest)


def segment_tails(keys, valid):
    """Last valid row of every equal-key run: the next row holds another
    key or is invalid, or there is none. Computed from the keys, not from
    the heads, so XLA:TPU fuses it into the output mask and keeps no
    boundary array alive beside the scan."""
    ends = (keys[1:] != keys[:-1]) | ~valid[1:]
    return valid & jnp.concatenate([ends, jnp.ones((min(keys.shape[0], 1),), bool)])


def segmented_reduce(keys, valid, values, fn, identity):
    """Reduce consecutive equal-key runs (keys must be sorted, invalid at
    arbitrary positions). Returns ``(tails, scanned)``: the inclusive
    segmented scan, whose value at a segment's last row (``tails``) is the
    segment's total; other valid rows hold a partial total and invalid
    rows the identity. Keeping the last row needs no gather.

    fn: associative binary row fn (pytrees); identity: row pytree.
    """
    bounds = segment_heads(keys, valid) | ~valid

    vals = jax.tree.map(
        lambda x, i: jnp.where(
            valid.reshape((-1,) + (1,) * (x.ndim - 1)), x, jnp.asarray(i, x.dtype)
        ),
        values,
        identity,
    )

    def comb(a, b):
        va, ha = a
        vb, hb = b
        merged = fn(va, vb)
        v = jax.tree.map(
            lambda m, y: jnp.where(hb.reshape(hb.shape + (1,) * (y.ndim - hb.ndim)), y, m),
            merged,
            vb,
        )
        return (v, ha | hb)

    scanned, _ = chunked_scan(comb, (vals, bounds))
    return segment_tails(keys, valid), scanned


# ---------------------------------------------------------------------------
# post hooks: the sort→heads→reduce fusion targets (run per shard inside the
# wide stage — valid because equal keys never span shards)
# ---------------------------------------------------------------------------


def heads_post(keys, valid, data):
    """distinct: keep the first row of every equal-key run."""
    return data, segment_heads(keys, valid)


def make_reduce_post(fn, identity):
    """reduceByKey: segmented reduce fused into the sort stage. Each key's
    row is its segment's last, which holds the total; the key there is the
    head's, so the rows stay one per key and in key order."""

    def post(keys, valid, data):
        tails, red = segmented_reduce(keys, valid, data["value"], fn, identity)
        return {"key": data["key"], "value": red}, tails

    return post


def make_reduce_post_kernel(op: str, identity, block: int, interpret: bool):
    """reduceByKey on the kernel tier (docs/kernels.md): the Pallas
    segmented scan replaces ``segmented_reduce``, fused into the same wide
    stage, and keeps the same rows. Only built for values the registry
    recognized as a single supported-dtype leaf with a builtin op —
    bit-identical to ``make_reduce_post`` for associative-exact data."""
    from repro.kernels.segment_reduce.ops import segment_totals

    def post(keys, valid, data):
        leaves, treedef = jax.tree_util.tree_flatten(data["value"])
        ident = jax.tree_util.tree_leaves(identity)[0]
        tails, red = segment_totals(keys, valid, leaves[0], op=op,
                                    identity=ident, block=block,
                                    interpret=interpret)
        value = jax.tree_util.tree_unflatten(treedef, [red])
        return {"key": data["key"], "value": value}, tails

    return post


def make_group_post(G: int):
    """groupByKey: G-bounded gather of each key run, fused into the sort
    stage. Rows (key, {items[G], mask[G], count}) at segment heads."""

    def post(keys, valid, data):
        heads = segment_heads(keys, valid)
        n = keys.shape[0]
        idx = jnp.arange(n)
        raw = idx[:, None] + jnp.arange(G)[None, :]
        gidx = jnp.clip(raw, 0, n - 1)
        same = (keys[gidx] == keys[:, None]) & valid[gidx] & (raw < n)
        vals = jax.tree.map(lambda x: x[gidx], data["value"])
        counts = same.sum(-1)
        return (
            {"key": data["key"], "value": {"items": vals, "mask": same, "count": counts}},
            heads,
        )

    return post


# ---------------------------------------------------------------------------
# local (post-exchange) join with bounded fan-out
# ---------------------------------------------------------------------------


def local_join(lk, lvalid, lvals, rk, rvalid, rvals, max_matches: int):
    """Sort-merge join on one shard. Returns dict rows of capacity n_left·M."""
    with _scope(MERGE):
        return _local_join(lk, lvalid, lvals, rk, rvalid, rvals, max_matches)


def _local_join(lk, lvalid, lvals, rk, rvalid, rvals, max_matches: int):
    big = _sentinel(rk.dtype)
    rs = jnp.where(rvalid, rk, big)
    order = _argsort(rs)
    rs, rv, rvalid_s = _permute(order, rs, rvals, rvalid)

    lo = jnp.searchsorted(rs, lk, side="left")
    hi = jnp.searchsorted(rs, lk, side="right")
    M = max_matches
    j = lo[:, None] + jnp.arange(M)[None, :]  # (n_left, M)
    ok = (j < hi[:, None]) & lvalid[:, None]
    jc = jnp.clip(j, 0, rs.shape[0] - 1)
    ok &= rvalid_s[jc]
    out_overflow = jnp.maximum(hi - lo - M, 0).sum()

    n = lk.shape[0]

    def expand_l(x):
        return jnp.repeat(x, M, axis=0)

    def take_r(x):
        return x[jc].reshape(n * M, *x.shape[1:])

    rows = {
        "key": expand_l(lk),
        "value": (jax.tree.map(expand_l, lvals), jax.tree.map(take_r, rv)),
    }
    return rows, ok.reshape(n * M), out_overflow
