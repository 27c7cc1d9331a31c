"""Pallas kernels: commit refreshed rows into cache buffers in place.

The Upd module of Algorithm 1 (K/V/H^c/proxy cache writes).  All buffers
are aliased input->output (no copy); the grid walks (batch,
index-block) steps, row indices are scalar-prefetched into SMEM, and row
payloads stream through VMEM.  Every store into the cache is a
``pltpu.make_async_copy`` DMA: a cache ref in ``pl.ANY`` memory cannot
be written with a vector store.

A DMA moves whole TPU tiles.  How a row reaches HBM depends on where the
row sits in the buffer's layout:

  * direct — the buffer has two or more feature dims (K/V:
    [B, N, KVH, hd]): a row is a leading-dim slice made of whole tiles,
    so each row is ONE DMA from the VMEM payload block.
  * tile read-modify-write — the row dim is the second-minor (tiled)
    dim (H^c [B, N, d], proxies [B, N, r], scales [B, N]): the aligned
    slab of ``sublane_tile(dtype)`` rows that holds the row is DMA'd
    into VMEM, the row is selected in (f32 exact round-trip), and the
    slab is DMA'd back.  Consecutive rows in one slab (sorted
    selections) share one load and one store; an unsorted index order
    reloads the slab, so any order is correct.

``scatter_update_multi`` commits an arbitrary set of cache buffers (K,
V, H, proxy, int8 scales — any mix of dtypes/row widths) for a whole
[B, N, ·] cache slice in ONE aliased call, so a layer's Phase-2 commit
(k+v+scales) and Phase-3 commit (h+scale+proxy) each cost a single
kernel launch instead of one scatter per buffer.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.proxy_score import sublane_tile

# DMAs kept in flight at once by the page-copy and direct-row loops.
_DMA_GROUP = 8


def _direct(shape) -> bool:
    """Whether rows of a [lead, rows, *feat] buffer are whole tiles."""
    return len(shape) >= 4


def _commit_rows(locate: Callable, idx_ref, base, n_rows: int, r_ref,
                 o_ref, tiles, sem, *, tile: int):
    """Write payload rows ``r_ref[0, j]`` (j < n_rows) into ``o_ref``.

    ``locate(ri) -> (ok, lead, row)`` maps a logical row index to the
    buffer address ``o_ref[lead, row]``; rows with ``ok`` False drop.
    ``tiles`` is None for direct buffers, else a VMEM [tile, f] slab."""
    if tiles is None:
        group = math.gcd(_DMA_GROUP, n_rows)

        def direct_group(g, carry):
            copies = []
            for t in range(group):
                j = g * group + t
                ok, lead, row = locate(idx_ref[base + j])
                cp = pltpu.make_async_copy(r_ref.at[0, j],
                                           o_ref.at[lead, row], sem)
                copies.append((ok, cp))

                @pl.when(ok)
                def _start(cp=cp):
                    cp.start()

            for ok, cp in copies:
                @pl.when(ok)
                def _wait(cp=cp):
                    cp.wait()

            return carry

        jax.lax.fori_loop(0, n_rows // group, direct_group, 0)
        return

    def slab(lead, start):
        return pltpu.make_async_copy(
            o_ref.at[lead, pl.ds(pl.multiple_of(start, tile), tile)],
            tiles, sem)

    def swap_in(lead, start, cur):
        """Write back the loaded slab ``cur`` (if any), load slab
        (lead, start)."""
        @pl.when(cur[0] >= 0)
        def _flush():
            cp = pltpu.make_async_copy(
                tiles, o_ref.at[cur[0], pl.ds(
                    pl.multiple_of(cur[1], tile), tile)], sem)
            cp.start()
            cp.wait()

        cp = slab(lead, start)
        cp.start()
        cp.wait()

    def body(j, cur):
        ok, lead, row = locate(idx_ref[base + j])
        start = (row // tile) * tile
        fresh = jnp.logical_or(cur[0] != lead, cur[1] != start)

        @pl.when(jnp.logical_and(ok, fresh))
        def _load():
            swap_in(lead, start, cur)

        @pl.when(ok)
        def _update():
            t32 = tiles[...].astype(jnp.float32)
            sel = jax.lax.broadcasted_iota(jnp.int32, t32.shape, 0) \
                == row - start
            new = r_ref[0, pl.ds(j, 1), :]                # [1, f] f32
            tiles[...] = jnp.where(sel, new, t32).astype(tiles.dtype)

        return (jnp.where(ok, lead, cur[0]), jnp.where(ok, start, cur[1]))

    last = jax.lax.fori_loop(0, n_rows, body,
                             (jnp.int32(-1), jnp.int32(0)))

    @pl.when(last[0] >= 0)
    def _final_flush():
        cp = pltpu.make_async_copy(
            tiles, o_ref.at[last[0], pl.ds(
                pl.multiple_of(last[1], tile), tile)], sem)
        cp.start()
        cp.wait()


def _scatter_kernel(*refs, paged: bool, n_bufs: int, bk: int, kp: int,
                    n: int, page: int, n_log: int, tiles_of):
    """Shared body of the dense and paged row commits.  Dense buffers
    [B, N, ...] hold row ri of batch row b at [b, ri]; paged arenas
    [P, page, ...] resolve it through the page table to
    [pt[b, ri // page], ri % page]."""
    idx_ref, pt_ref = refs[0], (refs[1] if paged else None)
    refs = refs[2 if paged else 1:]
    rows_refs = refs[:n_bufs]
    o_refs = refs[2 * n_bufs:3 * n_bufs]      # cache refs aliased
    scratch = refs[3 * n_bufs:]
    sem = scratch[-1]
    bb = pl.program_id(0)
    base = bb * kp + pl.program_id(1) * bk

    if pt_ref is None:
        def locate(ri):
            return jnp.logical_and(ri >= 0, ri < n), bb, ri
    else:
        def locate(ri):
            lpage = ri // page
            pid = pt_ref[bb * n_log + jnp.clip(lpage, 0, n_log - 1)]
            ok = jnp.logical_and(jnp.logical_and(ri >= 0, lpage < n_log),
                                 pid > 0)       # page 0 = zero page
            return ok, pid, ri % page

    slabs = iter(scratch[:-1])
    for r_ref, o_ref, tile in zip(rows_refs, o_refs, tiles_of):
        _commit_rows(locate, idx_ref, base, bk, r_ref, o_ref,
                     None if tile is None else next(slabs), sem,
                     tile=tile or 1)


def _scatter_call(bufs, idx, rows, pt, *, rows_per_lead: int,
                  block_k: int, interpret: bool, name: str):
    """One aliased pallas_call committing rows [B, k, *feat] into every
    buffer of ``bufs`` ([lead, rows_per_lead, *feat] each); ``name`` is
    the kernel's name in the compiled program (its public caller's)."""
    b, k = idx.shape
    n_log = 0 if pt is None else pt.shape[1]
    n = rows_per_lead if pt is None else n_log * rows_per_lead
    bk = min(block_k, k)
    pad = (-k) % bk
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)), constant_values=n)
        rows = [jnp.pad(r, ((0, 0), (0, pad)) + ((0, 0),) * (r.ndim - 2))
                for r in rows]
    kp = idx.shape[1]
    m = len(bufs)
    tiles_of, payloads, scratch = [], [], []
    for buf, r in zip(bufs, rows):
        r = r.astype(buf.dtype)
        if _direct(buf.shape):
            tiles_of.append(None)
            payloads.append(r)
        else:
            tile = math.gcd(sublane_tile(buf.dtype), rows_per_lead)
            tiles_of.append(tile)
            # f32 payload: a dynamic one-row VMEM read is only
            # addressable on 32-bit data; the cast back is exact
            payloads.append(r.astype(jnp.float32))
            scratch.append(pltpu.VMEM((tile,) + buf.shape[2:], buf.dtype))
    scratch.append(pltpu.SemaphoreType.DMA(()))

    def row_spec(r):
        tail = (0,) * (r.ndim - 2)
        return pl.BlockSpec((1, bk) + r.shape[2:],
                            lambda bb, i, *_: (bb, i) + tail)

    prefetch = [idx.astype(jnp.int32).reshape(-1)]
    if pt is not None:
        prefetch.append(pt.astype(jnp.int32).reshape(-1))
    n_pre = len(prefetch)
    outs = pl.pallas_call(
        functools.partial(_scatter_kernel, paged=pt is not None, n_bufs=m,
                          bk=bk, kp=kp, n=n, page=rows_per_lead,
                          n_log=n_log, tiles_of=tuple(tiles_of)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre,
            grid=(b, kp // bk),
            in_specs=([row_spec(r) for r in payloads]
                      + [pl.BlockSpec(memory_space=pl.ANY)] * m),
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * m,
            scratch_shapes=scratch,
        ),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in bufs],
        input_output_aliases={n_pre + m + j: j for j in range(m)},
        interpret=interpret,
        name=name,
    )(*prefetch, *payloads, *bufs)
    return tuple(outs)


def _rows3(a: jax.Array) -> jax.Array:
    """[B, N] (per-row scales) -> [B, N, 1]; wider buffers unchanged."""
    return a[..., None] if a.ndim == 2 else a


def scatter_update_multi(caches: Sequence[jax.Array], idx: jax.Array,
                         rows: Sequence[jax.Array], *, block_k: int = 128,
                         interpret: bool = False
                         ) -> Tuple[jax.Array, ...]:
    """caches[i]: [B, N, ...]; idx: [B, k] int32 (any order; entries
    outside [0, N) are dropped; SORTED indices share slab loads);
    rows[i]: [B, k, ...] payloads.  Returns the updated caches (all
    buffers committed in one aliased call)."""
    shapes = [c.shape for c in caches]
    outs = _scatter_call([_rows3(c) for c in caches], idx,
                         [_rows3(r) for r in rows], None,
                         rows_per_lead=caches[0].shape[1],
                         block_k=block_k, interpret=interpret,
                         name="scatter_update_multi")
    return tuple(o.reshape(s) for o, s in zip(outs, shapes))


def scatter_rows_paged(arena: jax.Array, pt: jax.Array, idx: jax.Array,
                       rows: jax.Array, *, block_k: int = 128,
                       interpret: bool = False) -> jax.Array:
    """Row-granular paged commit: arena is ONE layer's pooled buffer
    [P, page, ...feat] SHARED by all batch rows (each row's page-table
    row maps into disjoint pages); idx [B, k] logical canvas rows
    (sorted common; out-of-range/zero-page rows dropped); rows
    [B, k, ...feat].  Returns the updated arena (aliased
    input->output)."""
    (out,) = _scatter_call([_rows3(arena)], idx, [_rows3(rows)], pt,
                           rows_per_lead=arena.shape[1], block_k=block_k,
                           interpret=interpret, name="scatter_rows_paged")
    return out.reshape(arena.shape)


def scatter_update(cache: jax.Array, idx: jax.Array, rows: jax.Array,
                   *, block_k: int = 128,
                   interpret: bool = False) -> jax.Array:
    """cache: [N, d]; idx: [k] int32; rows: [k, d]. Returns updated cache.

    Single-buffer unbatched form of ``scatter_update_multi`` (the cache
    buffer is aliased input->output — in-place on TPU when the caller's
    buffer is donatable)."""
    (out,) = _scatter_call([cache[None]], idx[None], [rows[None]], None,
                           rows_per_lead=cache.shape[0], block_k=block_k,
                           interpret=interpret, name="scatter_update")
    return out[0]


# ---------------------------------------------------------------------------
# Paged views (DESIGN.md §5): cache rows live in a pooled arena of
# fixed-size pages; logical canvas row n of batch row b resolves to
# physical row  pt[b, n // page] * page + n % page.  Page ids are
# scalar-prefetched into SMEM and every page moves as ONE HBM->HBM DMA
# (pages are contiguous in the arena by construction; with page a
# multiple of the dtype's sublane tile the canvas-side slice is
# tile-aligned too).  Physical page 0 is the pool's reserved zero page —
# never written, so logical pages past a request's ``kv_len`` can all
# alias it.
# ---------------------------------------------------------------------------


def _page_copies(pt_ref, n_log: int, copy_for, *, skip_zero: bool):
    """Issue ``copy_for(j, pid)`` for every logical page j of the
    current batch row, ``_DMA_GROUP`` in flight at a time."""
    bb = pl.program_id(1)
    group = math.gcd(_DMA_GROUP, n_log)

    def run(g, carry):
        copies = []
        for t in range(group):
            j = g * group + t
            pid = pt_ref[bb * n_log + j]
            copies.append((pid > 0, copy_for(j, pid)))
        for step in ("start", "wait"):
            for ok, cp in copies:
                if skip_zero:
                    pl.when(ok)(getattr(cp, step))
                else:
                    getattr(cp, step)()

        return carry

    jax.lax.fori_loop(0, n_log // group, run, 0)


def _gather_pages_kernel(pt_ref, a_ref, o_ref, sem, *, n_log: int,
                         page: int):
    ll, bb = pl.program_id(0), pl.program_id(1)
    _page_copies(pt_ref, n_log, lambda j, pid: pltpu.make_async_copy(
        a_ref.at[ll, pid], o_ref.at[ll, bb, pl.ds(j * page, page)], sem),
        skip_zero=False)


def gather_pages(arena: jax.Array, pt: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """arena: [L, P, page, ...feat]; pt: [B, n_log] int32 page table.
    Returns the dense view [L, B, n_log*page, ...feat] — one contiguous
    HBM->HBM DMA per (layer, batch row, logical page)."""
    shape = arena.shape
    l, page = shape[0], shape[2]
    b, n_log = pt.shape
    return pl.pallas_call(
        functools.partial(_gather_pages_kernel, n_log=n_log, page=page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(l, b),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((l, b, n_log * page) + shape[3:],
                                       arena.dtype),
        interpret=interpret,
        name="gather_pages",
    )(pt.astype(jnp.int32).reshape(-1), arena)


def _scatter_pages_kernel(pt_ref, d_ref, a_ref, o_ref, sem, *, n_log: int,
                          page: int):
    del a_ref                                # aliased input; only written
    ll, bb = pl.program_id(0), pl.program_id(1)
    _page_copies(pt_ref, n_log, lambda j, pid: pltpu.make_async_copy(
        d_ref.at[ll, bb, pl.ds(j * page, page)], o_ref.at[ll, pid], sem),
        skip_zero=True)                      # page 0 = reserved zero page


def scatter_pages(arena: jax.Array, pt: jax.Array, dense: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """Inverse of :func:`gather_pages`: write the dense view back through
    the page table (arena aliased input->output; writes to the zero page
    are dropped, so tail pages of short rows stay zero)."""
    shape = arena.shape
    l, page = shape[0], shape[2]
    b, n_log = pt.shape
    dense = dense.reshape((l, b, n_log * page) + shape[3:])
    return pl.pallas_call(
        functools.partial(_scatter_pages_kernel, n_log=n_log, page=page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(l, b),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(shape, arena.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="scatter_pages",
    )(pt.astype(jnp.int32).reshape(-1), dense.astype(arena.dtype), arena)
