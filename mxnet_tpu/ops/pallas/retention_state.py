"""The retention mixer's decode-step state update over the pools as they lie.

Degree-2 power retention keeps, a KV head and sequence, ``S`` (F x d) and
``z`` (F), ``F = d (d + 1) / 2`` features of a head of ``d``
(``models.hybrid``).  A decode step reads each live lane's entry for the
output, ``num = phi(q)^T S`` and ``den = phi(q) . z`` of the head's query
heads, and writes ``S <- g S + phi(k) v^T``, ``z <- g z + phi(k)``.
:func:`decode_state_step` does both in one pass, a lane a grid step:

- the pools stay where they are (``memory_space=pl.ANY``) and are written
  in place (``input_output_aliases``): a lane's S is copied into VMEM in
  row blocks through a ring of buffers (two blocks read ahead, one written
  back behind), updated there and copied back to where it came from, so
  each live entry is read once and written once;
- a lane that is not active starts no copy and returns zeros: the scratch
  page and every page no active lane owns are neither read nor written;
- a lane that starts at position 0 (``fresh``) reads no S: its old
  contribution is zero and it writes ``phi(k) v^T``.

A pool half holds ``H2`` KV heads, ``rows = H2 F`` rows of ``d``: the walk
runs over those rows flat, in blocks of ``R`` rows (a multiple of 128 on
the chip, so that a block's features are whole lane tiles of the query rows
and of z) of at most one head boundary: a block's query rows are split
there into the two heads' (zeros outside each), and one product ``(2 Gp,
R) x (R, d)`` gives both heads' parts.  ``z`` comes in with the lane's
other inputs and goes back after its last block, the page's z of every
layer at once: on the chip the pools hold z page-major (the device's
layout of ``(L, P, n)`` is ``{2,0,1:T(4,128)}``), so a page's rows of all
layers are whole tiles where one layer's row is not.

The product runs at ``precision=HIGHEST``, as the XLA form's does (one
bfloat16 pass over the state reads 0.145 of a logit's standard deviation
against a limit of 0.02: PERF.md section 6); the updates are float32 on the
VPU, ``phi(k) v^T`` made as ``v phi(k)^T`` and transposed, so that the
features stay on the lanes as they come.

Selected by :func:`kernel_mode` from what the code sees: a TPU backend and
a float32 state whose rows fill whole tiles; elsewhere the caller keeps its
XLA form.  No environment variable steers it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kernel_mode", "block_rows", "decode_state_step", "last_path"]

#: Which form the last decode step traced took: "pallas" |
#: "pallas-interpret" | "xla" (set by the caller where it keeps the XLA
#: form).
last_path = None

_HI = jax.lax.Precision.HIGHEST
_MAX_BLOCK = 768        # rows of a block: 384 KB of a state of 128 lanes
_RING = 4               # blocks in VMEM: two read ahead, one written back


def kernel_mode(S, z_parts):
    """"compiled" on a TPU backend where the layer's S ``(P, H2, F, d)``
    is float32 with ``d`` a multiple of the 128 lanes, a pool half's rows
    (``H2 F``) a whole number of 128-row blocks no longer than a head, and
    each part of z whole lane tiles; None elsewhere (the caller's XLA
    form)."""
    _, H2, F, d = S.shape
    if (jax.default_backend() != "tpu" or S.dtype != jnp.float32
            or d % 128 or (H2 * F) % 128 or F < 128
            or any(z.shape[-1] % 128 for z in z_parts)):
        return None
    return "compiled"


def block_rows(rows, F, aligned=True):
    """Rows of a block: the largest divisor of ``rows`` up to
    ``_MAX_BLOCK`` and ``F`` (so that a block meets at most two heads), a
    multiple of 128 where ``aligned``."""
    step = 128 if aligned else 1
    fits = [r for r in range(step, min(rows, F, _MAX_BLOCK) + 1, step)
            if rows % r == 0]
    if not fits:
        raise ValueError("no block of %d rows a head of %d" % (rows, F))
    return fits[-1]


def _kernel(li_ref, page_ref, active_ref, fresh_ref, g_ref, x_hbm, vt_hbm,
            sk_hbm, sv_hbm, *refs, parts, G, H2, F, R):
    """One lane a grid step (see the module's docstring).  ``refs``: the
    two pools' z parts, then the outputs (the S halves, the z parts, num,
    den), then the scratch."""
    n = parts
    z_in = refs[:n], refs[n:2 * n]
    s_out = refs[2 * n:2 * n + 2]
    z_out = refs[2 * n + 2:3 * n + 2], refs[3 * n + 2:4 * n + 2]
    num_ref, den_ref = refs[4 * n + 2:4 * n + 4]
    xbuf, vtbuf, vcol, zbuf, ring, sem_in, sem_out, sem_lane = refs[
        4 * n + 4:]
    s_in = sk_hbm, sv_hbm
    b = pl.program_id(0)
    rows = H2 * F
    nb = rows // R                      # blocks a pool half
    T = 2 * nb                          # blocks a lane
    W = rows // n                       # floats of a part of z
    ahead = _RING - 2
    Gp = xbuf.shape[1]
    num_ref[...] = jnp.zeros(num_ref.shape, num_ref.dtype)
    den_ref[...] = jnp.zeros(den_ref.shape, den_ref.dtype)

    @pl.when(active_ref[b] != 0)
    def _():
        li, page = li_ref[0], page_ref[b]
        fresh = fresh_ref[b] != 0
        reads = jnp.logical_not(fresh)

        def block_copy(t, slot, inward):
            """Start the copy of block ``t`` of the lane's S into (or out
            of) ring slot ``slot``, in the pool half it lies in."""
            half, r0 = t // nb, (t % nb) * R
            for h in range(2):
                @pl.when(half == h)
                def _(h=h):
                    there = (s_in if inward else s_out)[h].at[
                        page, pl.ds(r0, R)]
                    if inward:
                        pltpu.make_async_copy(there, ring.at[slot],
                                              sem_in.at[slot]).start()
                    else:
                        pltpu.make_async_copy(ring.at[slot], there,
                                              sem_out.at[slot]).start()

        def wait(slot, inward):
            there = s_in[0].at[page, pl.ds(0, R)]
            if inward:
                pltpu.make_async_copy(there, ring.at[slot],
                                      sem_in.at[slot]).wait()
            else:
                pltpu.make_async_copy(ring.at[slot], there,
                                      sem_out.at[slot]).wait()

        def z_copies(inward):
            """The page's z of every layer, a part of a pool a copy."""
            copies = []
            for h in range(2):
                for p in range(n):
                    there = (z_in if inward else z_out)[h][p].at[page]
                    here = zbuf.at[h, :, pl.ds(p * W, W)]
                    copies.append(pltpu.make_async_copy(
                        *((there, here) if inward else (here, there)),
                        sem_lane.at[2]))
            return copies

        lane_in = [pltpu.make_async_copy(x_hbm.at[b], xbuf, sem_lane.at[0]),
                   pltpu.make_async_copy(vt_hbm.at[b], vtbuf,
                                         sem_lane.at[1])] + z_copies(True)
        for c in lane_in:
            c.start()

        @pl.when(reads)
        def _():
            for t in range(min(ahead, T)):
                block_copy(t, t % _RING, True)
        for c in lane_in:
            c.wait()

        # each KV head's v as a column (d, 1): a masked sum over the lanes
        at = jax.lax.broadcasted_iota(jnp.int32, vtbuf.shape[1:], 1)
        for h in range(2):
            for j in range(H2):
                vcol[h * H2 + j] = jnp.sum(jnp.where(at == j, vtbuf[h], 0.0),
                                           axis=1, keepdims=True)

        def block(t, carry):
            slot = t % _RING

            @pl.when(reads)
            def _():
                wait(slot, True)

            @pl.when(t + ahead < T)
            def _():
                nxt = t + ahead

                @pl.when(nxt >= _RING)
                def _():
                    wait(nxt % _RING, False)    # the slot's last write-back

                @pl.when(reads)
                def _():
                    block_copy(nxt, nxt % _RING, True)

            half = t // nb
            r0 = pl.multiple_of((t % nb) * R, 128 if R % 128 == 0 else R)
            lo = r0 // F                            # the block's heads:
            hi = jnp.minimum(lo + 1, H2 - 1)        # rows from `edge` on
            edge = (lo + 1) * F - r0                # are head `hi`'s
            xb = xbuf[half, :, pl.ds(r0, R)]                     # (Gp, R)
            upper = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1) >= edge
            lhs = jnp.concatenate([jnp.where(upper, 0.0, xb),
                                   jnp.where(upper, xb, 0.0)])  # (2Gp, R)
            old = jnp.where(fresh, 0.0, ring[slot])              # (R, d)
            z_old = jnp.where(fresh, 0.0,
                              zbuf[half, pl.ds(li, 1), pl.ds(r0, R)])
            got = jax.lax.dot_general(
                lhs, old, (((1,), (0,)), ((), ())), precision=_HI,
                preferred_element_type=jnp.float32)              # (2Gp, d)
            zs = jnp.sum(lhs * z_old, axis=1, keepdims=True)     # (2Gp, 1)
            num_ref[half, lo] += got[:Gp]
            num_ref[half, hi] += got[Gp:]
            den_ref[half, lo] += jnp.broadcast_to(zs[:Gp], (Gp, 128))
            den_ref[half, hi] += jnp.broadcast_to(zs[Gp:], (Gp, 128))
            # the update, each row by its own head's g and v
            g_lo = g_ref[b, half * H2 + lo]
            g_hi = g_ref[b, half * H2 + hi]
            pk = xb[G:G + 1]                                     # (1, R)
            zbuf[half, pl.ds(li, 1), pl.ds(r0, R)] = (
                jnp.where(upper, g_hi, g_lo) * z_old + pk)
            v = jnp.where(upper, vcol[half * H2 + hi],
                          vcol[half * H2 + lo])                  # (d, R)
            below = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) >= edge
            ring[slot] = jnp.where(below, g_hi, g_lo) * old + (v * pk).T
            block_copy(t, slot, False)
            return carry

        jax.lax.fori_loop(0, T, block, 0)
        for t in range(max(0, T - _RING), T):
            wait(t % _RING, False)
        lane_out = z_copies(False)
        for c in lane_out:
            c.start()
        for c in lane_out:
            c.wait()


@functools.partial(jax.jit, static_argnames=("G", "H2", "R", "interpret"))
def _call(li, pages, active, fresh, g, x, vt, S_k, S_v, z_k, z_v, *, G, H2, R,
          interpret):
    """The kernel over (1,) layer index, the lanes' (B,) pages and active
    and fresh flags, g (B, KVH) in SMEM, the lanes' query and key features
    ``x`` (B, 2, Gp, rows), their v ``vt`` (B, 2, d, lanes) with a pool's
    ``H2`` KV heads on the lanes, a layer's two S halves (P, rows, d) and
    the two pools' z parts page-major (P, L, W).  One jitted function, so
    that the layers of a step program share one trace and one lowering to
    Mosaic (traced and lowered a layer at a time, a Pallas call costs every
    process's warm-up again: PERF.md section 6)."""
    B, _, Gp, rows = x.shape
    d = S_k.shape[-1]
    n = len(z_k)
    # the state in place and first among the outputs, so that the
    # kernel's trace event names its shape
    state = (S_k, S_v) + tuple(z_k) + tuple(z_v)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)

    def lane(width):
        return pl.BlockSpec((None, 2, H2, Gp, width),
                            lambda i, *_: (i, 0, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, parts=n, G=G, H2=H2, F=rows // H2, R=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            + [anywhere] * (2 + len(state)),
            out_specs=[anywhere] * len(state) + [lane(d), lane(128)],
            scratch_shapes=[
                pltpu.VMEM((2, Gp, rows), jnp.float32),      # x
                pltpu.VMEM(vt.shape[1:], jnp.float32),       # v
                pltpu.VMEM((2 * H2, d, 1), jnp.float32),     # v, a column a head
                pltpu.VMEM((2, z_k[0].shape[1], rows), jnp.float32),  # z
                pltpu.VMEM((_RING, R, d), jnp.float32),      # S blocks
                pltpu.SemaphoreType.DMA((_RING,)),
                pltpu.SemaphoreType.DMA((_RING,)),
                pltpu.SemaphoreType.DMA((3,))]),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in state]
        + [jax.ShapeDtypeStruct((B, 2, H2, Gp, d), jnp.float32),
           jax.ShapeDtypeStruct((B, 2, H2, Gp, 128), jnp.float32)],
        # operands: 4 scalars, g, x, vt, then the state
        input_output_aliases={7 + i: i for i in range(len(state))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="retention_state_step",
    )(li, pages, active, fresh, g, x, vt, *state)


def decode_state_step(mode, li, pages, active, fresh, g, pq, pk, v, S, z):
    """One layer's decode-step state update and the lanes' reads of it, by
    the kernel in ``mode`` ("compiled" or "interpret").

    ``pq`` (B, KVH, G, F) phi of the lanes' queries, ``pk`` (B, KVH, F)
    phi of their keys, ``v`` (B, KVH, d), ``g`` (B, KVH) the gates (0 where
    ``fresh``); ``pages`` (B,) the page each lane owns, ``active`` and
    ``fresh`` (B,) bool; ``S``: the layer's S of the two pools, (P, KVH /
    2, F, d) each; ``z``: the two pools' z, each a tuple of parts (L, P,
    W), layer ``li`` read and written.  Returns ``num`` (B, KVH, G, d) and
    ``den`` (B, KVH, G) over the state before the write, zero for a lane
    that is not active or is fresh, and the new S pair and z pair."""
    global last_path
    B, KVH, G, F = pq.shape
    H2 = KVH // 2
    Gp = -(-(G + 1) // 8) * 8           # the query rows and phi(k), tiles

    def halves(a):                      # (B, KVH, .., F) -> (B, 2, .., H2 F)
        a = jnp.moveaxis(a.reshape((B, 2, H2) + a.shape[2:]), 2, -2)
        return a.reshape(a.shape[:-2] + (H2 * F,))
    x = jnp.concatenate([halves(pq), halves(pk[:, :, None])], axis=2)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, Gp - G - 1), (0, 0)))
    vt = v.reshape(B, 2, H2, -1).swapaxes(2, 3)              # (B, 2, d, H2)
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, 0), (0, -H2 % 128)))
    as_i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    out = _call(
        as_i32(li).reshape(1), as_i32(pages), as_i32(active), as_i32(fresh),
        g.astype(jnp.float32), x, vt,
        *(s.reshape(s.shape[0], -1, s.shape[-1]) for s in S),   # views
        # z page-major: on the chip the pools' own layout, so a view
        *(tuple(part.swapaxes(0, 1) for part in pool) for pool in z),
        G=G, H2=H2, R=block_rows(H2 * F, F, aligned=mode == "compiled"),
        interpret=mode == "interpret")
    last_path = "pallas" if mode == "compiled" else "pallas-interpret"
    n = len(z[0])
    num = out[-2][:, :, :, :G].reshape(B, KVH, G, -1)
    den = out[-1][:, :, :, :G, 0].reshape(B, KVH, G)
    z = tuple(tuple(a.swapaxes(0, 1) for a in out[2 + i * n:2 + (i + 1) * n])
              for i in range(2))
    return num, den, (out[0].reshape(S[0].shape),
                      out[1].reshape(S[1].shape)), z
