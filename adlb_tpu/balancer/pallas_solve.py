"""Pallas TPU kernel for the greedy assignment inner loop.

The batched global solve (see :mod:`adlb_tpu.balancer.solve`) is this
framework's hot op — the TPU-native replacement for the reference's
per-Reserve O(|wq|·16) linear scans (reference ``src/xq.c:190-247``) run
once per balancer round over every server's queue at once.

Kernel design (SURVEY §7 stage 5, "Pallas for the auction inner loop"):

* An XLA pre-pass folds priority ordering, padding, requester validity and
  the type mask into one ``[NT, NRp]`` int32 *compatibility matrix*
  (``compat[k, r] = 1`` iff the k-th task in descending-priority order may
  go to requester ``r``) — pure vectorized gather work XLA fuses well.
* The Pallas kernel then runs the inherently sequential greedy sweep with
  the live state resident in VMEM: a grid over task-row *blocks* (so the
  compatibility matrix streams through VMEM block by block instead of
  having to fit whole — 16k x 2k once hit the 128M VMEM cap exactly), one
  ``fori_loop`` over the block's rows, each step a VPU-width mask/min over
  the open-requester vector, a scalar winner write, and an in-place
  open-vector update.  The open vector lives in persistent VMEM scratch
  across grid steps (TPU grids execute sequentially).  No HBM traffic
  inside the loop, no per-step XLA dispatch — exactly the "keep the inner
  loop on-chip" recipe.
* Winner inversion (task-order → per-requester assignment) is another tiny
  XLA scatter after the kernel.

Semantics are bit-identical to :func:`adlb_tpu.balancer.solve._host_greedy`
(tasks in stable descending-priority order, each taking the lowest-index
open compatible requester), so all three backends — host numpy, jitted XLA
scan, Pallas — are interchangeable and cross-checked in tests.

On non-TPU backends the kernel runs in interpreter mode (tests, CPU dev);
on TPU it compiles with Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from adlb_tpu.balancer.solve import _NEG

_LANE = 128  # TPU lane width: requester vectors are padded to a multiple
# per-grid-step compat slab budget, in compat-matrix BYTES (int8 when
# streaming, int32 otherwise; see _BIG_ELEMS)
_SLAB_BYTES = 2 << 20
# Above this compat-matrix size (elements) the sweep is DMA-bound and the
# matrix streams from HBM as int8 (4x less traffic; measured 14.7 -> 10 ms
# at 65k x 8k). Mosaic cannot prove alignment for dynamic single-row loads
# from an int8 (32-sublane-tiled) block, so each grid step first upcasts
# its whole block into an int32 VMEM scratch (one aligned full-block op)
# and the row loop reads that. BELOW the threshold the matrix stays int32
# and rows load straight from the input block: the upcast is a relayout
# (retiling) whose cost exceeds the DMA it saves at small shapes
# (measured 0.6 -> 1.1 ms regression at 4k x 512).
_BIG_ELEMS = 16 << 20


def _greedy_sweep_kernel(nopen0_ref, compat_ref, winner_ref, open_scr,
                         nopen_scr, *blk_scr, upcast: bool):
    """Sequential greedy over one block of priority-ordered task rows.

    nopen0_ref: [1] int32 scalar prefetch — number of MATCHABLE requesters
                (valid with a non-empty type mask) open at sweep start
    compat_ref: [B, NRp] int8 (upcast=True) or int32 (1 = this task may
                go to this requester)
    winner_ref: [B, 1] int32 out — requester index per task row, -1 = none
    open_scr:   [1, NRp] int32 scratch — 1 while a requester is unmatched;
                persists across the (sequential) task-block grid
    nopen_scr:  [1] int32 SMEM scratch — open matchable requesters left;
                every match decrements it, and a block that starts at zero
                skips its sweep (and upcast) outright: at most NR of the
                NT priority-ordered tasks can win, so for NT >> NR most
                of the sweep is this skip
    blk_scr:    (only when upcast) [B, NRp] int32 scratch — the int8
                block upcast once per grid step; see _BIG_ELEMS
    """
    nb = compat_ref.shape[0]
    nrp = compat_ref.shape[1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        open_scr[:] = jnp.ones((1, nrp), dtype=jnp.int32)
        nopen_scr[0] = nopen0_ref[0]

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, nrp), 1)
    # decide BEFORE the sweep mutates the counter, so the two branches
    # below cannot both fire on the block where exhaustion happens
    active = nopen_scr[0] > 0

    @pl.when(active)
    def _sweep():
        if upcast:
            blk_scr[0][:] = compat_ref[:].astype(jnp.int32)
            rows = blk_scr[0]
        else:
            rows = compat_ref

        def body(t, _):
            row = rows[pl.ds(t, 1), :] * open_scr[:]
            # lowest-index open compatible requester (the host twin's
            # argmax on a bool mask picks the same first-True index)
            idx = jnp.min(jnp.where(row > 0, lane, nrp))
            found = idx < nrp
            winner_ref[pl.ds(t, 1), :] = jnp.where(found, idx, -1).reshape(
                1, 1
            )
            open_scr[:] = jnp.where(found & (lane == idx), 0, open_scr[:])
            nopen_scr[0] = nopen_scr[0] - found.astype(jnp.int32)
            return 0

        jax.lax.fori_loop(0, nb, body, 0)

    @pl.when(jnp.logical_not(active))
    def _exhausted():
        winner_ref[:] = jnp.full((nb, 1), -1, dtype=jnp.int32)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_greedy_assign(
    task_prio: jax.Array,  # [NT] int32, _NEG for padding
    task_type: jax.Array,  # [NT] int32 type index, -1 for padding
    req_mask: jax.Array,  # [NR, T] bool
    req_valid: jax.Array,  # [NR] bool
    interpret: bool = False,
) -> jax.Array:
    """Drop-in twin of :func:`adlb_tpu.balancer.solve._greedy_assign` with
    the sweep as a Pallas kernel. Returns assign[NR] int32 (task index per
    requester, -1 if none)."""
    NT = task_prio.shape[0]
    NR = req_mask.shape[0]
    NRp = _round_up(max(NR, 1), _LANE)
    # layout decision is static (shapes are): int8 streaming + upcast
    # scratch for big DMA-bound matrices, plain int32 otherwise
    upcast = NT * NRp >= _BIG_ELEMS
    cbytes = 1 if upcast else 4
    # task-block size: keep each block's compat slab small (see
    # _SLAB_BYTES; with upcast the int32 scratch is 4x the slab)
    block = max(min(NT, _SLAB_BYTES // (cbytes * NRp)), 8)
    block = min(_round_up(block, 8), _round_up(NT, 8))
    NTp = _round_up(NT, block)

    # XLA pre-pass: stable descending-priority order + compat matrix
    order = jnp.argsort(-task_prio, stable=True)
    s_prio = task_prio[order]
    s_type = task_type[order]
    live = (s_prio > _NEG) & (s_type >= 0)
    compat = (
        live[:, None]
        & req_valid[None, :]
        & req_mask[:, jnp.clip(s_type, 0)].T
    )
    compat = jnp.pad(compat, ((0, NTp - NT), (0, NRp - NR))).astype(
        jnp.int8 if upcast else jnp.int32
    )
    # matchable = can ever be assigned; requesters with empty masks (or
    # invalid slots) must not count toward the exhaustion check
    nopen0 = (req_valid & req_mask.any(axis=1)).sum().astype(jnp.int32)

    scratch = [
        pltpu.VMEM((1, NRp), jnp.int32),
        pltpu.SMEM((1,), jnp.int32),
    ]
    if upcast:
        scratch.append(pltpu.VMEM((block, NRp), jnp.int32))
    winner = pl.pallas_call(
        functools.partial(_greedy_sweep_kernel, upcast=upcast),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(NTp // block,),
            in_specs=[
                pl.BlockSpec((block, NRp), lambda i, s: (i, 0),
                             memory_space=pltpu.VMEM)
            ],
            out_specs=pl.BlockSpec((block, 1), lambda i, s: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((NTp, 1), jnp.int32),
        # the open vector and counter carry from one task block to the
        # next in scratch: the grid must run in order, on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(nopen0.reshape(1), compat)[:NT, 0]

    # invert winner-per-ordered-task into per-requester assignment; each
    # requester wins at most once so the scatter is 1-1
    valid = winner >= 0
    assign = jnp.full((NR,), -1, dtype=jnp.int32)
    assign = assign.at[jnp.where(valid, winner, NR)].set(
        jnp.where(valid, order.astype(jnp.int32), -1), mode="drop"
    )
    return assign


def make_pallas_assign(interpret: bool | None = None):
    """Returns a (task_prio, task_type, req_mask, req_valid) -> assign
    callable; interpret defaults to True off-TPU so tests and CPU dev runs
    exercise the same kernel code path."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return functools.partial(pallas_greedy_assign, interpret=interpret)
