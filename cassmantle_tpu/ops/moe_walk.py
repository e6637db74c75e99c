"""Pallas TPU kernel for the sparse LM's decode walk: the routed experts'
matrices stream through on-chip memory back to back, in pieces.

``models/moe.py::HeldExperts`` at decode has a handful of rows, each
routed to ``top_k`` experts of which a few (or all) live on this chip.
The walk goes over the held experts that some real row chose, each once,
in ascending id: rows that chose the same expert share one read of its
matrices. The XLA form (``HeldExperts._walk``) is a ``fori_loop`` whose
trip count is known only on the device: every trip starts the read of one
expert's ``gate_up`` (D, 2F) and ``down`` (F, D), waits for it,
multiplies every row, and only then may the next trip's read start. This
kernel is the same walk as one call a layer. The matrices stay in HBM;
the kernel copies them by hand in pieces of whole rows (``walk_plan``: at
most ``PIECE_BYTES`` each) through two rings of slots in on-chip memory,
one for ``gate_up``'s pieces and one for ``down``'s (``RING_BYTES``).
Piece ``c`` of the ``i``-th expert read is number ``i * pieces + c`` of
its matrix's stream and lands in slot ``number % slots``; when a piece
has been multiplied, the piece that takes its slot next, ``slots``
further on in the stream and so of this expert or of a later one, is
started. The sizes of a piece and of a ring follow from the widths and
not from the expert's size: an expert of 6.3 MB (D 2048, F 512: 4 + 2
pieces, rings that hold two experts) and one of 18.9 MB (D 2048, F 1536:
more than the 16 MiB a kernel may have, so it passes through its rings in
more than one turn) take the same code. The reads follow each other
without a gap across experts, a product runs under the reads behind it,
and only the last piece's product of a call is under none. The loop runs
``count`` times, the number of distinct experts that some real row chose
(``experts_touched``): nothing is read when nothing landed, and no byte
more than the loop reads. ``experts``, the (T, held) table of each row's
weight for each expert and ``count`` arrive as scalar-prefetch operands,
so no gather runs beside the kernel.

Arithmetic, as the compiled ``_walk`` has it: operands in the stored
type, float32 accumulation, the routing weight applied in float32, a
row's experts summed in ascending id into that row of a (T, D) float32
block; a row that did not choose the expert adds an exact 0, so a row's
sum is the same whatever rows share the call. ``_walk`` writes ``h``
rounded to the stored type before ``down``, but at one row its compiled
form is a float32 multiply-reduce and the compiler drops the rounding (it
may keep more precision than asked): on the chip the loop agrees with a
float32 reference to 1.3e-7 where a kernel that rounds ``h`` is 9.3e-4
off (outputs of size 0.66). The kernel keeps what the program has
served: ``h`` goes through ``down`` as two operands of the stored type,
its rounding and what the rounding left, which is ``h`` to 2**-17;
``lm_logit_gap``'s limit leaves no room for a second source of near-tie
expert swaps (PERF.md section 2). Every row goes through the MXU against
the expert and keeps its own weight times the product: with 1 to 4 rows
the unit's time is the load of the matrix, whatever the rows, and it
hides under the next read as the VPU's multiply-reduce does (timed, below).

On-chip memory: the two rings are 8 + 4 MiB at most (12 MiB at F 512,
11.5 at F 1536, bfloat16); the call asks for ``VMEM_LIMIT_BYTES``, under
the 16 MiB the v5e's compiler gives a kernel (it refused 32 MiB in PR
22). ``moe_walk_fits`` answers from the plan's bytes, not from lanes
alone.

The readings below are of the walk before it went by expert, one read
an assignment: at one row the reads are the same, at two and four rows
the walk by expert reads only the distinct experts (not yet timed).

What the chip said (one v5e, ``tools/moe_walk_timing.py``, PR 32: one
layer of the ``qwen3next_game`` cut, 64 dependent calls, us a call at 1 /
2 / 4 rows with 2.48 / 5.08 / 10.25 assignments landed a call; the scan
and the routing alone are 18.3 / 19.2 / 18.7 of it; the read's floor is
19.1 / 39.0 / 78.7). The XLA loop: 53.1 / 97.9 / 180.7. Whole-expert
blocks fetched by a grid of ``T · top_k`` steps, padded steps skipped:
43.5 / 65.7 / 111.2 on the MXU, 44.4 / 67.4 / 111.5 as a VPU
multiply-reduce (2.5 us of each in gathers that then ran beside the
kernel). Two slots of a whole expert, copied in 4 + 2 pieces: 39.4 / 60.7
/ 105.2, and 38.5 / 60.7 / 105.7 as a multiply-reduce; pieces of 2 + 1 or
8 + 4 instead cost 0.1 to 2.3 us more. Net of the scan that form moved an
assignment's 6.29 MB at 90-94% of the memory's pace in either arithmetic
form: the simpler one is kept. With ``h`` carried as two operands (above;
a later call, the loop 54.1 / 100.7 / 182.4 and the scan 17.6 / 19.1 /
19.1 in it): 40.9 / 62.0 / 107.4. PR 34's rings of pieces: the same
tool and cut (the loop 52.6 / 98.7 / 183.2, the scan 16.9 / 17.6 / 18.7)
read 39.4 / 62.7 / 106.3, as the two whole-expert slots did. At the
``lfm2_game`` cut (``--config lfm2``: D 2048, F 1536, every one of a
call's 4 / 8 / 16 assignments lands, 18.87 MB each; the read's floor 92.2
/ 184.4 / 368.7, the scan 17.1 / 17.1 / 19.6): the loop 141.3 / 280.1 /
540.0, the kernel 120.1 / 222.2 / 420.1, net of the scan 89-92% of the
memory's pace, 3.6e-6 from a float32 expert layer. The plan hardly
matters there (1 / 4 rows): pieces of at most 1 MiB (16 + 6 an expert,
rings of 10 + 4) 120.1 / 420.1; of 1.5 MiB (8 + 4, rings of 5 + 2) 123.0
/ 421.3; ``gate_up`` 1.5 MiB and ``down`` 2 MiB (rings of 4 + 2) 120.7 /
422.1; ``down`` in 12 pieces of 0.5 MiB 120.7 / 420.0. 1 MiB is kept:
it is the F 512 form's piece.

What the chip said of a whole dispatch (same tool, ``--dispatch``: 8
layers, 96 steps, batch 1, bucket 32, ms on the host's clock; the XLA loop
146.5). The compiler overlaps a decode step's weight reads only where its
cost model sees a long instruction to put them under: the parent's step
held 132 prefetches of mixer, head and shared-expert weights into on-chip
memory, all scheduled under the walk's inner loops, whose length the
model overrates. With no cost stated the kernel counts as free, the
scan's body gets no prefetch at all, every product waits for its own
read, and the dispatch takes 158.7 though the walk itself fell from 31.5
to 15.9 ms. ``cost_estimate`` is therefore the bound, all ``T · top_k``
assignments landed (62.9 MB at one row; exact where every expert is
held): 138.0 (138.5 with ``h`` as two operands; 139.8 in bucket 64
against 147.9, and at 2 and 4 rows 247.4 and 286.7 against 254.3 and
342.3). Stated at 0.5 and 0.6 of
the bound 158.7 and 158.9 (no prefetch); at 0.7, 0.8, 0.9, 1.2: 136.9,
136.7, 137.3, 136.0; at 1.5, 2, 4: 141.9, 142.6, 143.2. The bound is
kept as the one value with a meaning; it lies clear of the cliff.
At the ``lfm2_game`` cut (PR 34, ``--config lfm2 --dispatch``: 1 + 8
layers, batch 1, bucket 32): the loop 192.1, the kernel 191.8. An
assignment there is a read of 18.9 MB, against which a loop trip's
wait is small, and what the loop leaves open the compiler fills with its
other prefetches: the kernel wins a fifth of a layer call alone and
nothing of a dispatch, and is kept as the one form both cuts take (in a
served round its 768 calls a dispatch take 102.5 us each, 90% of the
memory's pace).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cassmantle_tpu.ops.platform import on_tpu

F32 = jnp.float32
_LANES = 128
#: a piece is the largest block of whole-lane rows of a matrix that
#: divides it and takes at most this many bytes: an assignment's
#: arithmetic starts when the first has landed
PIECE_BYTES = 1024 * 1024
#: what the two rings of pieces hold, ``gate_up``'s and ``down``'s: an
#: expert's two matrices are 2 to 1, and so are the rings
RING_BYTES = (8 * 1024 * 1024, 4 * 1024 * 1024)
VMEM_LIMIT_BYTES = 14 * 1024 * 1024


class WalkPlan(NamedTuple):
    """How the kernel cuts an expert's matrices and what it keeps on the
    chip: ``gate_up`` (D, 2F) in pieces of ``gate_up_rows`` rows through a
    ring of ``gate_up_slots`` pieces, ``down`` (F, D) likewise."""

    gate_up_rows: int
    gate_up_slots: int
    down_rows: int
    down_slots: int

    def scratch_bytes(self, d: int, f: int, itemsize: int) -> int:
        return itemsize * (self.gate_up_slots * self.gate_up_rows * 2 * f
                           + self.down_slots * self.down_rows * d)


def walk_plan(d: int, f: int, itemsize: int, piece_bytes: int = PIECE_BYTES,
              ring_bytes=RING_BYTES) -> Optional[WalkPlan]:
    """The plan for experts of these widths, from the widths alone: the
    pieces' sizes and the rings' do not follow the expert's, so an expert
    larger than on-chip memory streams through. None where the kernel
    does not tile them: lanes that are not whole, or no piece small
    enough for a ring of two."""
    if d % _LANES or f % _LANES:
        return None

    def rows(axis: int, width: int) -> int:
        return max((r for r in range(_LANES, axis + 1, _LANES)
                    if axis % r == 0 and r * width * itemsize <= piece_bytes),
                   default=0)

    gate_up_rows, down_rows = rows(d, 2 * f), rows(f, d)
    if not gate_up_rows or not down_rows:
        return None
    plan = WalkPlan(
        gate_up_rows, ring_bytes[0] // (gate_up_rows * 2 * f * itemsize),
        down_rows, ring_bytes[1] // (down_rows * d * itemsize))
    return plan if min(plan.gate_up_slots, plan.down_slots) >= 2 else None


def _walk_kernel(experts_ref, weight_ref, count_ref, x_ref, gate_up_hbm,
                 down_hbm, out_ref, gate_up_buf, down_buf, gate_up_sems,
                 down_sems):
    count = count_ref[0]
    t = x_ref.shape[0]
    f = down_hbm.shape[1]

    class Ring:
        """One matrix's pieces through its slots: piece ``c`` of the
        ``i``-th expert read is number ``i * pieces + c`` of the stream
        and lands in slot ``number % slots``; the piece that takes a slot
        next is started when the one in it has been multiplied."""

        def __init__(self, hbm, buf, sems):
            self.hbm, self.buf, self.sems = hbm, buf, sems
            self.slots, self.rows = buf.shape[0], buf.shape[1]
            self.pieces = hbm.shape[1] // self.rows

        def copy(self, i, c, slot):
            return pltpu.make_async_copy(
                self.hbm.at[experts_ref[i], pl.ds(c * self.rows, self.rows)],
                self.buf.at[slot], self.sems.at[slot])

        def start(self, i, c, slot):
            @pl.when(i < count)
            def _():
                self.copy(i, c, slot).start()

        def fill(self):
            for number in range(self.slots):
                i, c = divmod(number, self.pieces)
                self.start(i, c, number)

        def product(self, i, lhs_parts, width):
            """sum over the pieces of parts[:, piece] @ matrix[piece]."""
            acc = jnp.zeros((t, width), F32)
            for c in range(self.pieces):
                slot = jax.lax.rem(i * self.pieces + c, self.slots)
                self.copy(i, c, slot).wait()
                piece = slice(c * self.rows, (c + 1) * self.rows)
                for part in lhs_parts:
                    acc += jnp.dot(part[:, piece], self.buf[slot],
                                   preferred_element_type=F32)
                ahead, c_next = divmod(c + self.slots, self.pieces)
                self.start(i + ahead, c_next, slot)
            return acc

    gate_up = Ring(gate_up_hbm, gate_up_buf, gate_up_sems)
    down = Ring(down_hbm, down_buf, down_sems)
    out_ref[...] = jnp.zeros_like(out_ref)
    gate_up.fill()
    down.fill()
    rows = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)

    def expert(i, carry):
        gu = gate_up.product(i, (x_ref,), 2 * f)
        h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        # h at float32's precision from two products in the stored type:
        # its rounding, and what the rounding left
        h_hi = h.astype(down_buf.dtype)
        h_lo = (h - h_hi.astype(F32)).astype(down_buf.dtype)
        y = down.product(i, (h_hi, h_lo), out_ref.shape[1])
        # each row's weight for this expert, 0 where the row did not
        # choose it: such a row adds an exact 0
        column = experts_ref[i] * t
        w = jnp.zeros(out_ref.shape, F32)
        for r in range(t):
            w = jnp.where(rows == r, weight_ref[column + r], w)
        out_ref[...] += jnp.where(w != 0.0, w * y, 0.0)
        return carry

    jax.lax.fori_loop(0, count, expert, 0)


def moe_walk_fits(d: int, f: int, itemsize: int = 2) -> bool:
    """Widths the kernel tiles within the on-chip memory it asks for: the
    two rings, and as much again as one piece for the rows, the output
    and what the compiler keeps of its own."""
    plan = walk_plan(d, f, itemsize)
    return plan is not None and (plan.scratch_bytes(d, f, itemsize)
                                 + PIECE_BYTES <= VMEM_LIMIT_BYTES)


@functools.partial(jax.jit,
                   static_argnames=("top_k", "interpret", "plan"))
def moe_walk(x: jax.Array, gate_up: jax.Array, down: jax.Array,
             experts: jax.Array, combine: jax.Array, count: jax.Array,
             top_k: int, interpret=None,
             plan: Optional[WalkPlan] = None) -> jax.Array:
    """``HeldExperts._walk`` as one kernel call. ``x`` (T, D) in the
    stored type, ``gate_up`` (E, D, 2F), ``down`` (E, F, D); ``experts``
    (E,) local ids with the ``count`` that some row chose first, in
    ascending order; ``combine`` (T, E) each row's routing weight for
    each expert, 0 where the row did not choose it (``top_k`` a row).
    ``plan`` is ``walk_plan``'s for the widths unless a test hands one.
    Returns (T, D) float32: each row's chosen experts' outputs times
    their weights, summed in ascending expert id; zeros for a row none
    of whose experts is held here."""
    t, d = x.shape
    held, f = down.shape[0], down.shape[1]
    expert_size = 3 * d * f
    # the count is known only on the device, so the compiler is told the
    # most a call can read: an expert for every assignment of the call,
    # or every held expert if there are fewer (exact where every expert
    # is held and no two rows share one). It prefetches the step's other
    # weights into on-chip memory only under instructions it believes
    # long (module docstring)
    reads = min(t * top_k, held)
    if plan is None:
        plan = walk_plan(d, f, gate_up.dtype.itemsize)
    if interpret is None:
        interpret = not on_tpu()
    return pl.pallas_call(
        _walk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((t, d), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec((t, d), lambda i, *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((plan.gate_up_slots, plan.gate_up_rows, 2 * f),
                           gate_up.dtype),
                pltpu.VMEM((plan.down_slots, plan.down_rows, d), down.dtype),
                pltpu.SemaphoreType.DMA((plan.gate_up_slots,)),
                pltpu.SemaphoreType.DMA((plan.down_slots,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * reads * expert_size, transcendentals=reads * f,
            bytes_accessed=reads * expert_size * gate_up.dtype.itemsize),
        interpret=bool(interpret),
        # what a device trace calls the kernel (the HLO instruction and a
        # scope of its op_name), under the caller's ``moe_experts``
        name="moe_walk",
    )(experts.astype(jnp.int32),
      # by expert, then row: expert e's weights are [e * T, (e + 1) * T)
      jnp.transpose(combine).reshape(-1).astype(F32),
      jnp.reshape(count, (1,)).astype(jnp.int32), x, gate_up, down)
