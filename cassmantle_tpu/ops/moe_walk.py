"""Pallas TPU kernel for the sparse LM's decode walk: the routed experts'
matrices stream through on-chip memory back to back.

``models/moe.py::HeldExperts`` at decode has a handful of rows, each
routed to ``top_k`` experts of which a few live on this chip. The XLA
form (``HeldExperts._walk``) is a ``fori_loop`` whose trip count is known
only on the device: every trip starts the read of one expert's
``gate_up`` (D, 2F) and ``down`` (F, D), waits for it, multiplies, and
only then may the next trip's read start. This kernel is the same walk as
one call a layer. The matrices stay in HBM; the kernel copies an
assignment's two matrices by hand, in ``_GATE_UP_PIECES`` and
``_DOWN_PIECES`` row pieces, into one of two slots of on-chip memory, and
starts assignment ``i + 1``'s copies before assignment ``i``'s
arithmetic, which waits for each piece where it first reads it. So the
reads follow each other without a gap, a product runs under the read
behind it, and only the last piece's product of a call is under none.
The loop runs ``count`` times, the number of assignments that landed:
nothing is read when nothing landed, and no byte more than the loop
reads. ``order``, ``expert``, ``weight`` and ``count`` arrive as
scalar-prefetch operands, so no gather runs beside the kernel.

Arithmetic, as the compiled ``_walk`` has it: operands in the stored
type, float32 accumulation, the routing weight applied in float32, a
row's assignments summed in their own order into that row of a (T, D)
float32 block. ``_walk`` writes ``h`` rounded to the stored type before
``down``, but its compiled form is a float32 multiply-reduce and the
compiler drops the rounding (it may keep more precision than asked): on
the chip the loop agrees with a float32 reference to 1.3e-7 where a
kernel that rounds ``h`` is 9.3e-4 off (outputs of size 0.66). The kernel
keeps what the program has served: ``h`` goes through ``down`` as two
operands of the stored type, its rounding and what the rounding left,
which is ``h`` to 2**-17; ``lm_logit_gap``'s limit leaves no room for a
second source of near-tie expert swaps (PERF.md section 2). Every row goes through
the MXU and the assignment's row is kept: with 1 to 4 rows the unit's
time is the load of the matrix, whatever the rows, and it hides under the
next read as the VPU's multiply-reduce does (timed, below).

On-chip memory: two slots of one expert are 2 · (4 + 2) MiB at the
served widths (D 2048, F 512, bfloat16); the call asks for
``VMEM_LIMIT_BYTES``, under the 16 MiB the v5e's compiler gives a kernel
(it refused 32 MiB in PR 22).

What the chip said (one v5e, ``tools/moe_walk_timing.py``, PR 32: one
layer of the served cut, 64 dependent calls, us a call at 1 / 2 / 4 rows
with 2.48 / 5.08 / 10.25 assignments landed a call; the scan and the
routing alone are 18.3 / 19.2 / 18.7 of it; the read's floor is 19.1 /
39.0 / 78.7). The XLA loop: 53.1 / 97.9 / 180.7. Whole-expert blocks
fetched by a grid of ``T · top_k`` steps, padded steps skipped: 43.5 /
65.7 / 111.2 on the MXU, 44.4 / 67.4 / 111.5 as a VPU multiply-reduce
(2.5 us of each in gathers that then ran beside the kernel). This form:
39.4 / 60.7 / 105.2, and 38.5 / 60.7 / 105.7 as a multiply-reduce; pieces
of 2 + 1 or 8 + 4 instead of 4 + 2 cost 0.1 to 2.3 us more. Net of the
scan the kernel moves an assignment's 6.29 MB at 90-94% of the memory's
pace in either arithmetic form: the simpler one is kept. With ``h``
carried as two operands (above; a later call, the loop 54.1 / 100.7 /
182.4 and the scan 17.6 / 19.1 / 19.1 in it): 40.9 / 62.0 / 107.4.

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
assignments landed (62.9 MB at one row): 138.0 (138.5 with ``h`` as two
operands; 139.8 in bucket 64 against 147.9, and at 2 and 4 rows 247.4
and 286.7 against 254.3 and 342.3). Stated at 0.5 and 0.6 of
the bound 158.7 and 158.9 (no prefetch); at 0.7, 0.8, 0.9, 1.2: 136.9,
136.7, 137.3, 136.0; at 1.5, 2, 4: 141.9, 142.6, 143.2. The bound is
kept as the one value with a meaning; it lies clear of the cliff.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cassmantle_tpu.ops.platform import on_tpu

F32 = jnp.float32
_LANES = 128
#: row pieces an expert's two matrices are copied in: an assignment's
#: arithmetic starts when the first has landed
_GATE_UP_PIECES = 4
_DOWN_PIECES = 2
VMEM_LIMIT_BYTES = 14 * 1024 * 1024


def _walk_kernel(order_ref, expert_ref, weight_ref, count_ref, x_ref,
                 gate_up_hbm, down_hbm, out_ref, gate_up_buf, down_buf,
                 sems, *, top_k: int):
    count = count_ref[0]
    d, f2 = gate_up_buf.shape[1:]
    f = f2 // 2
    gu_rows, dn_rows = d // _GATE_UP_PIECES, f // _DOWN_PIECES

    def copies(i, slot):
        """Assignment ``i``'s copies into ``slot``, (``gate_up``'s pieces,
        ``down``'s): started once, described again where each is waited
        for."""
        e = expert_ref[order_ref[i]]

        def pieces(hbm, buf, rows, n, first):
            return [pltpu.make_async_copy(
                hbm.at[e, pl.ds(c * rows, rows)],
                buf.at[slot, pl.ds(c * rows, rows)], sems.at[slot, first + c])
                for c in range(n)]

        return (pieces(gate_up_hbm, gate_up_buf, gu_rows, _GATE_UP_PIECES, 0),
                pieces(down_hbm, down_buf, dn_rows, _DOWN_PIECES,
                       _GATE_UP_PIECES))

    def start(i, slot):
        for copy in sum(copies(i, slot), []):
            copy.start()

    out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(count > 0)
    def _first():
        start(0, 0)

    def assignment(i, carry):
        slot = i % 2

        @pl.when(i + 1 < count)
        def _next():
            start(i + 1, 1 - slot)

        gate_up_copies, down_copies = copies(i, slot)
        gu = jnp.zeros((x_ref.shape[0], f2), F32)
        for c, copy in enumerate(gate_up_copies):
            copy.wait()
            piece = slice(c * gu_rows, (c + 1) * gu_rows)
            gu += jnp.dot(x_ref[:, piece], gate_up_buf[slot, piece, :],
                          preferred_element_type=F32)
        h = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        # h at float32's precision from two products in the stored type:
        # its rounding, and what the rounding left
        h_hi = h.astype(down_buf.dtype)
        h_lo = (h - h_hi.astype(F32)).astype(down_buf.dtype)
        y = jnp.zeros(out_ref.shape, F32)
        for c, copy in enumerate(down_copies):
            copy.wait()
            piece = slice(c * dn_rows, (c + 1) * dn_rows)
            for part in (h_hi, h_lo):
                y += jnp.dot(part[:, piece], down_buf[slot, piece, :],
                             preferred_element_type=F32)
        at = order_ref[i]
        rows = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
        out_ref[...] += jnp.where(rows == at // top_k, weight_ref[at] * y,
                                  0.0)
        return carry

    jax.lax.fori_loop(0, count, assignment, 0)


def moe_walk_fits(d: int, f: int) -> bool:
    """Widths the kernel tiles: whole lanes, cut into its row pieces."""
    return (d % (_LANES * _GATE_UP_PIECES) == 0
            and f % (_LANES * _DOWN_PIECES) == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_walk(x: jax.Array, gate_up: jax.Array, down: jax.Array,
             expert: jax.Array, weight: jax.Array, order: jax.Array,
             count: jax.Array, interpret=None) -> jax.Array:
    """``HeldExperts._walk`` as one kernel call. ``x`` (T, D) in the
    stored type, ``gate_up`` (E, D, 2F), ``down`` (E, F, D); ``expert``
    and ``weight`` (T · top_k,) by assignment slot (slot // top_k is the
    row), ``order`` the slots with the ``count`` landed ones first.
    Returns (T, D) float32: each row's landed experts' outputs times
    their weights, summed in ``order``; zeros for a row none landed
    for."""
    t, d = x.shape
    f = down.shape[1]
    steps = expert.shape[0]
    expert_size = 3 * d * f
    if interpret is None:
        interpret = not on_tpu()
    return pl.pallas_call(
        functools.partial(_walk_kernel, top_k=steps // t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((t, d), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM),
                pl.BlockSpec(memory_space=pltpu.HBM),
            ],
            out_specs=pl.BlockSpec((t, d), lambda i, *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, d, 2 * f), gate_up.dtype),
                pltpu.VMEM((2, f, d), down.dtype),
                pltpu.SemaphoreType.DMA(
                    (2, _GATE_UP_PIECES + _DOWN_PIECES)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        # the count is known only on the device, so the compiler is told
        # the most a call can read: every slot's assignment landed. It
        # prefetches the step's other weights into on-chip memory only
        # under instructions it believes long (module docstring)
        cost_estimate=pl.CostEstimate(
            flops=2 * steps * expert_size, transcendentals=steps * f,
            bytes_accessed=steps * expert_size * gate_up.dtype.itemsize),
        interpret=bool(interpret),
        # what a device trace calls the kernel (the HLO instruction and a
        # scope of its op_name), under the caller's ``moe_experts``
        name="moe_walk",
    )(order.astype(jnp.int32), expert.astype(jnp.int32),
      weight.astype(F32), jnp.reshape(count, (1,)).astype(jnp.int32),
      x, gate_up, down)
