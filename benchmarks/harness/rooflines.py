"""The least time the chip could take for a kernel's call: its operations
and bytes, from the plain reference's shapes, over the published peaks.

A call in the trace is given its site by the table of device instructions
(trace.reduce_trace): the event's whole HLO text holds the call's result
and operand shapes, and the reference says which products of those shapes
the models have. A call that fits no site of the reference (at a whole
multiple of the site's batch), or more than one, is an error: a share of a roofline is never worked out from a guess.
"""

from __future__ import annotations

import re

from . import flops, peaks
from . import reference as ref

SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
#: bytes an element, by the HLO's name for the types the configurations
#: state; a call in another type is an error until it is on record here
ELEMENT_BYTES = {"f32": 4, "bf16": 2}


def call_shapes(hlo: str) -> tuple:
    """(results, operands) of one HLO instruction's text, each a list of
    (element type, dimensions); layouts and attributes are dropped."""
    text = re.sub(r"\{[^{}]*\}", "", hlo.split(" = ", 1)[-1])
    head, _, rest = text.partition("(")

    def shapes(part: str) -> list:
        return [(kind, tuple(int(n) for n in dims.split(",") if n))
                for kind, dims in SHAPE.findall(part)]

    return shapes(head), shapes(rest.partition(")")[0])


def attention_sites(trees: dict, sizes: dict, names: dict) -> set:
    """(B, S_q, S_k, H*D) of every attention product an image takes in the
    plain reference: the text towers, the UNet at the batch the
    configuration's trajectory calls it at, the VAE decode."""
    with ref.list_attention() as sites:
        flops.image_flops(trees, sizes, names)
    return {(b, sq, sk, heads * d) for b, heads, sq, sk, d in sites}


def attention_call(hlo: str, sites: set) -> tuple:
    """(site at the call's batch, bytes an element) of one attention kernel
    call: q, k and v as (B, S, H*D) operands, the output of q's shape. The
    program may pad the keys (a ragged S_k up to whole blocks) and may
    gather several images into one call: the site is the one with the
    call's S_q and H*D, a B that divides the call's, and the call's S_k,
    or else the only one whose S_k is shorter. The work counted is the
    site's, with the published S_k and head size, not the padded call's,
    once for each of the site's batches the call holds: the site comes
    back with the call's B in place of its own, so its floor is that
    multiple of one image's."""
    results, operands = call_shapes(hlo)
    if len(results) != 1 or len(operands) != 3 \
            or any(len(dims) != 3 for _, dims in results + operands):
        raise ValueError(f"not an attention call on (B, S, H*D): {hlo[:300]}")
    (kind, (b, sq, width)), (_, (_, sk_call, _)) = results[0], operands[1]
    if kind not in ELEMENT_BYTES:
        raise ValueError(f"no size on record for element type {kind!r}")
    fits = [s for s in sites
            if (s[1], s[3]) == (sq, width) and b % s[0] == 0]
    exact = [s for s in fits if s[2] == sk_call]
    match = exact or [s for s in fits if s[2] < sk_call]
    if len(match) != 1:
        raise ValueError(
            f"an attention call of B {b}, S_q {sq}, S_k {sk_call}, H*D "
            f"{width} fits {len(match)} sites of the reference "
            f"{sorted(sites)}")
    return (b,) + match[0][1:], ELEMENT_BYTES[kind]


def attention_floor_s(site: tuple, element_bytes: int,
                      device_kind: str) -> float:
    """The larger of 4*B*S_q*S_k*(H*D) FLOPs (QK^T and PV) over the bf16
    peak and q, k, v and the output moved once over the memory's."""
    b, sq, sk, width = site
    ops = 4.0 * b * sq * sk * width
    moved = (2.0 * b * sq * width + 2.0 * b * sk * width) * element_bytes
    return max(ops / peaks.peak(device_kind, "bf16_flops_per_s"),
               moved / peaks.peak(device_kind, "hbm_bytes_per_s"))
