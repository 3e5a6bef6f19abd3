"""Operations and bytes of the kernels, counted from shapes.

The device trace prints every op as its HLO text (``%raw.66 =
u32[2097152,128]{...} custom-call(u32[2097152,128]{...} %a, ...),
custom_call_target="tpu_custom_call", ...``).  The Pallas kernels carry no
name of their own there, so a kernel is recognised by its signature:

* ``vote`` (``vote_combine``): r copies and the accumulator in, all uint32
  of the output's shape;
* ``mask`` (``mask_encrypt_batch``): float32 payload tiles and a (3, B)
  uint32 table of seeds, node ids and offsets in, uint32 of the payload's
  shape out;
* ``unmask`` (``unmask_decrypt_batch``): uint32 aggregate tiles and a
  (2, B) uint32 table in, float32 of the aggregate's shape out.

Their HBM traffic is each operand read once and the result written once:
the least any implementation of the call moves.
"""
from __future__ import annotations

import math
import re
from typing import Optional

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
               "u64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_CUSTOM = re.compile(r"^%\S+ = (?P<out>.*?) custom-call\((?P<args>.*?)\), "
                     r"custom_call_target=\"tpu_custom_call\"")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def shapes(text: str) -> list:
    """[(dtype, dims)] of every array shape in a piece of HLO text."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def nbytes(shape: tuple) -> int:
    dt, dims = shape
    return DTYPE_BYTES[dt] * math.prod(dims)


def kernel_of(op: str) -> Optional[str]:
    """``"vote"``, ``"mask"``, ``"unmask"`` or ``"pallas"`` (another
    kernel) for a Pallas call's HLO text; None for any other op."""
    m = _CUSTOM.match(op)
    if m is None:
        return None
    out, args = shapes(m.group("out")), shapes(m.group("args"))
    if len(out) != 1 or not args:
        return "pallas"
    (odt, odims), (adt, adims) = out[0], args[0]
    if (odt == "u32" and len(args) >= 2
            and all(a == out[0] for a in args)):
        return "vote"
    if len(args) == 2 and adims == odims and args[1][0] == "u32":
        table = args[1][1]
        if (adt, odt) == ("f32", "u32") and table[:1] == (3,):
            return "mask"
        if (adt, odt) == ("u32", "f32") and table[:1] == (2,):
            return "unmask"
    return "pallas"


def kernel_bytes(op: str) -> int:
    """HBM bytes of one Pallas call: every operand read once, the result
    written once."""
    m = _CUSTOM.match(op)
    if m is None:
        raise ValueError(f"not a Pallas call: {op[:80]}")
    return sum(nbytes(s) for s in shapes(m.group("out"))
               + shapes(m.group("args")))


def opcode(op: str) -> str:
    """A short label of an op for the breakdown: the kernel for a Pallas
    call, otherwise the HLO opcode and its result shape."""
    k = kernel_of(op)
    if k is not None:
        return f"{k}_kernel"
    m = re.match(r"^%\S+ = (?P<out>\S+?)(\{[^}]*\})? (?P<code>[\w-]+)\(", op)
    if m is None:
        return op.split(" ", 1)[0][:64]
    return f"{m.group('code')} {m.group('out')}"


def is_collective(op: str) -> bool:
    m = re.match(r"^%\S+ = \S+ (?P<code>[\w-]+)\(", op)
    code = m.group("code") if m else op
    return any(code.startswith(c) for c in COLLECTIVES)


def ssd_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward FLOPs a token of one Mamba-2 layer's SSD takes by the
    chunked algorithm (arXiv:2405.21060, Listing 1): its four
    contractions over chunks of Q positions, the whole Q x Q block of
    each: the scores C B^T (2 Q N), the diagonal blocks' outputs
    (2 Q H P), the chunk states and the states' outputs (2 N H P each).
    The chunk-to-chunk passing of the states (H P N a chunk) is left out."""
    s = model["ssm"]
    Q = min(s["chunk"], seq_len)
    N, P = s["d_state"], s["head_dim"]
    H = s["expand"] * model["d_model"] // P
    return 2 * Q * N + 2 * Q * H * P + 4 * N * H * P


def matmul_params(model: dict) -> int:
    """Parameters a token meets in a matrix product: each layer's input
    projections (z, x, B, C and dt) and output projection, and the output
    head over the vocabulary (the tied embedding; the lookup is no
    product)."""
    d, s = model["d_model"], model["ssm"]
    d_in = s["expand"] * d
    heads = d_in // s["head_dim"]
    layer = d * (2 * d_in + 2 * s["d_state"] + heads) + d_in * d
    return model["n_layers"] * layer + model["vocab_size"] * d


def train_step_flops(model: dict, batch: int, seq_len: int) -> float:
    """Model FLOPs of one training step of a Mamba-2 language model,
    whatever implements it: 6 x the matrix-product parameters x tokens,
    plus every layer's SSD forward and backward (3 x its forward).  The
    forward that rematerialisation runs again is not counted."""
    tokens = batch * seq_len
    return tokens * (6 * matmul_params(model) + 3 * model["n_layers"]
                     * ssd_flops_per_token(model, seq_len))
