"""Kernel recognition and byte counts from the HLO text the device trace
prints, against hand-worked values."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import counts  # noqa: E402

# as printed by the trace of one committee-256.fl-round session on a v5e
MASK = ("%raw.65 = u32[256,8192,128]{2,1,0:T(8,128)} custom-call(f32[256,"
        "8192,128]{2,1,0:T(8,128)} %bitcast.8, u32[3,256]{1,0:T(4,128)S(1)} "
        "%copy-done.2), custom_call_target=\"tpu_custom_call\", "
        "operand_layout_constraints={f32[256,8192,128]{2,1,0}, "
        "u32[3,256]{1,0}}, frontend_attributes={kernel_metadata={}}")
VOTE = ("%raw.66 = u32[2097152,128]{1,0:T(8,128)} custom-call(u32[2097152,"
        "128]{1,0:T(8,128)} %reshape.323, u32[2097152,128]{1,0:T(8,128)} "
        "%broadcast_select_fusion.125, u32[2097152,128]{1,0:T(8,128)} "
        "%broadcast_select_fusion.62, u32[2097152,128]{1,0:T(8,128)} "
        "%reshape.326), custom_call_target=\"tpu_custom_call\", "
        "operand_layout_constraints={u32[2097152,128]{1,0}, u32[2097152,128]"
        "{1,0}, u32[2097152,128]{1,0}, u32[2097152,128]{1,0}}, "
        "frontend_attributes={kernel_metadata={}}")
UNMASK = ("%raw.194 = f32[1,8192,128]{2,1,0:T(8,128)} custom-call(u32[1,"
          "8192,128]{2,1,0:T(8,128)} %bitcast.9, u32[2,1]{1,0:T(2,128)S(1)} "
          "%fusion.3), custom_call_target=\"tpu_custom_call\"")
RESHAPE = ("%reshape.11 = u32[2097152,128]{1,0:T(8,128)} reshape(u32[1,256,"
           "1048576]{2,1,0:T(8,128)} %fusion.1)")
GIB = 1 << 30


def test_kernels_are_recognised_by_signature():
    assert counts.kernel_of(MASK) == "mask"
    assert counts.kernel_of(VOTE) == "vote"
    assert counts.kernel_of(UNMASK) == "unmask"
    assert counts.kernel_of(RESHAPE) is None


def test_vote_bytes_of_one_fl_session_hop():
    # r=3 copies and the accumulator of 256 x 2^20 uint32 read, one written
    assert counts.kernel_bytes(VOTE) == 5 * GIB


def test_mask_bytes_of_one_fl_session():
    # 1 GiB float32 read, 1 GiB uint32 written, the (3, 256) table read
    assert counts.kernel_bytes(MASK) == 2 * GIB + 3 * 256 * 4


def test_unmask_bytes_of_one_fl_session():
    # 4 MiB uint32 read, 4 MiB float32 written, the (2, 1) table read
    assert counts.kernel_bytes(UNMASK) == 8 * (1 << 20) + 8


def test_shapes_and_opcode_labels():
    assert counts.shapes("u32[3,256]{1,0} f32[] pred[8]") == [
        ("u32", (3, 256)), ("f32", ()), ("pred", (8,))]
    assert counts.nbytes(("bf16", (4, 8))) == 64
    assert counts.opcode(VOTE) == "vote_kernel"
    assert counts.opcode(RESHAPE) == "reshape u32[2097152,128]"


def test_collectives_are_recognised():
    assert counts.is_collective(
        "%all-reduce.3 = f32[4096]{0} all-reduce(f32[4096]{0} %p)")
    assert counts.is_collective("%cp = u32[8]{0} collective-permute-start("
                                "u32[8]{0} %x)")
    assert not counts.is_collective(RESHAPE)
