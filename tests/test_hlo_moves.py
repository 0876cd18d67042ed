"""``obs.hlo.program_moves`` over canned optimized-HLO text: what counts as a pure data
movement of a weight's size anywhere in a program, and what does not.

The text below is cut from the chip compiler's output for the serving decode programs as
they stood before the attention projections were multiplied where they lie (names and
shapes kept, layouts' tiling and the metadata dropped): one layer's ``wq`` sliced out of
its stack inside the layer loop and copied to another layout in front of its matmul (the
chat cell), seven layers' ``wq`` sliced and re-laid by one multi-output fusion in the entry
computation (the ``k-exaone`` cell, whose loop is unrolled), beside ``wo``, whose slice
sits inside the matmul's own fusion and is read where it lies. The programs themselves
are held to the reader in ``tests/test_paged_attention_kernel.py``.
"""

from __future__ import annotations

import pytest

from torchx_tpu.obs.hlo import loop_moves, moves_by_loop, program_moves

MIB = 2**20

HLO = """\
HloModule jit_decode, is_scheduled=true

%region_4.10 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[]{:T(128)} parameter(0)
  %b = f32[]{:T(128)} parameter(1)
  ROOT %add.1 = f32[]{:T(128)} add(%a, %b)
}

%fused_computation.65 (param_0.646: bf16[16,4096,4096], param_1.795: s32[]) -> bf16[1,4096,4096] {
  %param_0.646 = bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.795 = s32[]{:T(128)} parameter(1)
  %constant.789 = s32[]{:T(128)} constant(0)
  %one = s32[]{:T(128)} constant(1)
  %next = s32[]{:T(128)} add(%param_1.795, %one)
  ROOT %dynamic_slice.130 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)S(1)} dynamic-slice(%param_0.646, %next, %constant.789, %constant.789), dynamic_slice_sizes={1,4096,4096}
}

%fused_computation.16 (param_0.1: bf16[16,4096,4096], param_1.1: s32[]) -> bf16[4096,4096] {
  %param_0.1 = bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.1 = s32[]{:T(128)} parameter(1)
  %constant.2 = s32[]{:T(128)} constant(0)
  %dynamic_slice.9 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.1, %param_1.1, %constant.2, %constant.2), dynamic_slice_sizes={1,4096,4096}
  ROOT %bitcast.9 = bf16[4096,4096]{1,0:T(8,128)(2,1)} bitcast(%dynamic_slice.9)
}

%fused_computation.31 (param_0.656: bf16[16,4096], param_1.802: bf16[16,4096,4096], param_2.769: s32[]) -> bf16[16,4096] {
  %param_0.656 = bf16[16,4096]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1.802 = bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)} parameter(1)
  %param_2.769 = s32[]{:T(128)} parameter(2)
  %fusion.39 = bf16[4096,4096]{1,0:T(8,128)(2,1)} fusion(%param_1.802, %param_2.769), kind=kLoop, calls=%fused_computation.16
  ROOT %convolution.13 = bf16[16,4096]{1,0:T(8,128)(2,1)} convolution(%param_0.656, %fusion.39), dim_labels=bf_io->bf
}

%fused_computation.47 (param_0.7: bf16[32,128,4096], param_1.7: bf16[16,4096]) -> bf16[16,32,128] {
  %param_0.7 = bf16[32,128,4096]{2,1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.7 = bf16[16,4096]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.30 = bf16[16,32,128]{2,0,1:T(8,128)(2,1)S(1)} convolution(%param_1.7, %param_0.7), window={size=32 pad=31_31 rhs_reversal=1}, dim_labels=bf0_0oi->b0f
}

%fused_computation.90 (param_0.9: bf16[16,4096]) -> bf16[16,4096] {
  %param_0.9 = bf16[16,4096]{1,0:T(8,128)(2,1)} parameter(0)
  %slice.90 = bf16[16,4096]{1,0:T(8,128)(2,1)} slice(%param_0.9), slice={[0:16], [0:4096]}
  ROOT %add.90 = bf16[16,4096]{1,0:T(8,128)(2,1)} add(%slice.90, %slice.90)
}

%body (state: (s32[], bf16[16,4096], bf16[16,4096,4096], bf16[16,4096,4096])) -> (s32[], bf16[16,4096], bf16[16,4096,4096], bf16[16,4096,4096]) {
  %state = (s32[]{:T(128)}, bf16[16,4096]{1,0:T(8,128)(2,1)}, bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}, bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%state), index=0
  %x = bf16[16,4096]{1,0:T(8,128)(2,1)} get-tuple-element(%state), index=1
  %wq = bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)} get-tuple-element(%state), index=2
  %wo = bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)} get-tuple-element(%state), index=3
  %constant_dynamic-slice_fusion.6 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)S(1)} fusion(%wq, %i), kind=kLoop, calls=%fused_computation.65
  %copy.41 = bf16[1,4096,4096]{1,2,0:T(8,128)(2,1)S(1)} copy(%constant_dynamic-slice_fusion.6)
  %bitcast.154 = bf16[32,128,4096]{2,1,0:T(8,128)(2,1)S(1)} bitcast(%copy.41)
  %fusion.151 = bf16[16,32,128]{2,0,1:T(8,128)(2,1)S(1)} fusion(%bitcast.154, %x), kind=kOutput, calls=%fused_computation.47
  %copy.38 = bf16[16,32,128]{2,1,0:T(8,128)(2,1)} copy(%fusion.151)
  %bitcast.155 = bf16[16,4096]{1,0:T(8,128)(2,1)} bitcast(%copy.38)
  %fusion.157 = bf16[16,4096]{1,0:T(8,128)(2,1)} fusion(%bitcast.155, %wo, %i), kind=kOutput, calls=%fused_computation.31
  %fusion.190 = bf16[16,4096]{1,0:T(8,128)(2,1)} fusion(%fusion.157), kind=kLoop, calls=%fused_computation.90
  ROOT %tuple.1 = (s32[]{:T(128)}, bf16[16,4096]{1,0:T(8,128)(2,1)}, bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}, bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}) tuple(%i, %fusion.190, %wq, %wo)
}

%cond (state.1: (s32[], bf16[16,4096], bf16[16,4096,4096], bf16[16,4096,4096])) -> pred[] {
  %state.1 = (s32[]{:T(128)}, bf16[16,4096]{1,0:T(8,128)(2,1)}, bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}, bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i.1 = s32[]{:T(128)} get-tuple-element(%state.1), index=0
  %n = s32[]{:T(128)} constant(16)
  ROOT %lt = pred[]{:T(512)} compare(%i.1, %n), direction=LT
}

%fused_computation.512 (param_0.5: bf16[7,6144,8192]) -> (bf16[8192,6144], bf16[8192,6144]) {
  %param_0.5 = bf16[7,6144,8192]{2,1,0:T(8,128)(2,1)} parameter(0)
  %slice.70 = bf16[1,6144,8192]{2,1,0:T(8,128)(2,1)} slice(%param_0.5), slice={[0:1], [0:6144], [0:8192]}
  %bitcast.70 = bf16[8192,6144]{0,1:T(8,128)(2,1)} bitcast(%slice.70)
  %slice.71 = bf16[1,6144,8192]{2,1,0:T(8,128)(2,1)} slice(%param_0.5), slice={[1:2], [0:6144], [0:8192]}
  %bitcast.71 = bf16[8192,6144]{0,1:T(8,128)(2,1)} bitcast(%slice.71)
  ROOT %tuple.70 = (bf16[8192,6144]{0,1:T(8,128)(2,1)}, bf16[8192,6144]{0,1:T(8,128)(2,1)}) tuple(%bitcast.70, %bitcast.71)
}

ENTRY %main (wq.1: bf16[16,4096,4096], wo.1: bf16[16,4096,4096], x.1: bf16[16,4096], wq7: bf16[7,6144,8192], wk1: bf16[1,6144,1024]) -> bf16[16,4096] {
  %wq.1 = bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %wo.1 = bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)} parameter(1)
  %x.1 = bf16[16,4096]{1,0:T(8,128)(2,1)} parameter(2)
  %wq7 = bf16[7,6144,8192]{2,1,0:T(8,128)(2,1)} parameter(3)
  %wk1 = bf16[1,6144,1024]{2,1,0:T(8,128)(2,1)} parameter(4)
  %zero = s32[]{:T(128)} constant(0)
  %tuple.0 = (s32[]{:T(128)}, bf16[16,4096]{1,0:T(8,128)(2,1)}, bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}, bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}) tuple(%zero, %x.1, %wq.1, %wo.1)
  %while.9 = (s32[]{:T(128)}, bf16[16,4096]{1,0:T(8,128)(2,1)}, bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}, bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)}) while(%tuple.0), condition=%cond, body=%body
  %slice_bitcast_fusion = (bf16[8192,6144]{0,1:T(8,128)(2,1)}, bf16[8192,6144]{0,1:T(8,128)(2,1)}) fusion(%wq7), kind=kLoop, calls=%fused_computation.512
  %get-tuple-element.7 = bf16[8192,6144]{0,1:T(8,128)(2,1)} get-tuple-element(%slice_bitcast_fusion), index=0
  %copy.224 = bf16[8192,6144]{1,0:T(8,128)(2,1)} copy(%get-tuple-element.7)
  %bitcast.225 = bf16[1024,6144]{0,1:T(8,128)(2,1)} bitcast(%wk1)
  %transpose.225 = bf16[6144,1024]{0,1:T(8,128)(2,1)} transpose(%bitcast.225), dimensions={1,0}
  %copy-start.3 = (bf16[1,6144,1024]{2,1,0:T(8,128)(2,1)S(1)}, bf16[1,6144,1024]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%wk1)
  %copy-done.3 = bf16[1,6144,1024]{2,1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.3)
  ROOT %result = bf16[16,4096]{1,0:T(8,128)(2,1)} get-tuple-element(%while.9), index=1
}
"""


def _names(lines: list[str]) -> list[str]:
    return [line.split(": ")[1].split(" = ")[0] for line in lines]


@pytest.mark.parametrize("name,counts,why", [
    ("copy.41", True, "a copy to another layout, inside the layer loop"),
    ("constant_dynamic-slice_fusion.6", True, "a dynamic-slice alone in its fusion: scalar index arithmetic computes nothing"),
    ("slice_bitcast_fusion", True, "slices and bitcasts only, several outputs, outside any loop (an unrolled layer loop)"),
    ("copy.224", True, "a copy to another layout in the entry computation"),
    ("transpose.225", True, "a transpose that stands alone"),
    ("fusion.157", False, "the layer's slice is fused INTO the matmul: read where it lies"),
    ("fusion.39", False, "that slice's own fusion, nested in the matmul's"),
    ("fusion.151", False, "a matmul"),
    ("fusion.190", False, "a slice beside an add: the fusion computes"),
    ("copy.38", False, "a copy of 16 rows of activation: below the size"),
    ("copy-start.3", False, "XLA's asynchronous prefetch of an operand, not the program's own move"),
    ("copy-done.3", False, "the same prefetch's end"),
    ("bitcast.154", False, "a bitcast moves nothing"),
])  # fmt: skip
def test_what_counts_as_a_move_of_a_weights_size(name, counts, why):
    found = _names(program_moves(HLO, 12 * MIB))  # wk of the k-exaone cell, the smallest projection here
    assert (name in found) == counts, why


def test_the_size_is_of_what_the_move_writes():
    """A multi-output fusion by its largest output; the threshold is inclusive."""
    assert _names(program_moves(HLO, 8192 * 6144 * 2)) == ["copy.224", "slice_bitcast_fusion"]
    assert program_moves(HLO, 8192 * 6144 * 2 + 1) == []
    assert "copy.38" in _names(program_moves(HLO, 16 * 32 * 128 * 2))


def test_it_reads_the_whole_program_where_loop_moves_reads_the_loops():
    """``loop_moves`` is blind to the unrolled layers' moves in the entry computation:
    the reason this reader exists."""
    in_loops = _names(loop_moves(HLO, 12 * MIB))
    assert "copy.41" in in_loops and "copy.224" not in in_loops and "slice_bitcast_fusion" not in in_loops
    lines = program_moves(HLO, 12 * MIB)
    assert lines == sorted(lines) and all(": " in line and " = " in line for line in lines)
    assert [line for line in lines if line.startswith("main: ")] and [line for line in lines if line.startswith("body: ")]


def test_a_program_with_nothing_to_read_moves_nothing():
    assert program_moves("", 1) == []
    assert program_moves("HloModule empty\n", 1) == []
    assert moves_by_loop("HloModule empty\n", 1) == {}


def test_the_moves_are_shared_out_among_the_loops_with_what_each_writes_a_turn():
    """``moves_by_loop`` (PR 51): the same instructions, under the loop whose body runs them (named by the ``while``'s
    ``op_name``, the body's name where the text has none) with the turns its condition counts to, the rest under
    ``entry``; a fusion with several outputs by all it writes, where the threshold reads its largest."""
    by_loop = moves_by_loop(HLO, 12 * MIB)
    assert set(by_loop) == {"body", "entry"}
    assert by_loop["body"] == {"turns": 16, "moves": {"constant_dynamic-slice_fusion.6": 32 * MIB, "copy.41": 32 * MIB}}
    assert by_loop["entry"]["turns"] == 1
    assert by_loop["entry"]["moves"] == {"copy.224": 96 * MIB, "slice_bitcast_fusion": 192 * MIB, "transpose.225": 12 * MIB}
    flat = sorted(inst for found in by_loop.values() for inst in found["moves"])
    assert flat == sorted(_names(program_moves(HLO, 12 * MIB)))
    named = HLO.replace("condition=%cond, body=%body", 'condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(layers)/while"}')
    assert set(moves_by_loop(named, 12 * MIB)) == {"jit(step)/jvp(layers)/while", "entry"}



def test_the_rehearsal_script_prints_each_loops_moves_and_the_steps_total(capsys):
    """``scripts/rehearse_train_step.py::print_moves`` over the same text: a line a loop with MiB a turn, its turns
    and GiB a step, a line a move, and the step's total with what it would cost at the wire."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts", "rehearse_train_step.py")
    spec = importlib.util.spec_from_file_location("rehearse_train_step", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    script.print_moves(HLO, 12 * MIB)
    out = capsys.readouterr().out.splitlines()
    assert "  body: 64 MiB written a turn, 16 turns, 1.00 GiB a step" in out
    assert "  entry: 300 MiB written a turn, 1 turns, 0.29 GiB a step" in out
    assert any(line.split()[:2] == ["32", "MiB"] and "copy.41" in line for line in out)
    assert out[-1].startswith("  in all: 1.29 GiB written a step and as much read, 3.4 ms at 819 GB/s")
