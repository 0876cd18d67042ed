"""Read a compiled program's optimized HLO text for what a loop moves.

``jax.jit(f).lower(...).compile().as_text()`` is the program as the backend
will run it: whether a buffer carried through a ``lax.scan`` is updated where
it lies or sliced out, copied and written back is decided there and nowhere
in the jaxpr. :func:`loop_moves` is the check the serving programs are held to
(``tests/test_pools_in_carry.py``, ``scripts/rehearse_serve_cell.py``): no
pass over a layer's paged pool inside the layer loop. :func:`program_moves`
reads the whole program, loops or none: no layer's attention projection re-laid
in front of its matmul (``tests/test_paged_attention_kernel.py``);
:func:`moves_by_loop` shares those out among the program's outermost loops with
what each writes a turn (``scripts/rehearse_train_step.py``: the training
step's two layer loops).
"""

from __future__ import annotations

import math
import re

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_ARRAY = re.compile(r"\b([a-z]+)(\d+)?\[([\d,]*)\]")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")


def _sizes(type_text: str) -> list[int]:
    """Bytes of each array of an HLO type (a tuple's elements)."""
    sizes = []
    for dtype, bits, dims in _ARRAY.findall(type_text):
        item = 1 if dtype == "pred" else int(bits or 8) // 8
        sizes.append(item * math.prod(int(d) for d in dims.split(",") if d))
    return sizes


def _bytes(type_text: str) -> int:
    """The largest array of an HLO type (a tuple's largest element)."""
    return max(_sizes(type_text), default=0)


def _split(rest: str) -> tuple[str, str, str]:
    """``type opcode(operands), attributes`` -> (type, opcode, the rest)."""
    if rest.startswith("("):  # a tuple type: to its closing parenthesis
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        type_text, rest = rest[: end + 1], rest[end + 2 :]
    else:
        type_text, _, rest = rest.partition(" ")
    opcode, _, rest = rest.partition("(")
    return type_text, opcode, rest


def _parse(hlo_text: str) -> dict[str, dict[str, tuple[bool, str, str, list[str], str]]]:
    """computation -> instruction -> (is the root, type, opcode, operands, the rest of its line)."""
    computations: dict[str, dict[str, tuple[bool, str, str, list[str], str]]] = {}
    current = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = computations.setdefault(head.group(1), {})
        elif current is not None and (m := _INSTRUCTION.match(line)):
            type_text, opcode, rest = _split(m.group(2))
            operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
            current[m.group(1)] = ("ROOT " in line[: m.start(1)], type_text, opcode, operands, rest)
    return computations


def loop_moves(hlo_text: str, at_least_bytes: int) -> list[str]:
    """The ``copy``, ``dynamic-slice`` and ``dynamic-update-slice``
    instructions inside any ``while`` loop of the program (its body, and the
    fusions and loops the body calls) that make a buffer of ``at_least_bytes``
    or more: the result of a copy or a slice, the update of an update-slice
    (its result is the operand, updated where it lies). Inside a fusion only
    what the fusion hands out counts (its root, through bitcasts and tuples): a
    slice that feeds the fusion's own matmul is read where it lies. ->
    ``computation: instruction`` lines, empty when the loops move nothing that
    large."""
    computations = _parse(hlo_text)
    found, seen = [], set()

    def visit(name: str, fused: bool) -> None:
        if name in seen:
            return
        seen.add(name)
        body = computations.get(name, {})
        handed_out = set(body)
        if fused:
            handed_out, walk = set(), [inst for inst, (root, *_) in body.items() if root]
            while walk:
                inst = walk.pop()
                handed_out.add(inst)
                if inst in body and body[inst][2] in ("bitcast", "tuple"):
                    walk.extend(body[inst][3])
        for inst, (_, type_text, opcode, operands, rest) in body.items():
            if opcode == "fusion" and inst not in handed_out:
                continue  # its result stays inside this fusion
            for a, b in _CALLED.findall(rest):
                for c in filter(None, [a, *re.findall(r"[\w.\-]+", b)]):
                    visit(c, opcode == "fusion")
            if opcode in _MOVES and inst in handed_out:
                moved = type_text
                if opcode == "dynamic-update-slice" and len(operands) > 1 and operands[1] in body:
                    moved = body[operands[1]][1]
                if _bytes(moved) >= at_least_bytes:
                    found.append(f"{name}: {inst} = {type_text} {opcode}")

    for body in computations.values():
        for *_, opcode, _, rest in body.values():
            if opcode == "while" and (m := re.search(r"body=%?([\w.\-]+)", rest)):
                visit(m.group(1), False)
    return sorted(found)


_PURE_MOVES = frozenset(("copy", "slice", "dynamic-slice", "transpose"))
# what a fusion may hold beside its moves and still compute nothing
_CARRIES = frozenset(("parameter", "constant", "bitcast", "tuple", "get-tuple-element"))
_FUSED = re.compile(r"calls=%?([\w.\-]+)")


def _elements(type_text: str) -> int:
    """Elements of the largest array of an HLO type: 1 for a scalar."""
    return max([math.prod(int(d) for d in dims.split(",") if d) for _, _, dims in _ARRAY.findall(type_text)] or [1])


def program_moves(hlo_text: str, at_least_bytes: int) -> list[str]:
    """Every pure data movement of ``at_least_bytes`` or more **anywhere** in
    the program, loop or not (a layer loop short enough to be unrolled leaves
    its moves in the entry computation, where :func:`loop_moves` does not
    look): a ``copy``, ``slice``, ``dynamic-slice`` or ``transpose`` that stands
    alone, or a fusion of nothing but those and bitcasts (scalar index
    arithmetic aside), by the size of what it writes. A slice fused **into** a
    matmul's fusion is read where it lies and does not count; neither does
    XLA's asynchronous ``copy-start``/``copy-done`` prefetch of an operand
    into fast memory, which is no operation of the program's own. ->
    ``computation: instruction`` lines, empty when nothing that large moves."""
    computations = _parse(hlo_text)
    fusions = [rest for body in computations.values() for _, _, opcode, _, rest in body.values() if opcode == "fusion"]
    fused = {called for rest in fusions for called in _FUSED.findall(rest)}

    def only_moves(name: str) -> bool:
        opcodes = {op for _, type_text, op, _, _ in computations.get(name, {}).values() if _elements(type_text) > 1}
        return bool(opcodes & _PURE_MOVES) and opcodes <= _PURE_MOVES | _CARRIES

    found = []
    for name, body in computations.items():
        if name in fused:
            continue  # judged whole, at the fusion that calls it
        for inst, (_, type_text, opcode, _, rest) in body.items():
            if _bytes(type_text) < at_least_bytes:
                continue
            if opcode in _PURE_MOVES or (opcode == "fusion" and all(map(only_moves, _FUSED.findall(rest)))):
                found.append(f"{name}: {inst} = {type_text} {opcode}")
    return sorted(found)


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_lines(hlo_text: str) -> dict[str, str]:
    """instruction name -> its whole line (type, layout, ``op_name``, the backend's ``estimated_cycles``): what a
    reader of :func:`moves_by_loop` prints or compares beside a move's name."""
    return {m.group(1): line for line in hlo_text.splitlines() if (m := _INSTRUCTION.match(line))}


def moves_by_loop(hlo_text: str, at_least_bytes: int) -> dict[str, dict]:
    """:func:`program_moves` by where they run: ``{loop: {"turns": n, "moves": {instruction: bytes written, every array of a tuple}}}``,
    a loop named by the ``op_name`` of its ``while`` (``jit(step)/jvp(layers)/while`` is a scan over ``layers``
    under differentiation, ``.../transpose(jvp(layers))/while`` its backward), whatever runs in no loop under
    ``"entry"`` with one turn. A move inside a loop that a loop's body runs counts under the outer one, once a
    turn of the outer. ``turns`` is the integer the loop's condition compares against (a scan's length), None
    where it holds no single such constant."""
    computations = _parse(hlo_text)
    owner: dict[str, str] = {}
    turns: dict[str, int | None] = {"entry": 1}

    def claim(name: str, loop: str) -> None:
        if name in owner or name not in computations:
            return
        owner[name] = loop
        for *_, rest in computations[name].values():
            for a, b in _CALLED.findall(rest):
                for c in filter(None, [a, *re.findall(r"[\w.\-]+", b)]):
                    claim(c, loop)

    # the loops the entry computation runs, each with everything its body calls; then the entry's own
    for name in re.findall(r"^ENTRY %?([\w.\-]+) ", hlo_text, re.M):
        for *_, opcode, _, rest in computations.get(name, {}).values():
            body, cond = re.search(r"body=%?([\w.\-]+)", rest), re.search(r"condition=%?([\w.\-]+)", rest)
            if opcode == "while" and body and cond:
                loop = (_OP_NAME.search(rest) or body).group(1)
                bounds = [int(r.split(")")[0]) for *_, op, _, r in computations.get(cond.group(1), {}).values()
                          if op == "constant" and r.split(")")[0].isdigit()]  # fmt: skip
                turns[loop] = bounds[0] if len(bounds) == 1 else None
                claim(body.group(1), loop)
        claim(name, "entry")
    found: dict[str, dict] = {}
    for line in program_moves(hlo_text, at_least_bytes):
        where, _, inst = line.partition(": ")
        loop = owner.get(where, "entry")
        inst, _, type_text = inst.partition(" = ")
        found.setdefault(loop, {"turns": turns.get(loop), "moves": {}})["moves"][inst] = sum(_sizes(type_text.rpartition(" ")[0]))
    return found
