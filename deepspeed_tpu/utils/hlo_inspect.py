"""Collective-op inspection of compiled HLO text.

The wire-truth of the compressed collectives (``runtime/comm/compressed.py``
/ ``quantized.py``) is a property of the *compiled program*: the claim
"the 1-bit exchange carries uint8" is proven by finding the all-gather in
the optimized HLO and reading its operand type, not by trusting the Python
that requested it. This module is that reader — shared by the HLO
regression tests (``tests/unit/test_comm_quantization.py``) and the
telemetry step-cost collector (``telemetry/jit_watch.py``), so the test
and the reported wire bytes can never disagree on parsing.
"""

import re
from typing import Dict, List, Optional, Sequence, Tuple

COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                  "reduce-scatter", "collective-permute")

# `u8[8,513]{1,0}` — dtype + dims (scalar shapes print as `f32[]`); the
# TPU compiler appends a tiling to the layout, `{1,0:T(8,128)(2,1)S(1)}`
_SHAPE_RE = re.compile(r"\b(pred|[sufc]\d+|bf16|f8\w+)\[([\d,]*)\]")
# `  ROOT %all-reduce.3 = <type> all-reduce(%fusion.1), channel_id=...`:
# the installed XLA prints operands by NAME only, so an operand's shape is
# the result type of the instruction that defines it.
# (pre-optimization text, ``lowered.as_text(dialect="hlo")``, drops the `%`)
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_NAME_RE = re.compile(r"%?([\w.\-]+)\s*(?:,|$)")
_OP_RE = re.compile(
    r"\s*(" + "|".join(COLLECTIVE_OPS) + r")(?:-start)?\(")

# the two spellings XLA prints for replica_groups:
#   literal    `replica_groups={{0,1},{2,3}}`
#   iota form  `replica_groups=[2,2]<=[4]` / `[4,2]<=[2,4]T(1,0)`
_GROUPS_LITERAL_RE = re.compile(
    r"replica_groups=\{(\{\d+(?:,\d+)*\}(?:,\{\d+(?:,\d+)*\})*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+(?:,\d+)*)\]"
    r"(?:T\((\d+(?:,\d+)*)\))?")


_PAIRS_RE = re.compile(
    r"source_target_pairs=\{(\{\d+,\d+\}(?:,\{\d+,\d+\})*)\}")


def parse_source_target_pairs(line: str) -> Optional[List[Tuple[int, int]]]:
    """The ``(source, target)`` pairs of one ``collective-permute`` line,
    ``None`` for a line that carries none."""
    m = _PAIRS_RE.search(line)
    if not m:
        return None
    return [tuple(int(x) for x in pair.split(","))
            for pair in m.group(1)[1:-1].split("},{")]


def parse_replica_groups(line: str) -> Optional[List[List[int]]]:
    """The replica groups of one HLO collective line as a list of member
    lists, or ``None`` when the line carries no ``replica_groups=``.

    Handles both the literal form and the iota ("v2") form — the latter
    means: take ``iota(prod(dims))``, reshape to ``dims``, transpose by
    the optional ``T(perm)``, flatten, and cut into ``num_groups`` rows of
    ``group_size``. That is exactly how GSPMD prints subgroup collectives
    over the non-major mesh axes, so a parser without it would misread
    every fsdp/tp-axis collective on a multi-axis mesh."""
    m = _GROUPS_LITERAL_RE.search(line)
    if m:
        return [[int(x) for x in g.split(",")]
                for g in m.group(1)[1:-1].split("},{")]
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        num_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = list(range(int(_prod(dims))))
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = _transpose_flat(ids, dims, perm)
        return [ids[i * group_size:(i + 1) * group_size]
                for i in range(num_groups)]
    return None


def _prod(xs) -> int:
    n = 1
    for x in xs:
        n *= int(x)
    return n


def _transpose_flat(ids: List[int], dims: List[int],
                    perm: List[int]) -> List[int]:
    """Flattened row-major transpose of ``ids`` viewed as shape ``dims``."""
    strides = [0] * len(dims)
    s = 1
    for i in reversed(range(len(dims))):
        strides[i] = s
        s *= dims[i]
    out_dims = [dims[p] for p in perm]
    out = []
    idx = [0] * len(out_dims)
    total = _prod(dims)
    for _ in range(total):
        src = sum(idx[j] * strides[perm[j]] for j in range(len(perm)))
        out.append(ids[src])
        for j in reversed(range(len(out_dims))):
            idx[j] += 1
            if idx[j] < out_dims[j]:
                break
            idx[j] = 0
    return out


def _dtype_bits(dtype: str) -> int:
    """Bit width from the HLO dtype name: the trailing digits ARE the
    width (s4 → 4, u8 → 8, f32 → 32, bf16 → 16), so sub-byte types a
    future int4 wire would put in a collective never KeyError here.
    ``pred`` packs as one byte in HLO buffers."""
    if dtype == "pred":
        return 8
    m = re.search(r"(\d+)$", dtype)
    return int(m.group(1)) if m else 32


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return (n * _dtype_bits(dtype) + 7) // 8


def _balanced(text: str, open_at: int) -> int:
    """Index of the ``)`` closing the ``(`` at ``text[open_at]``."""
    depth = 0
    for i in range(open_at, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(text) - 1


def _result_type(rest: str) -> str:
    """The type that opens an instruction's right-hand side: one array
    type (no blanks, though its tiling holds parentheses) or a
    parenthesised tuple of them."""
    if rest.startswith("("):
        return rest[:_balanced(rest, 0) + 1]
    return rest.split(None, 1)[0] if rest else ""


def parse_collectives(hlo_text: str) -> List[Dict]:
    """Collective ops of a compiled-HLO module as
    ``{op, operands: [(dtype, bytes)], operand_dims, operand_bytes, groups,
    group_size, pairs}`` dicts (``operand_dims``: each operand's dims as
    a tuple; ``pairs``: a ``collective-permute``'s source-target pairs).

    ``operand_bytes`` is the per-member contribution each device feeds the
    collective — the honest wire-size proxy (an all-gather *result* is
    world× larger but each member only sends its operand).

    Instruction names repeat across computations (``%param_0`` of every
    fusion) but a definition precedes its uses inside one computation, so
    one pass that keeps the latest definition resolves every operand.
    """
    out = []
    defined: Dict[str, List[Tuple[str, str]]] = {}
    for line in hlo_text.splitlines():
        definition = _DEF_RE.match(line)
        if not definition:
            continue
        rest = line[definition.end():]
        rtype = _result_type(rest)
        defined[definition.group(1)] = _SHAPE_RE.findall(rtype)
        m = _OP_RE.match(rest, len(rtype))
        if not m:
            continue
        args = rest[m.end():_balanced(rest, m.end() - 1)]
        shapes = [s for name in _NAME_RE.findall(args)
                  for s in defined.get(name, ())]
        operands = [(d, _shape_bytes(d, dims)) for d, dims in shapes]
        groups = parse_replica_groups(line)
        out.append({
            "op": m.group(1),
            "operands": operands,
            "operand_dims": [tuple(int(x) for x in dims.split(",") if x)
                             for _, dims in shapes],
            "operand_bytes": sum(b for _, b in operands),
            "groups": groups,
            "group_size": len(groups[0]) if groups else None,
            "pairs": parse_source_target_pairs(line),
        })
    return out


def received_bytes(coll: Dict) -> int:
    """Per-member *received* wire bytes of one parsed collective:
    ``operand_bytes x (group_size - 1)``. This is the honest comparator
    when group sizes differ — a hierarchical all-gather ships a LARGER
    operand over a SMALLER group, so comparing operand bytes alone would
    call the cheaper program more expensive. A collective with no (or
    trivial) replica groups costs zero wire."""
    g = coll.get("group_size") or 1
    return coll["operand_bytes"] * max(0, g - 1)


def attribute_collectives(hlo_text: str,
                          axis_sizes: Sequence[Tuple[str, int]],
                          min_bytes: int = 0) -> Dict[str, int]:
    """Per-mesh-axis wire attribution of a compiled module:
    ``{"data": bytes, "fsdp": bytes, "data+fsdp": bytes, ...}`` of
    per-member :func:`received_bytes`, keyed by the '+'-joined (mesh-order)
    axes each collective's replica groups span.

    ``axis_sizes`` is the mesh's ``(axis, size)`` list in major-to-minor
    order — device id = row-major multi-index, the same convention
    ``Mesh(devices.reshape(sizes), names)`` uses. A collective whose
    groups vary a coordinate on some axis spans that axis; one with no
    replica_groups (single-device or full-world default) is keyed
    ``"all"``."""
    names = [a for a, _ in axis_sizes]
    sizes = [int(s) for _, s in axis_sizes]
    strides = [0] * len(sizes)
    s = 1
    for i in reversed(range(len(sizes))):
        strides[i] = s
        s *= sizes[i]

    def coords(dev: int) -> Tuple[int, ...]:
        return tuple((dev // strides[i]) % sizes[i]
                     for i in range(len(sizes)))

    out: Dict[str, int] = {}
    for c in parse_collectives(hlo_text):
        if c["operand_bytes"] < min_bytes:
            continue
        groups = c.get("groups")
        if not groups:
            key = "all"
        else:
            varying = set()
            for g in groups:
                cs = [coords(d) for d in g]
                for i in range(len(sizes)):
                    if len({x[i] for x in cs}) > 1:
                        varying.add(i)
            key = "+".join(names[i] for i in sorted(varying)) or "none"
        out[key] = out.get(key, 0) + received_bytes(c)
    return out


def collective_operand_bytes(hlo_text: str,
                             ops: Optional[Sequence[str]] = None,
                             min_bytes: int = 0) -> int:
    """Total per-member collective operand bytes in the module; ``ops``
    restricts to op names, ``min_bytes`` skips control-sized collectives
    (loss scalars, flags)."""
    return sum(c["operand_bytes"] for c in parse_collectives(hlo_text)
               if (ops is None or c["op"] in ops)
               and c["operand_bytes"] >= min_bytes)


def collective_operand_dtypes(hlo_text: str, min_bytes: int = 0):
    """Set of operand dtypes appearing in collectives >= ``min_bytes``."""
    dtypes = set()
    for c in parse_collectives(hlo_text):
        if c["operand_bytes"] >= min_bytes:
            dtypes.update(d for d, _ in c["operands"])
    return dtypes


# ----------------------------------------------------------------------
# per-step counts: a collective inside a `while` body runs once a trip
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_CALLEE_RE = re.compile(
    r"\b(body|condition|to_apply|calls)=%?([\w.\-]+)")
_CALLEE_LIST_RE = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_TRIPS_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _computations(hlo_text: str):
    """``(entry name, {name: [lines]})`` of a printed HLO module."""
    comps: Dict[str, List[str]] = {}
    entry, name = None, None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m:
            name = m.group(2)
            comps[name] = []
            if m.group(1):
                entry = name
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return entry, comps


def collectives_per_step(hlo_text: str) -> List[Dict]:
    """:func:`parse_collectives`, each entry with ``trips``: how many times
    one execution of the module runs it, the product of the known trip
    counts of the ``while`` loops around it (1 outside every loop), and
    ``computation``, the one it is printed in. A loop whose trip count
    the compiler did not print counts as one trip."""
    entry, comps = _computations(hlo_text)
    trips: Dict[str, int] = {}

    def visit(name: str, mult: int):
        if name not in comps:
            return
        trips[name] = trips.get(name, 0) + mult
        for line in comps[name]:
            n = _TRIPS_RE.search(line)
            for kind, callee in _CALLEE_RE.findall(line):
                loop = kind in ("body", "condition") and n
                visit(callee, mult * (int(n.group(1)) if loop else 1))
            for group in _CALLEE_LIST_RE.findall(line):
                for callee in _NAME_RE.findall(group):
                    visit(callee, mult)

    if entry is not None:
        visit(entry, 1)
    out = []
    for name, lines in comps.items():
        for c in parse_collectives("\n".join(lines)):
            out.append({**c, "computation": name,
                        "trips": trips.get(name, 1)})
    return out
