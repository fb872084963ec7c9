"""HLO-text cost model with while-loop trip-count scaling.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified
by calibration in tests/test_hlo_cost.py), which silently drops ~L× the
FLOPs of a scanned L-layer model and all collectives inside the scan.
This module parses the optimized (post-SPMD, per-device) HLO text and
walks the call graph with multipliers:

- while loops: trip count extracted from the condition's comparison
  constant (lax.scan/fori_loop always lower to ``compare(iv, const)``),
- fusions / calls / reduces: descend with unchanged multiplier,
- conditionals: each branch weighted 1/num_branches (our conditional
  branches are FLOP-identical — see dryrun notes),

producing:
- ``flops``   : dot FLOPs (2*M*N*K, batch-aware) + elementwise FLOPs,
- ``bytes``   : HBM traffic proxy — operand+result bytes of *top-level*
  (non-fused-interior) instructions, trip-scaled,
- ``collectives`` : per-type trip-scaled operand bytes + counts.

Everything is per-device (the HLO is the per-device SPMD module).
"""

from __future__ import annotations

import dataclasses
import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
    "token": 0, "opaque": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (it may be ``None``
    for a backend without a cost model)."""
    return dict(compiled.cost_analysis() or {})


_ELTWISE_1 = {"add", "subtract", "multiply", "divide", "maximum", "minimum",
              "and", "or", "xor", "compare", "select", "negate", "abs",
              "floor", "ceil", "round-nearest-afz", "sign"}
_ELTWISE_T = {"exponential", "tanh", "log", "rsqrt", "sqrt", "logistic",
              "power", "sine", "cosine", "erf", "exponential-minus-one",
              "log-plus-one", "cbrt", "atan2"}  # transcendental ~ 4 flops

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?\s*->.*{")
_SHAPE_TOK = re.compile(r"(\w[\w\d]*)\[([\d,]*)\]")
_ASSIGN_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE_RE = re.compile(r"^([\w\-]+)\((.*)$")


def _split_instr(line: str):
    """(name, shape_str, opcode, rest) or None.

    Handles tuple result shapes containing /*index=N*/ comments by
    matching the balanced outer parens of the shape.
    """
    m = _ASSIGN_RE.match(line)
    if not m:
        return None
    name, rest = m.group(1), m.group(2).lstrip()
    if rest.startswith("("):
        depth = 0
        end = -1
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        shape, tail = rest[:end + 1], rest[end + 1:].lstrip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        shape, tail = rest[:sp], rest[sp + 1:].lstrip()
    m2 = _OPCODE_RE.match(tail)
    if not m2:
        return None
    return name, shape, m2.group(1), m2.group(2)
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations)=\{?%?"
    r"([\w.\-{}%, ]+?)\}?(?:,|$| )")


@dataclasses.dataclass
class Instr:
    name: str
    shape_str: str
    opcode: str
    rest: str  # operands + attributes (raw)

    @property
    def result_dims(self):
        shapes = _SHAPE_TOK.findall(self.shape_str)
        return shapes

    def result_bytes(self) -> int:
        return _shape_bytes(self.shape_str)

    def result_elems(self) -> int:
        total = 0
        for _, dims in _SHAPE_TOK.findall(self.shape_str):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n
        return total


def _shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_TOK.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _first_shape_dims(s: str):
    m = _SHAPE_TOK.search(s)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


def parse_hlo(text: str) -> dict:
    """computation name -> list[Instr]."""
    comps: dict = {}
    cur = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and "{" in line and "->" in line:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)", line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = []
            continue
        if line.strip() == "}":
            continue
        if cur is None:
            continue
        parsed = _split_instr(line)
        if parsed:
            name, shape_str, opcode, rest = parsed
            comps[cur].append(Instr(name, shape_str.strip(), opcode, rest))
    return comps


def _operand_names(rest: str) -> list:
    # operands end at the first ")" at depth 0 of the leading parens;
    # depth must track [] and {} too — operand types like
    # ``f32[8,8]{1,0}`` contain commas that are NOT operand separators
    ops = []
    depth = 0
    buf = ""
    for ch in rest:
        if ch in "([{":
            depth += 1
            buf += ch
        elif ch == ")" and depth == 0:
            break
        elif ch in ")]}":
            depth -= 1
            buf += ch
        elif ch == "," and depth == 0:
            ops.append(buf)
            buf = ""
        else:
            buf += ch
    if buf.strip():
        ops.append(buf)
    names = []
    for o in ops:
        o = o.strip()
        # strip inline types: "f32[8,16] %foo.1" -> foo.1
        m = re.search(r"%([\w.\-]+)\s*$", o)
        if m:
            names.append(m.group(1))
        elif o and not o.startswith("("):
            names.append(o.split(" ")[-1].lstrip("%"))
    return names


def _called_comps(rest: str) -> list:
    out = []
    for m in _CALLED.finditer(rest):
        blob = m.group(1)
        for piece in blob.split(","):
            piece = piece.strip().strip("{}").lstrip("%").strip()
            if piece:
                out.append(piece)
    return out


def _dot_flops(instr: Instr, shapes: dict) -> float:
    """2*M*N*K*batch from result shape + contracting/batch dims."""
    res = _first_shape_dims(instr.shape_str)
    if res is None:
        return 0.0
    # lhs operand name
    opnames = _operand_names(instr.rest)
    if not opnames:
        return 0.0
    lhs_dims = shapes.get(opnames[0])
    if lhs_dims is None:
        return 0.0
    cm = re.search(r"lhs_contracting_dims=\{([\d, ]*)\}", instr.rest)
    contract = [int(x) for x in cm.group(1).split(",")] if cm and \
        cm.group(1).strip() else []
    k = 1
    for d in contract:
        if d < len(lhs_dims):
            k *= lhs_dims[d]
    n_res = 1
    for d in res:
        n_res *= d
    return 2.0 * n_res * max(k, 1)


_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"')


def _while_trip(while_rest: str, cond_instrs: list) -> int:
    """Trip count: XLA's known_trip_count backend_config, else the
    condition computation's comparison constant."""
    m = _TRIP_RE.search(while_rest)
    if m:
        return max(int(m.group(1)), 1)
    consts = []
    for ins in cond_instrs:
        if ins.opcode == "constant":
            mm = re.search(r"^(-?\d+)\)", ins.rest)
            if mm:
                consts.append(int(mm.group(1)))
    if consts:
        return max(max(consts), 1)
    return 1


def analyze(text: str) -> dict:
    comps = parse_hlo(text)
    entry = None
    for name in comps:
        if name.startswith("main") or entry is None:
            if name.startswith("main"):
                entry = name
    if entry is None:
        entry = next(iter(comps))

    # global shape registry (names are unique across the module in
    # practice; collisions resolve to last writer which is fine for dims)
    shapes: dict = {}
    for instrs in comps.values():
        for ins in instrs:
            dims = _first_shape_dims(ins.shape_str)
            if dims is not None:
                shapes[ins.name] = dims

    flops = 0.0
    bytes_hbm = 0.0
    coll_bytes = {c: 0.0 for c in _COLLECTIVES}
    coll_counts = {c: 0.0 for c in _COLLECTIVES}
    warnings: list = []

    def size_of(name: str, comp_instrs_by_name: dict) -> int:
        ins = comp_instrs_by_name.get(name)
        if ins is None:
            return 0
        return ins.result_bytes()

    # ---- fusion parameter access model -----------------------------------
    # A fusion operand that is only consumed by (dynamic-)slice / gather
    # ops inside the fused computation is read partially: count the slice
    # results, not the full parameter (this is how stacked layer weights
    # are accessed inside lax.scan bodies — without this rule the memory
    # term overcounts by ~num_layers x).
    _SLICING = ("dynamic-slice", "slice", "gather")

    def fusion_param_bytes(fcomp: str) -> dict:
        """param index -> bytes actually read (None = full)."""
        out: dict = {}
        instrs = comps.get(fcomp, [])
        params = {}
        for ins in instrs:
            if ins.opcode == "parameter":
                m = re.search(r"^(\d+)\)", ins.rest)
                if m:
                    params[ins.name] = int(m.group(1))
        # consumers per param
        consume: dict = {name: [] for name in params}
        for ins in instrs:
            for opn in _operand_names(ins.rest):
                if opn in consume:
                    consume[opn].append(ins)
        for pname, idx in params.items():
            users = consume[pname]
            if users and all(u.opcode in _SLICING for u in users):
                out[idx] = sum(u.result_bytes() for u in users)
        return out

    _fusion_cache: dict = {}

    def instr_bytes(ins: Instr, by_name: dict) -> float:
        """HBM traffic model for one top-level instruction."""
        op = ins.opcode
        if op in ("parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast"):
            return 0.0
        if op in _SLICING:
            return 2.0 * ins.result_bytes()  # read slice + write result
        if op == "dynamic-update-slice":
            opnames = _operand_names(ins.rest)
            upd = size_of(opnames[1], by_name) if len(opnames) > 1 else 0
            return 2.0 * upd  # read + write the updated region (aliased)
        b = float(ins.result_bytes())
        if op == "fusion":
            called = _called_comps(ins.rest)
            pb = _fusion_cache.get(called[0]) if called else None
            if called and pb is None:
                pb = fusion_param_bytes(called[0])
                _fusion_cache[called[0]] = pb
            for i, opn in enumerate(_operand_names(ins.rest)):
                if pb is not None and i in pb:
                    b += pb[i]
                else:
                    b += size_of(opn, by_name)
            return b
        for opn in _operand_names(ins.rest):
            b += size_of(opn, by_name)
        return b

    def visit(comp: str, mult: float, top_level: bool, seen: tuple):
        nonlocal flops, bytes_hbm
        if comp not in comps or comp in seen:
            return
        instrs = comps[comp]
        by_name = {i.name: i for i in instrs}
        for ins in instrs:
            op = ins.opcode
            # --- flops ---
            if op == "dot":
                flops += mult * _dot_flops(ins, shapes)
            elif op in _ELTWISE_1:
                flops += mult * ins.result_elems()
            elif op in _ELTWISE_T:
                flops += mult * 4 * ins.result_elems()
            # --- bytes (top-level only; fusion interiors don't touch HBM)
            if top_level:
                bytes_hbm += mult * instr_bytes(ins, by_name)
            # --- collectives ---
            base = op.replace("-start", "")
            if base in _COLLECTIVES and not op.endswith("-done"):
                total = 0
                for opn in _operand_names(ins.rest):
                    total += size_of(opn, by_name)
                if total == 0:
                    total = ins.result_bytes()
                coll_bytes[base] += mult * total
                coll_counts[base] += mult
            # --- descend ---
            called = _called_comps(ins.rest)
            if op == "while":
                body = cond = None
                bm = re.search(r"body=%?([\w.\-]+)", ins.rest)
                cm = re.search(r"condition=%?([\w.\-]+)", ins.rest)
                if bm:
                    body = bm.group(1)
                if cm:
                    cond = cm.group(1)
                trips = _while_trip(ins.rest, comps.get(cond, []))
                if body:
                    visit(body, mult * trips, top_level, seen + (comp,))
            elif op == "conditional":
                branches = [c for c in called]
                w = 1.0 / max(len(branches), 1)
                for b in branches:
                    visit(b, mult * w, top_level, seen + (comp,))
            elif op == "fusion":
                for c in called:
                    visit(c, mult, False, seen + (comp,))
            elif called and op in ("call", "custom-call", "reduce",
                                   "reduce-window", "scatter", "sort",
                                   "map", "select-and-scatter",
                                   "async-start"):
                for c in called:
                    visit(c, mult, False, seen + (comp,))

    visit(entry, 1.0, True, ())
    return {
        "flops": flops,
        "bytes_hbm": bytes_hbm,
        "collective_bytes": {k: int(v) for k, v in coll_bytes.items()},
        "collective_counts": {k: round(v, 2)
                              for k, v in coll_counts.items()},
        "collective_total_bytes": int(sum(coll_bytes.values())),
        "warnings": warnings,
    }
