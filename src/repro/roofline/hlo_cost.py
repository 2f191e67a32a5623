"""Loop-aware cost model over compiled HLO text.

XLA's ``HloCostAnalysis`` (what ``compiled.cost_analysis()`` reports) counts
every computation ONCE — a ``while`` loop over n − 1 merges contributes a
single merge's FLOPs.  Since every engine here is such a loop, those
numbers undercount by orders of magnitude.  This module re-derives
loop-aware totals from the optimized HLO text itself:

* ``while`` ops carry ``backend_config={"known_trip_count":{"n":...}}`` —
  body/condition costs are multiplied through (nested loops compose);
* ``dot`` FLOPs = 2 · |out| · (contracted lhs dims), with operand shapes
  resolved from the per-computation symbol table;
* memory bytes are counted at instruction *boundaries* (operands+outputs
  of top-level ops; fusion interiors are skipped — a reasonable stand-in
  for fused HBM traffic);
* collective wire bytes use a ring model: all-gather ≈ out·(g−1)/g,
  all-reduce ≈ 2·out·(g−1)/g, reduce-scatter ≈ out·(g−1), all-to-all ≈
  out·(g−1)/g, collective-permute ≈ out — with the replica-group size g
  parsed per op, and loop multipliers applied (a per-trip all-reduce in
  a 126-trip loop counts 126×).

Everything here is per-DEVICE (the module is the SPMD-partitioned one).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

from repro.roofline import hw

_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->.*\{\s*$")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s*([a-z][\w\-]*)\((.*)$")
_PARAM = re.compile(r"([\w.\-]+):\s*([a-z]+\d*\[[\d,]*\])")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"(?:to_apply|condition|body)=%?([\w.\-]+)")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_ELTWISE_TRANSCENDENTAL = {"exponential", "tanh", "log", "rsqrt", "sqrt",
                           "power", "logistic", "sine", "cosine"}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * hw.DTYPE_BYTES.get(dtype, 0)


def _shape_elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


@dataclass
class Instr:
    name: str
    out_shapes: list            # [(dtype, dims_str)]
    opcode: str
    rest: str                   # everything after the opening paren
    line: str


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_by_op: dict = field(default_factory=dict)

    def __iadd__(self, o: "Cost"):
        self.flops += o.flops
        self.bytes += o.bytes
        self.coll_bytes += o.coll_bytes
        for k, v in o.coll_by_op.items():
            self.coll_by_op[k] = self.coll_by_op.get(k, 0.0) + v
        return self

    def scaled(self, m: float) -> "Cost":
        return Cost(self.flops * m, self.bytes * m, self.coll_bytes * m,
                    {k: v * m for k, v in self.coll_by_op.items()})


def parse_computations(text: str) -> tuple[dict, str]:
    """→ ({comp_name: (instrs, param_shapes)}, entry_name)."""
    comps: dict[str, tuple[list[Instr], dict]] = {}
    entry = None
    cur: list[Instr] | None = None
    cur_params: dict | None = None
    cur_name = None
    for raw in text.splitlines():
        m = _COMP_HDR.match(raw)
        if m:
            cur_name = m.group(2)
            cur = []
            cur_params = {}
            for pname, ptype in _PARAM.findall(m.group(3)):
                sm = _SHAPE.findall(ptype)
                if sm:
                    cur_params[pname] = sm[0]
            comps[cur_name] = (cur, cur_params)
            if m.group(1):
                entry = cur_name
            continue
        if raw.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        im = _INSTR.match(raw)
        if not im:
            continue
        name, out_t, opcode, rest = im.groups()
        cur.append(Instr(name, _SHAPE.findall(out_t), opcode, rest, raw))
    return comps, entry or "main"


def _group_size(line: str, n_partitions: int) -> int:
    m = _GROUPS_LIST.search(line)
    if m:
        return max(1, len(m.group(1).split(",")))
    m = _GROUPS_IOTA.search(line)
    if m:
        return max(1, int(m.group(2)))
    return max(1, n_partitions)


def _wire_bytes(op: str, out_bytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    if op == "all-gather":
        return out_bytes * (g - 1) / g
    if op == "all-reduce":
        return 2.0 * out_bytes * (g - 1) / g
    if op == "reduce-scatter":
        return float(out_bytes) * (g - 1)
    if op == "all-to-all":
        return out_bytes * (g - 1) / g
    return float(out_bytes)  # collective-permute


class HloCost:
    def __init__(self, text: str, n_partitions: int = 1):
        self.comps, self.entry = parse_computations(text)
        self.n_partitions = n_partitions
        self._memo: dict[str, Cost] = {}

    # ---- per-instruction ----------------------------------------------------

    def _sym(self, comp_name: str) -> dict:
        instrs, params = self.comps[comp_name]
        table = dict(params)
        for i in instrs:
            if i.out_shapes:
                table[i.name] = i.out_shapes[0]
            # tuple-typed: keep all for gte? gte lines carry own types.
        return table

    def _producers(self, comp_name: str) -> dict:
        return {i.name: i for i in self.comps[comp_name][0]}

    def _is_legalized_bf16(self, comp_name: str, i: Instr, sym: dict) -> bool:
        """True when a collective's f32 operand is a CPU-legalization
        upconvert of a bf16 value (the CPU backend has no native bf16 and
        float-normalizes before collectives; on the TPU target these ops
        move bf16).  Detected by a convert-producer whose source is bf16."""
        if not i.out_shapes or i.out_shapes[0][0] != "f32":
            return False
        prods = self._producers(comp_name)
        paren = i.rest.split(")")[0]
        for ref in re.findall(r"%([\w.\-]+)", paren):
            p = prods.get(ref)
            if p is None:
                continue
            looks_convert = (p.opcode == "convert"
                             or "convert" in p.name
                             or (p.opcode == "fusion" and "convert" in p.line))
            if looks_convert and "bf16[" in p.line:
                return True
            # one more hop through copies
            if p.opcode in ("copy", "bitcast") :
                inner = re.findall(r"%([\w.\-]+)", p.rest.split(")")[0])
                for r2 in inner:
                    p2 = prods.get(r2)
                    if p2 is not None and ("convert" in p2.name
                                           or p2.opcode == "convert") \
                            and "bf16[" in p2.line:
                        return True
        return False

    def _instr_cost(self, comp_name: str, i: Instr, sym: dict) -> Cost:
        c = Cost()
        op = i.opcode
        out_b = sum(_shape_bytes(dt, dd) for dt, dd in i.out_shapes)
        out_e = sum(_shape_elems(dd) for _, dd in i.out_shapes)

        # ---- called computations ------------------------------------------
        if op == "while":
            trip = 1
            tm = _TRIP.search(i.line)
            if tm:
                trip = int(tm.group(1))
            for sub in _TO_APPLY.findall(i.line):
                c += self.cost_of(sub).scaled(trip)
            return c
        if op == "fusion":
            cm = _CALLS.search(i.line)
            if cm:
                sub = self.cost_of(cm.group(1))
                c.flops += sub.flops          # interior bytes skipped (fused)
                c.coll_bytes += sub.coll_bytes
            c.bytes += out_b + self._operand_bytes(i, sym)
            return c
        if op in ("call", "async-start", "custom-call"):
            cm = _CALLS.search(i.line) or _TO_APPLY.search(i.line)
            if cm:
                c += self.cost_of(cm.group(1))
            c.bytes += out_b + self._operand_bytes(i, sym)
            return c
        if op == "conditional":
            subs = [self.cost_of(s) for s in _TO_APPLY.findall(i.line)]
            if subs:
                best = max(subs, key=lambda s: s.flops)
                c += best
            return c

        # ---- collectives -----------------------------------------------------
        base_op = op[:-6] if op.endswith("-start") else op
        if base_op in _COLLECTIVES:
            if op.endswith("-done"):
                return c
            g = _group_size(i.line, self.n_partitions)
            eff_b = out_b
            if self._is_legalized_bf16(comp_name, i, sym):
                eff_b = out_b // 2          # TPU target moves bf16, not f32
            w = _wire_bytes(base_op, eff_b, g)
            c.coll_bytes += w
            c.coll_by_op[base_op] = c.coll_by_op.get(base_op, 0.0) + w
            c.bytes += eff_b
            return c

        # ---- compute ---------------------------------------------------------
        if op == "dot":
            # contraction size from lhs shape + lhs_contracting_dims.  Newer
            # XLA prints operands with inline types (``dot(f32[64,256]{1,0}
            # %x, ...)``) — take the first inline shape; older text prints
            # bare ``%x`` refs — fall back to the symbol table.
            paren = i.rest.split(")")[0]
            inline = _SHAPE.findall(paren)
            if inline:
                lhs = inline[0]
            else:
                lm = re.search(r"%([\w.\-]+)", paren)
                lhs = sym.get(lm.group(1)) if lm else None
            kdim = 1
            mm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", i.line)
            if lhs and mm and mm.group(1):
                dims = lhs[1].split(",") if lhs[1] else []
                for idx in mm.group(1).split(","):
                    ii = int(idx)
                    if ii < len(dims):
                        kdim *= int(dims[ii])
            # batch dims are part of out; contraction covers the rest
            c.flops += 2.0 * out_e * kdim
        elif op == "convolution":
            c.flops += 2.0 * out_e  # lower bound; no convs on our hot paths
        elif op in _ELTWISE_TRANSCENDENTAL:
            c.flops += float(out_e)
        elif op in ("add", "multiply", "subtract", "divide", "maximum",
                    "minimum", "compare", "select"):
            c.flops += float(out_e)

        if op not in ("parameter", "get-tuple-element", "tuple", "bitcast",
                      "constant"):
            c.bytes += out_b + self._operand_bytes(i, sym)
        return c

    def _operand_bytes(self, i: Instr, sym: dict) -> int:
        total = 0
        paren = i.rest.split(")")[0]
        for ref in re.findall(r"%([\w.\-]+)", paren):
            sh = sym.get(ref)
            if sh:
                total += _shape_bytes(sh[0], sh[1])
        return total

    # ---- per-computation -----------------------------------------------------

    def cost_of(self, comp_name: str) -> Cost:
        if comp_name in self._memo:
            return self._memo[comp_name]
        if comp_name not in self.comps:
            return Cost()
        self._memo[comp_name] = Cost()  # cycle guard
        sym = self._sym(comp_name)
        total = Cost()
        for i in self.comps[comp_name][0]:
            total += self._instr_cost(comp_name, i, sym)
        self._memo[comp_name] = total
        return total

    def total(self) -> Cost:
        return self.cost_of(self.entry)
