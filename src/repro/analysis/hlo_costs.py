"""Trip-count-aware static cost analysis of optimized (partitioned) HLO.

``compiled.cost_analysis()`` counts every while-loop body ONCE (verified in
EXPERIMENTS.md §Roofline-methodology), which under-counts a scanned L-layer
transformer by ~L.  This module re-derives the three roofline inputs from
``compiled.as_text()`` with loop multipliers:

  * parse computations and their instructions (result shapes from defs),
  * read each ``while`` op's ``backend_config known_trip_count``,
  * propagate multipliers through the call graph
    (body/condition/calls/to_apply),
  * FLOPs   = sum over ``dot`` ops of 2 * prod(result) * prod(contracting)
              x multiplier  (+ convolutions via the same formula on their
              metadata when present),
  * bytes   = sum over materializing ops of (operands + result) bytes
              x multiplier — the fusion-boundary traffic proxy,
  * collective bytes = result bytes of collective ops x multiplier
              (all-reduce weighted 2x: ring = reduce-scatter + all-gather).
"""
from __future__ import annotations

import dataclasses
import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "token": 0, "opaque": 0,
    "u4": 1, "s4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*->.*\{\s*$")
# a tuple result type may nest one level of parens: TPU layouts spell
# tiles as ``{1,0:T(8,128)(2,1)}``
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*"
    r"(\((?:[^()]|\([^()]*\))*\)|\w+\[[\d,]*\]\S*)\s+"
    r"([\w\-]+)\((.*)$")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_CALL_ONE_RE = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%([\w\.\-]+)")
_CALL_LIST_RE = re.compile(r"(?:calls|branch_computations)=\{([^}]*)\}")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"')

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# ops that don't touch HBM / carry no payload of their own
_SKIP_BYTES = {"parameter", "constant", "get-tuple-element", "tuple",
               "bitcast", "while", "conditional", "call", "after-all",
               "partition-id", "replica-id", "iota"}


def _shape_elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _type_bytes(t: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(t):
        nb = _DTYPE_BYTES.get(dt, 0)
        total += _shape_elems(dims) * nb
    return total


def _type_elems(t: str) -> int:
    m = _SHAPE_RE.search(t)
    return _shape_elems(m.group(2)) if m else 0


@dataclasses.dataclass
class Instr:
    name: str
    rtype: str
    opcode: str
    rest: str        # operand list + attrs (raw tail of the line)

    def operand_names(self) -> list:
        # operands come before the first "),": cut at the matching paren —
        # heuristically the first ")," or trailing ")"
        depth = 0
        for i, ch in enumerate(self.rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    head = self.rest[:i]
                    break
                depth -= 1
        else:
            head = self.rest
        return _OPERAND_RE.findall(head)

    def called_computations(self) -> list:
        out = [m.group(1) for m in _CALL_ONE_RE.finditer(self.rest)]
        for m in _CALL_LIST_RE.finditer(self.rest):
            out.extend(c.strip().lstrip("%") for c in m.group(1).split(","))
        return out

    def trip_count(self) -> int | None:
        m = _TRIP_RE.search(self.rest)
        return int(m.group(1)) if m else None


def parse_hlo(text: str) -> dict:
    """HLO text -> {computation_name: [Instr, ...]}; first key is entry."""
    comps: dict = {}
    cur = None
    entry = None
    for line in text.splitlines():
        if line.endswith("{") and ("->" in line or line.startswith("ENTRY")):
            m = _COMP_HDR_RE.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = []
                if line.startswith("ENTRY"):
                    entry = cur
                continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            comps[cur].append(Instr(m.group(1), m.group(2), m.group(3),
                                    m.group(4)))
    if entry and entry in comps:
        comps = {entry: comps[entry],
                 **{k: v for k, v in comps.items() if k != entry}}
    return comps


def while_reachable(comps: dict) -> set:
    """Names of computations that execute INSIDE some ``while`` op — the
    bodies/conditions of every while plus everything they transitively
    call.  This is the scope the pipeline auditor
    (``repro.analysis.staticcheck``) restricts itself to: collectives at
    entry (replicated embedding/LM-head grad reductions, GSPMD input
    reshards) are legitimate; inside the tick loop only the pipeline hop
    may touch the wire."""
    seeds: list = []
    for instrs in comps.values():
        for ins in instrs:
            if ins.opcode == "while":
                seeds.extend(ins.called_computations())
    reach = set()
    frontier = [c for c in seeds if c in comps]
    while frontier:
        name = frontier.pop()
        if name in reach:
            continue
        reach.add(name)
        for ins in comps[name]:
            frontier.extend(c for c in ins.called_computations()
                            if c in comps and c not in reach)
    return reach


def result_shape(rtype: str):
    """First ``(dtype, dims)`` of a result type string.

    For sync collectives this is the result itself; for the async
    ``-start`` spelling, whose result is a ``(operand, result, ...)``
    tuple, it is the operand — either way exactly ONE wire copy of the
    payload, which is what byte-honesty accounting needs (``_type_bytes``
    on the full tuple would double-count).
    """
    m = _SHAPE_RE.search(rtype)
    if not m:
        return None
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def source_target_pairs(rest: str):
    """``source_target_pairs={{0,2},{1,3}}`` -> [(0, 2), (1, 3)] (empty
    list when the attribute is absent)."""
    m = _STP_RE.search(rest)
    if not m:
        return []
    pairs = []
    for chunk in m.group(1).split("},{"):
        ids = [int(x) for x in chunk.replace("{", "").replace("}", "")
               .split(",") if x.strip()]
        if len(ids) == 2:
            pairs.append((ids[0], ids[1]))
    return pairs


def computation_multipliers(comps: dict) -> dict:
    """Propagate loop trip counts down the call graph."""
    mult = {name: 0.0 for name in comps}
    entry = next(iter(comps))
    mult[entry] = 1.0
    # topological-ish fixed point (call graphs are shallow)
    for _ in range(64):
        changed = False
        for name, instrs in comps.items():
            m = mult.get(name, 0.0)
            if m == 0.0:
                continue
            for ins in instrs:
                called = ins.called_computations()
                if not called:
                    continue
                k = m
                if ins.opcode == "while":
                    trip = ins.trip_count() or 1
                    k = m * trip
                for c in called:
                    if c in mult and mult[c] < k:
                        mult[c] = k
                        changed = True
        if not changed:
            break
    return mult


_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_METADATA_RE = re.compile(r'op_name="([^"]*)"')
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_RG_LIST_RE = re.compile(r"replica_groups=\{\{([\d,{}\s]*)\}\}")
# lazy up to the closing "}}" so EVERY pair is captured ("{0,2},{1,3"),
# not just the text before the first "}" (which would drop all but the
# first pair and blind any per-pair analysis of multi-pair permutes)
_STP_RE = re.compile(r"source_target_pairs=\{(.*?)\}\}")


def _crosses_pod(rest: str, pod_size: int) -> bool:
    """Does this collective's group structure span a pod boundary?

    Device ids are pod-major on our meshes (id // pod_size = pod index).
    """
    import numpy as np
    m = _RG_IOTA_RE.search(rest)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        total = int(np.prod(dims))
        ids = np.arange(total).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",")]
            ids = ids.transpose(perm)
        groups = ids.reshape(n_groups, group_size)
        pods = groups // pod_size
        return bool(np.any(pods.min(axis=1) != pods.max(axis=1)))
    m = _RG_LIST_RE.search(rest)
    if m:
        for grp in m.group(1).split("},{"):
            ids = [int(x) for x in grp.replace("{", "").replace("}", "")
                   .split(",") if x.strip()]
            if ids and min(ids) // pod_size != max(ids) // pod_size:
                return True
        return False
    m = _STP_RE.search(rest)
    if m:
        for pair in m.group(1).split("},{"):
            ids = [int(x) for x in pair.replace("{", "").replace("}", "")
                   .split(",") if x.strip()]
            if len(ids) == 2 and ids[0] // pod_size != ids[1] // pod_size:
                return True
        return False
    return False

# ops whose result is genuinely materialized to HBM on TPU (fusion-optimal
# traffic model: elementwise chains fuse into their matmul/reduce consumers
# and are "free"; what must move is matmul operands/results, reshuffles,
# and collective payloads)
_GATHERISH = {"dynamic-slice", "gather", "scatter",
              "copy", "transpose", "reshape"}


def analyze(text: str, top_n: int = 0, pod_size: int = 256,
            tpu_model: bool = False) -> dict:
    """Static roofline inputs -> {flops, bytes, coll_bytes, coll_by_kind,
    n_while, top_traffic, top_coll}.

    The memory term is the FUSION-OPTIMAL HBM traffic (roofline spirit:
    best-case time per resource): dot/convolution operands + results,
    gather/scatter/copy payloads, and collective payloads — all x loop
    multiplier.  Elementwise ops are assumed fused (free).

    ``tpu_model=True`` corrects two CPU-backend lowering artifacts that the
    TPU target does not have (EXPERIMENTS.md §Perf methodology):
      * XLA:CPU float-normalization upcasts every bf16 dot to f32 and
        hoists the weight converts out of the layer loop, so semantically-
        bf16 weight gathers / grad reduce payloads appear as f32 — billed
        at half width (native MXU bf16);
      * the jnp attention fallback materializes the [.., G, S] probability
        tensor with layout copies; the production path is the Pallas flash
        kernel (repro/kernels/flash_attention.py) where it never leaves
        VMEM — attention-internal einsum traffic (op_name containing the
        'bkrg' einsum labels) is dropped (FLOPs kept).
    """
    comps = parse_hlo(text)
    mult = computation_multipliers(comps)

    flops = 0.0
    traffic = 0.0
    coll = {k: 0.0 for k in COLLECTIVES}
    coll_dcn = 0.0        # bytes of collectives whose groups cross pods
    n_while = 0
    contrib_t: list = []
    contrib_c: list = []

    def note(lst, ins, nb, m):
        if top_n:
            md = _METADATA_RE.search(ins.rest)
            lst.append((nb * m, ins.opcode, ins.rtype[:48],
                        md.group(1)[-120:] if md else ""))

    def op_name(ins):
        md = _METADATA_RE.search(ins.rest)
        return md.group(1) if md else ""

    def attn_internal(ins):
        """Inner-kernel traffic: attention / WKV / LRU chunk-loop bodies.

        These live inside a second while level (layer scan x chunk scan);
        on TPU the Pallas kernels keep them VMEM-resident.  FLOPs are
        still counted — only HBM traffic is dropped.
        """
        if not tpu_model:
            return False
        name = op_name(ins)
        return "bkrg" in name or name.count("while/body") >= 2

    def f32_discount(ins):
        """0.5 for f32 payloads that are semantically bf16 on TPU
        (weight gathers / activation-grad reduces of bf16 params; XLA:CPU
        float-normalization upcasts them)."""
        if tpu_model and "f32[" in ins.rtype and "bf16[" not in ins.rtype:
            return 0.5
        return 1.0

    for name, instrs in comps.items():
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        symbols = {ins.name: ins.rtype for ins in instrs}
        for ins in instrs:
            if ins.opcode == "while":
                n_while += 1
            if ins.opcode in ("dot", "dot-general"):
                res_elems = _type_elems(ins.rtype)
                cm = _CONTRACT_RE.search(ins.rest)
                k_elems = 1
                ops = ins.operand_names()
                if cm and ops:
                    lhs_t = symbols.get(ops[0], "")
                    sm = _SHAPE_RE.search(lhs_t)
                    if sm:
                        dims = [int(d) for d in sm.group(2).split(",") if d]
                        for ci in cm.group(1).split(","):
                            if ci and int(ci) < len(dims):
                                k_elems *= dims[int(ci)]
                flops += 2.0 * res_elems * k_elems * m
                if not attn_internal(ins):
                    nb = (_type_bytes(ins.rtype) + sum(
                        _type_bytes(symbols.get(op, ""))
                        for op in ops[:2])) * f32_discount(ins)
                    traffic += nb * m
                    note(contrib_t, ins, nb, m)
            elif ins.opcode == "convolution":
                # 2 * out_elems * (kernel receptive field x c_in)
                res_elems = _type_elems(ins.rtype)
                rm = _SHAPE_RE.search(ins.rtype)
                c_out = int(rm.group(2).split(",")[-1]) if rm and rm.group(2) \
                    else 1
                ops = ins.operand_names()
                k_elems = 1
                if len(ops) > 1:
                    k_elems = max(1, _type_elems(symbols.get(ops[1], "")))
                flops += 2.0 * res_elems * (k_elems / max(c_out, 1)) * m
                nb = _type_bytes(ins.rtype) + sum(
                    _type_bytes(symbols.get(op, "")) for op in ops[:2])
                traffic += nb * m
                note(contrib_t, ins, nb, m)
            elif ins.opcode in _GATHERISH:
                if not attn_internal(ins):
                    nb = 2.0 * _type_bytes(ins.rtype) * f32_discount(ins)
                    traffic += nb * m
                    note(contrib_t, ins, nb, m)
            elif ins.opcode == "dynamic-update-slice":
                # in-place on TPU: traffic = the update slice, not the
                # full result buffer (a KV-cache insert writes one token)
                ops = ins.operand_names()
                upd = _type_bytes(symbols.get(ops[1], "")) if len(ops) > 1 \
                    else 0
                nb = 2.0 * upd
                traffic += nb * m
                note(contrib_t, ins, nb, m)
            elif ins.opcode == "reduce":
                ops = ins.operand_names()
                nb = sum(_type_bytes(symbols.get(op, "")) for op in ops[:1])
                traffic += nb * m
                note(contrib_t, ins, nb, m)
            for kind in COLLECTIVES:
                if ins.opcode == kind or ins.opcode == kind + "-start":
                    nb = _type_bytes(ins.rtype) * f32_discount(ins)
                    w = 2.0 if kind == "all-reduce" else 1.0
                    coll[kind] += nb * m
                    traffic += 2.0 * nb * m
                    if _crosses_pod(ins.rest, pod_size):
                        coll_dcn += w * nb * m
                    note(contrib_c, ins, nb, m)

    out = {
        "flops": flops,
        "bytes": traffic,
        "coll_by_kind": coll,
        "coll_bytes": (2.0 * coll["all-reduce"] + coll["all-gather"]
                       + coll["reduce-scatter"] + coll["all-to-all"]
                       + coll["collective-permute"]),
        "coll_dcn_bytes": coll_dcn,
        "n_while": n_while,
    }
    if top_n:
        out["top_traffic"] = sorted(contrib_t, reverse=True)[:top_n]
        out["top_coll"] = sorted(contrib_c, reverse=True)[:top_n]
    return out


def flat_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict.

    These are the trip-count-UNAWARE numbers (each while body counted
    once) that ``analyze`` corrects; they're retained in dry-run records
    for reference.
    """
    return dict(compiled.cost_analysis())
