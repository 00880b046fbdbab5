"""Trip-count-aware analysis of compiled (SPMD-partitioned) HLO.

``compiled.cost_analysis()`` counts every computation ONCE — the body of a
``while`` loop (every ``lax.scan``: the layer stack, flash-attention chunk
loops, SSD chunk scan) is not multiplied by its trip count, which undercounts
FLOPs/bytes/collective traffic by up to ~n_layers x.  This module parses the
compiled HLO text into its computation graph, recovers each loop's trip
count from its condition computation (the ``constant(N)`` bound of the
induction-variable compare), and walks the call graph so that every
computation carries the product of the trip counts of the loops enclosing
it.  On top of that multiplier map it derives:

  * ``dot_flops``        — 2 * prod(result_dims) * contracted_dims summed
                           over every dot, x multiplier: the matmul FLOPs
                           actually executed per chip;
  * ``result_bytes``     — sum of op-result sizes x multiplier (fusion-
                           internal ops excluded): per-chip HBM write-traffic
                           proxy (read traffic is symmetric to first order);
  * ``collective_bytes`` — per collective type, x multiplier: wire bytes per
                           chip including in-loop collectives (e.g. the FSDP
                           all-gather inside the layer scan).

Caveats (documented in EXPERIMENTS.md §Roofline): data-dependent loops
(bound management's retry) are charged at their static max bound; fused
elementwise FLOPs are excluded from dot_flops (MXU roofline convention);
convolutions (LeNet only) are not counted.

This module is the HLO layer of the :mod:`repro.analysis` static-analysis
package (``repro.launch.hlo_analysis`` re-exports it for backwards
compatibility).  Parser heuristics that can silently mis-resolve on unusual
XLA dumps — the "entry printed last" fallback of :func:`split_computations`
and the largest-constant fallback of :func:`_trip_count` — now emit a
structured :class:`HloParseWarning` so auditors (and CI) can surface them
instead of trusting a possibly-wrong answer.
"""

from __future__ import annotations

import re
import warnings
from typing import Dict, List, Optional, Tuple


class HloParseWarning(UserWarning):
    """A parser heuristic fell back to a convention that can mis-resolve.

    ``kind`` is a stable machine-checkable tag:

    * ``"entry-fallback"``      — no ``ENTRY`` marker found; the entry
      computation was guessed as the one printed last.
    * ``"trip-count-fallback"`` — a while condition had no resolvable
      ``compare(i, constant)`` root; the trip count was guessed as the
      largest integer constant in the block (can overcount when the
      condition embeds shape constants).
    """

    def __init__(self, kind: str, detail: str):
        super().__init__(f"[{kind}] {detail}")
        self.kind = kind
        self.detail = detail

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(
    r"\b(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|"
    r"pred|c64|c128)\[([0-9,]*)\]")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_OP_RE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_CALL_RE = re.compile(r"(?:condition|body|calls|to_apply)=([%\w.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _shape_elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _all_shapes_bytes(text: str) -> int:
    return sum(_shape_elems(m.group(2)) * _DTYPE_BYTES[m.group(1)]
               for m in _SHAPE_RE.finditer(text))


_PALLAS_NAME_RE = re.compile(r'op_name="[^"]*?/(\w+)/pallas_call')


def pallas_kernel_names(hlo: str) -> List[str]:
    """Kernel name of every Pallas launch (``tpu_custom_call``) in compiled
    TPU HLO text, read from its ``op_name`` metadata (``"?"`` when absent).
    Each launch is counted once where it is printed, not per loop trip."""
    names = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _PALLAS_NAME_RE.search(line)
            names.append(m.group(1) if m else "?")
    return names


def split_computations(hlo: str) -> Tuple[Dict[str, List[str]], str]:
    """computation name -> op lines; plus the entry computation name."""
    comps: Dict[str, List[str]] = {}
    entry = None
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if not line.startswith(" "):
            stripped = line.rstrip()
            if stripped.endswith("{") and ("(" in stripped):
                toks = stripped.split()
                name = toks[0]
                if name == "ENTRY":
                    name = toks[1]
                    entry = name
                cur = name
                comps[cur] = []
                continue
            if stripped.startswith("}"):
                cur = None
                continue
        if cur is not None and line.strip():
            comps[cur].append(line.strip())
    if entry is None and comps:
        entry = list(comps)[-1]    # printed last by convention
        warnings.warn(HloParseWarning(
            "entry-fallback",
            f"no ENTRY computation marker in HLO dump; assuming the "
            f"computation printed last ({entry!r}) is the entry — launch/"
            f"multiplier attribution may be wrong on reordered dumps"),
            stacklevel=2)
    return comps, entry


def _split_assign(line: str) -> Optional[Tuple[str, str, str, str]]:
    """op line -> (result_name, result_type_text, op_name, rest)."""
    if line.startswith("ROOT "):
        line = line[5:]
    if " = " not in line:
        return None
    name, rhs = line.split(" = ", 1)
    m = _OP_RE.search(" " + rhs)
    if not m:
        return None
    op = m.group(1)
    type_part = rhs[:m.start()]
    rest = rhs[m.start():]
    return name.strip(), type_part, op, rest


def _trip_count(cond_lines: List[str]) -> int:
    """Trip count of a lax.scan-lowered loop from its condition computation.

    Precise path: the condition's ROOT is ``compare(induction_var, bound)``
    with ``direction=LT``; resolve the bound constant within the block.
    Fallback: the largest integer constant in the block (can overcount if
    the condition embeds shape constants — the root parse avoids that)."""
    consts: Dict[str, int] = {}
    root = None
    for line in cond_lines:
        m = re.match(r"(ROOT\s+)?(%?[\w.\-]+)\s*=\s*\S+\s+constant\((\d+)\)",
                     line)
        if m:
            consts[m.group(2)] = int(m.group(3))
        if line.startswith("ROOT"):
            root = line
    if root is not None:
        cm = re.search(r"compare\(([^)]*)\)", root)
        if cm and "direction=LT" in root:
            for arg in cm.group(1).split(","):
                v = consts.get(arg.strip())
                if v is not None:
                    return max(v, 1)
    best = 1
    for line in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", line):
            best = max(best, int(m.group(1)))
    if cond_lines:
        warnings.warn(HloParseWarning(
            "trip-count-fallback",
            f"while condition has no resolvable compare(i, constant(N)) "
            f"root; using the largest integer constant in the block "
            f"({best}) as the trip count — this overcounts when the "
            f"condition embeds shape constants"), stacklevel=2)
    return best


def multiplier_map(hlo: str) -> Tuple[Dict[str, int], Dict[str, List[str]],
                                      str]:
    comps, entry = split_computations(hlo)
    mult: Dict[str, int] = {}

    def visit(name: str, m: int):
        if name not in comps or mult.get(name, 0) >= m:
            return
        mult[name] = m
        for line in comps[name]:
            parsed = _split_assign(line)
            if parsed is None:
                continue
            _, _, op, rest = parsed
            if op == "while":
                cond = re.search(r"condition=([%\w.\-]+)", rest)
                body = re.search(r"body=([%\w.\-]+)", rest)
                trips = _trip_count(comps.get(cond.group(1), [])) \
                    if cond else 1
                if cond:
                    visit(cond.group(1), m * trips)
                if body:
                    visit(body.group(1), m * trips)
            else:
                for cm in _CALL_RE.finditer(rest):
                    visit(cm.group(1), m)

    if entry:
        visit(entry, 1)
    return mult, comps, entry


def analyse_hlo(hlo: str) -> Dict[str, float]:
    """Trip-aware dot FLOPs, result bytes, collective bytes (per chip)."""
    mult, comps, _ = multiplier_map(hlo)

    # symbol tables: per computation, op name -> (type, op, first-arg name)
    symtab: Dict[str, Dict[str, str]] = {}
    defs: Dict[str, Dict[str, Tuple[str, str]]] = {}
    for cname, lines in comps.items():
        tab: Dict[str, str] = {}
        dtab: Dict[str, Tuple[str, str]] = {}
        for line in lines:
            parsed = _split_assign(line)
            if parsed is None:
                continue
            nm, type_part, op0, rest0 = parsed
            tab[nm] = type_part
            am = re.match(rf"{op0}\(([^)]*)\)", rest0)
            first_arg = am.group(1).split(",")[0].strip() if am else ""
            dtab[nm] = (op0, first_arg)
        symtab[cname] = tab
        defs[cname] = dtab

    def _dot_operand_width_bytes(cname: str, arg: str) -> float:
        """Bytes of a dot operand at its *pre-upcast* width.

        The CPU backend upcasts bf16 matmul inputs to f32 via explicit
        converts; a TPU MXU reads bf16 natively.  Follow the operand
        through converts / convert-fusions (depth<=3) and charge the
        narrowest width seen on the path."""
        tab, dtab = symtab[cname], defs[cname]
        best = None
        name = arg
        for _ in range(3):
            t = tab.get(name)
            if t is None:
                break
            b = _all_shapes_bytes(t)
            best = b if best is None else min(best, b)
            op0, first = dtab.get(name, ("", ""))
            if op0 == "convert" or (op0 == "fusion" and "convert" in name):
                name = first
                continue
            break
        return best or 0.0

    dot_flops = 0.0
    result_bytes = 0.0
    operand_bytes = 0.0
    dot_operand_bytes = 0.0
    fusion_result_bytes = 0.0
    attn_internal_bytes = 0.0   # score-matrix traffic a fused attention
                                # kernel keeps in VMEM (see analyse docstring)
    coll = {k: 0.0 for k in _COLLECTIVES}
    coll_count = 0
    _skip = ("parameter", "constant", "get-tuple-element", "tuple",
             "bitcast",
             # loop plumbing: the while/call RESULT is the carried tuple
             # (often the whole stacked-params state) — its real traffic is
             # already accounted by the ops inside the body; recounting the
             # tuple here double-charges entire parameter stacks
             "while", "call", "conditional", "custom-call",
             "opt-barrier", "after-all", "copy-start", "copy-done")
    # ops a TPU compile fuses into producers/consumers (layout changes,
    # dtype converts, broadcasts): excluded from the TPU-fusion-model
    # traffic; the CPU backend materialises them all (upper bound keeps them)
    _tpu_fused = ("convert", "broadcast", "reshape", "transpose", "slice",
                  "copy", "iota", "compare", "select", "add", "subtract",
                  "multiply", "divide", "maximum", "minimum", "exponential",
                  "tanh", "negate", "rsqrt", "sqrt", "log", "cosine", "sine",
                  "and", "or", "xor", "shift-right-logical", "shift-left",
                  "clamp", "floor", "round-nearest-even", "power", "abs",
                  "sign", "concatenate", "pad", "reverse", "reduce",
                  "reduce-window", "map", "exponential-minus-one")

    for cname, lines in comps.items():
        m = mult.get(cname, 0)
        if m == 0:
            continue
        is_fusion_body = "fused_computation" in cname
        tab = symtab[cname]
        for line in lines:
            parsed = _split_assign(line)
            if parsed is None:
                continue
            nm, type_part, op, rest = parsed
            if not is_fusion_body and op not in _skip:
                argm = re.match(rf"{op}\(([^)]*)\)", rest)
                args = [a.strip() for a in argm.group(1).split(",")] \
                    if argm else []
                if op == "dynamic-update-slice":
                    # in-place on real hardware: traffic = the updated slice
                    # (read new data + write it), not the whole buffer
                    upd = tab.get(args[1]) if len(args) > 1 else None
                    if upd:
                        b = _all_shapes_bytes(upd)
                        result_bytes += b * m
                        operand_bytes += b * m
                        fusion_result_bytes += 2 * b * m
                elif op == "dynamic-slice":
                    b = _all_shapes_bytes(type_part)
                    result_bytes += b * m
                    operand_bytes += b * m
                    fusion_result_bytes += 2 * b * m
                else:
                    rb = _all_shapes_bytes(type_part)
                    result_bytes += rb * m
                    if op not in _tpu_fused:
                        fusion_result_bytes += rb * m
                    # read traffic: resolve operand names in the local
                    # symtab (XLA cost_analysis "bytes accessed" convention,
                    # multiplied by loop trip counts)
                    for arg in args:
                        t = tab.get(arg)
                        if t:
                            ob = _all_shapes_bytes(t)
                            operand_bytes += ob * m
                            if op == "dot":
                                dot_operand_bytes += \
                                    _dot_operand_width_bytes(cname, arg) * m
            if op == "dot":
                out_elems = sum(
                    _shape_elems(sm.group(2))
                    for sm in _SHAPE_RE.finditer(type_part))
                k_elems = 1
                cd = _LHS_CONTRACT_RE.search(rest)
                args = re.match(r"dot\(([^)]*)\)", rest)
                if cd and args:
                    lhs_name = args.group(1).split(",")[0].strip()
                    lhs_type = tab.get(lhs_name, "")
                    sm = _SHAPE_RE.search(lhs_type)
                    if sm:
                        dims = [int(d) for d in sm.group(2).split(",") if d]
                        for ci in cd.group(1).split(","):
                            if ci and int(ci) < len(dims):
                                k_elems *= dims[int(ci)]
                dot_flops += 2.0 * out_elems * k_elems * m
                # attention-internal traffic: the score matrix produced by
                # the qk dot and consumed by the pv dot never leaves VMEM
                # in a fused (flash) attention kernel; attribute it via the
                # einsum spec in the op metadata so the roofline can report
                # both the XLA-lowered and the kernel-projected memory term
                if "->bhqk" in rest:                  # qk^T: score result
                    attn_internal_bytes += \
                        _all_shapes_bytes(type_part) * m
                elif "bhqk," in rest and args:        # pv: score operand
                    p_name = args.group(1).split(",")[0].strip()
                    attn_internal_bytes += \
                        _dot_operand_width_bytes(cname, p_name) * m
            elif op.rstrip("-start").rstrip("-done") in _COLLECTIVES or \
                    op in _COLLECTIVES or \
                    any(op == c + "-start" for c in _COLLECTIVES):
                base = op[:-6] if op.endswith("-start") else op
                if base in _COLLECTIVES:
                    coll[base] += _all_shapes_bytes(type_part) * m
                    coll_count += 1

    out = {"dot_flops": dot_flops, "result_bytes": result_bytes,
           "operand_bytes": operand_bytes,
           "bytes_traffic": result_bytes + operand_bytes,
           # TPU-fusion model: every non-fusable tensor written once +
           # matmul operand reads + in-place cache slice traffic.  Converts/
           # elementwise/layout ops fuse into MXU epilogues on TPU; the CPU
           # backend materialises them (the upper bound above keeps them).
           "bytes_fusion_model": fusion_result_bytes + dot_operand_bytes,
           "dot_operand_bytes": dot_operand_bytes,
           "attn_internal_bytes": attn_internal_bytes,
           "collective_count": float(coll_count)}
    for k, v in coll.items():
        out[f"coll_{k}"] = v
    out["coll_total"] = sum(coll.values())
    return out


# ---------------------------------------------------------------------------
# Donation: input/output buffer aliasing of a compiled module
# ---------------------------------------------------------------------------

_ALIAS_BLOCK_RE = re.compile(
    r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}")
_ALIAS_PAIR_RE = re.compile(r"\{([0-9, ]*)\}:\s*\(\s*(\d+)\s*,")


def input_output_aliases(hlo: str) -> Dict[int, Tuple[int, ...]]:
    """Parse the module-level ``input_output_alias`` config of compiled HLO.

    Returns ``{parameter_index: (output_tuple_path...)}`` for every input
    buffer XLA actually aliased to an output (i.e. every donation it
    *accepted*).  Donations XLA silently declined simply do not appear —
    ``jaxpr_audit.audit_donation`` diffs this map against the donation
    request to recover them.
    """
    m = _ALIAS_BLOCK_RE.search(hlo)
    if not m:
        return {}
    out: Dict[int, Tuple[int, ...]] = {}
    for pm in _ALIAS_PAIR_RE.finditer(m.group(1)):
        path = tuple(int(t) for t in pm.group(1).split(",") if t.strip())
        out[int(pm.group(2))] = path
    return out
