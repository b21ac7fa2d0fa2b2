"""Device-cost attribution: protection vs. model HLO cost per bucket.

``Engine.decode_cost_analysis`` reports one aggregate flops /
bytes-accessed number per compiled decode variant — enough to see that
a scheme costs *something*, useless for saying *where*.  This module
walks the compiled HLO text instead (``fn.lower().compile()
.as_text()``), which on both the CPU and TPU backends keeps per
-instruction ``metadata={op_name=... stack_frame_id=N}`` pointing at the
Python call stack that built each op; the id resolves through the
``FileNames`` / ``FileLocations`` / ``StackFrames`` tables at the top of
the module text.  That lets us split the
decode step's cost into

* **protection** — AES-CTR keystream + BAES key schedule, NH/CBC-MAC,
  VN freshness, key-bank gathers, page binding/counter construction
  (the crypto files under ``core/`` and ``kernels/``, plus the
  protection helpers inside ``serve/kv_pages.py`` by source-line
  range), and
* **model** — attention/MLP/sampling and the paging glue the model
  would need even with protection ``off``.

Accounting conventions (deliberately close to XLA's own
HloCostAnalysis so the totals track ``cost_analysis()``):

* bytes: operand + output shape bytes of every *top-level* instruction
  (ENTRY / while bodies / called computations).  Instructions inside
  ``fused_computation``/``region_`` bodies are intermediates the
  fusion call line already accounts for; ``parameter`` /
  ``get-tuple-element`` / ``tuple`` / ``bitcast`` / ``constant`` are
  free (reads are charged at use sites).
* flops: ``dot`` = 2·M·N·K, elementwise arithmetic = one flop per
  output element, ``reduce`` = one per input element — counted in
  *every* computation (fusion bodies do the arithmetic; the fusion
  call itself contributes none).

The split is attached to the engine as lazy gauges (sampled from a
cache — snapshotting never compiles anything) and exported as JSON via
``Engine.profile()`` / ``ClusterEngine.profile()``.
"""

from __future__ import annotations

import ast
import math
import re
from dataclasses import dataclass, field
from typing import Optional

from repro.launch.hlo_utils import parse_shape_bytes
from repro.launch.roofline import HBM_BW, PEAK_FLOPS

__all__ = ["CostProfile", "attribute_hlo", "classify_source",
           "profile_decode"]

# -- source classification ---------------------------------------------------

# Crypto/integrity modules: every op they emit is protection work.
_PROTECTION_BASENAMES = frozenset({
    "aes.py", "baes.py", "ctr.py", "mac.py", "vn.py", "multilevel.py",
    "secure_exec.py", "secure_memory.py", "bytesutil.py",
})

# serve/kv_pages.py mixes paging glue (model-side) with the protection
# path; these functions are the protection side, attributed by the
# source-line ranges ast gives us.
_KV_PROTECTION_FUNCS = frozenset({
    "_block_pa", "_tenant_words", "_shard_ctr_word", "_block_counters",
    "_counter_words",
    "_block_binding", "_uniform_keys", "_crypt", "_page_block_macs",
    "_fused_crossing", "_fused_read", "_fused_write",
    "deferred_pool_check",
})

_kv_ranges_cache: Optional[list] = None


def _kv_protection_ranges() -> list:
    """[(lo, hi)] source-line ranges of kv_pages' protection helpers."""
    global _kv_ranges_cache
    if _kv_ranges_cache is None:
        from repro.serve import kv_pages
        with open(kv_pages.__file__) as f:
            tree = ast.parse(f.read())
        ranges = []
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in _KV_PROTECTION_FUNCS:
                ranges.append((node.lineno, node.end_lineno or node.lineno))
        _kv_ranges_cache = sorted(ranges)
    return _kv_ranges_cache


def classify_source(source_file: str, source_line: int) -> str:
    """'protection' | 'model' for one attributed HLO instruction."""
    path = source_file.replace("\\", "/")
    if "/kernels/" in path:
        return "protection"
    base = path.rsplit("/", 1)[-1]
    if base in _PROTECTION_BASENAMES:
        return "protection"
    if base == "kv_pages.py":
        for lo, hi in _kv_protection_ranges():
            if lo <= source_line <= hi:
                return "protection"
    return "model"


# -- HLO text walking --------------------------------------------------------

_FRAME_ID_RE = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW_RE = re.compile(r'^(\d+) (?:"(.*)"|\{(.*)\})$')
_FIELD_RE = re.compile(r"(\w+)=(\d+)")


def _frame_sources(hlo_text: str) -> dict:
    """``stack_frame_id`` -> (file, line) of that frame.

    The id names the innermost frame of the op's call stack; its
    ``FileLocations`` row gives the file and line.  (``parent_frame_id``
    links outward; the innermost frame is the one the attribution
    uses, as the old per-instruction ``source_file`` did.)
    """
    tables: dict = {}
    section = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section = tables.setdefault(line, {})
            continue
        m = _TABLE_ROW_RE.match(line) if section is not None else None
        if m is None:
            section = None
            continue
        key = int(m.group(1))
        section[key] = (m.group(2) if m.group(2) is not None
                        else {k: int(v) for k, v
                              in _FIELD_RE.findall(m.group(3))})
    files = tables.get("FileNames", {})
    locs = tables.get("FileLocations", {})
    out = {}
    for fid, frame in tables.get("StackFrames", {}).items():
        loc = locs.get(frame.get("file_location_id"))
        if loc is not None and loc.get("file_name_id") in files:
            out[fid] = (files[loc["file_name_id"]], loc.get("line", 0))
    return out
_SHAPE_RE = re.compile(r"\b(?:pred|s8|u8|s16|u16|f16|bf16|s32|u32|f32|s64"
                       r"|u64|f64|c64|c128)\[([0-9,]*)\]")
_OP_RE = re.compile(r"=\s*(?:\([^)]*\)\s*)?[a-z0-9_\[\],{}\s]*?"
                    r"([a-z][a-z0-9-]*)\(")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims={([0-9,]*)}")

# Shape-shuffling ops XLA charges nothing for (reads are charged where
# the value is consumed), plus control-flow wrappers whose operand
# tuples merely alias the bodies we already account for.
_FREE_OPS = frozenset({"parameter", "get-tuple-element", "tuple", "bitcast",
                       "constant", "after-all", "iota", "while",
                       "conditional", "call"})

# One flop per output element.
_ELEMENTWISE = frozenset({
    "add", "subtract", "multiply", "divide", "remainder", "power",
    "maximum", "minimum", "and", "or", "xor", "not", "negate", "abs",
    "shift-left", "shift-right-logical", "shift-right-arithmetic",
    "compare", "select", "exponential", "log", "tanh", "rsqrt", "sqrt",
    "sign", "floor", "ceil", "round-nearest-afz", "clamp", "convert",
    "sine", "cosine", "logistic", "atan2", "is-finite", "popcnt", "clz",
})

# Pure data movement: when even dataflow inheritance cannot attribute
# one of these, it is loop/layout glue and folds into the model bucket.
_MOVEMENT_OPS = frozenset({
    "copy", "broadcast", "transpose", "reshape", "pad", "slice",
    "concatenate", "dynamic-slice", "dynamic-update-slice", "reverse",
})

# Computations whose instructions are fusion/reduce intermediates; the
# calling instruction carries their memory traffic.
_INNER_COMP = re.compile(r"^%?(fused_computation|region_|\S*reduce_sub"
                         r"_computation|\S*scatter_computation)")


def _elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def _line_flops(line: str, opcode: str) -> float:
    shapes = _SHAPE_RE.findall(line)
    if not shapes:
        return 0.0
    out = _elems(shapes[0])
    if opcode in ("dot", "convolution"):
        contract = 1
        m = _CONTRACT_RE.search(line)
        if m and len(shapes) >= 2:
            lhs = shapes[1].split(",") if shapes[1] else []
            for d in (m.group(1).split(",") if m.group(1) else []):
                d = int(d)
                if d < len(lhs):
                    contract *= int(lhs[d])
        return 2.0 * out * contract
    if opcode == "reduce" and len(shapes) >= 2:
        return float(_elems(shapes[1]))
    if opcode in _ELEMENTWISE:
        return float(out)
    return 0.0


_CALLS_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")


def _iter_instructions(hlo_text: str):
    """Yield (computation_name, opcode, stripped_line) per instruction."""
    comp = "ENTRY"
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if line.endswith("{") and (") -> " in line
                                   or line.startswith("ENTRY")):
            name = line.split(" ", 1)[0].lstrip("%")
            comp = "ENTRY" if line.startswith("ENTRY") else name
            continue
        if line == "}" or "=" not in line:
            continue
        m_op = _OP_RE.search(line)
        if m_op:
            yield comp, m_op.group(1), line


_NAME_RE = re.compile(r"%[\w.\-]+")


def attribute_hlo(hlo_text: str) -> dict:
    """Split one HLO module's bytes/flops by protection|model|other.

    Returns ``{"protection": {...}, "model": {...}, "other": {...},
    "total": {...}, "by_file": {file: {...}}}`` where each leaf is
    ``{"bytes": float, "flops": float, "ops": int}``.

    Attribution cascades through three sources, strongest first:

    1. the instruction's own ``metadata={... stack_frame_id=N}``,
       resolved through the module's stack-frame tables;
    2. the flop-weighted majority source of a fused computation's body
       (for fusion call lines and metadata-less clones inside bodies);
    3. dataflow inheritance — XLA passes (e.g. the expansion of
       u8<->u32 bitcast-converts into whole shift/mask fusions) drop
       metadata entirely, so unresolved instructions inherit from
       their operands, then from their consumers, over a few sweeps.

    What still remains is ``other`` (XLA-inserted loop-carried copies
    with no attributable neighborhood) — the coverage criterion in
    ``tests`` keeps it under 5% of total bytes and flops.
    """
    # -- collect one record per instruction ---------------------------------
    frames = _frame_sources(hlo_text)
    records = []
    for comp, opcode, line in _iter_instructions(hlo_text):
        inner = bool(_INNER_COMP.match(comp))
        # Strip metadata / calls= before shape parsing: op_name strings
        # may embed shape-like text, and calls= carries no traffic.
        body = line.split(", metadata={")[0].split(", calls=")[0]
        lhs, _, rhs = body.partition("=")
        m_name = _NAME_RE.search(lhs)
        name = m_name.group(0) if m_name else None
        operands = _NAME_RE.findall(rhs)
        nbytes = 0.0
        if not inner and opcode not in _FREE_OPS:
            nbytes = float(parse_shape_bytes(body))
        flops = _line_flops(body, opcode)
        meta = _FRAME_ID_RE.search(line)
        src = frames.get(int(meta.group(1))) if meta else None
        callees = _CALLS_RE.findall(line)
        records.append({"comp": comp, "opcode": opcode, "name": name,
                        "operands": operands, "bytes": nbytes,
                        "flops": flops, "src": src, "callees": callees})

    # -- fused-body majority vote (flop-weighted, +1 floor) -----------------
    votes: dict = {}
    for r in records:
        if r["src"] and _INNER_COMP.match(r["comp"]):
            tally = votes.setdefault(r["comp"], {})
            tally[r["src"]] = tally.get(r["src"], 0.0) + r["flops"] + 1.0
    body_src = {comp: max(tally, key=tally.get)
                for comp, tally in votes.items()}
    for r in records:
        if r["src"] is None and _INNER_COMP.match(r["comp"]):
            r["src"] = body_src.get(r["comp"])
        if r["src"] is None and r["callees"]:
            for callee in r["callees"]:
                if callee in body_src:
                    r["src"] = body_src[callee]
                    break

    # -- dataflow inheritance ------------------------------------------------
    # Free ops (GTE/copy/tuple) participate as conduits so chains like
    # attributed-op -> GTE -> orphan fusion resolve.  Names are unique
    # module-wide in printed HLO, so one flat map suffices.  A fused
    # computation's parameters alias the call site's operands, linking
    # body interiors to the data they actually process.
    comp_params: dict = {}
    for r in records:
        if r["opcode"] == "parameter" and r["name"]:
            comp_params.setdefault(r["comp"], []).append(r["name"])
    aliases = []
    for r in records:
        for callee in r["callees"]:
            if callee in comp_params:
                aliases += list(zip(comp_params[callee], r["operands"]))

    attr = {r["name"]: r["src"] for r in records
            if r["name"] and r["src"]}
    for _ in range(6):
        changed = False
        for r in records:                       # forward: from operands
            if r["src"] is None:
                for op in r["operands"]:
                    if op in attr:
                        r["src"] = attr[op]
                        if r["name"]:
                            attr[r["name"]] = r["src"]
                        changed = True
                        break
        for r in reversed(records):             # backward: from consumers
            if r["src"] is not None:
                for op in r["operands"]:
                    if op not in attr:
                        attr[op] = r["src"]
                        changed = True
        for a, b in aliases:                    # param <-> call operand
            if a in attr and b not in attr:
                attr[b] = attr[a]
                changed = True
            elif b in attr and a not in attr:
                attr[a] = attr[b]
                changed = True
        for r in records:
            if r["src"] is None and r["name"] in attr:
                r["src"] = attr[r["name"]]
                changed = True
        if not changed:
            break

    # A resolved caller covers its callee computation's metadata-less
    # interior: XLA's u8<->u32 bitcast-convert expansion emits whole
    # `xla.bitcast_convert_*` computations (and the fusions inside
    # them) without metadata, while the `call(..., to_apply=...)` site
    # keeps it.  Iterate so chains resolve: call -> called computation
    # -> fusion inside it -> fused body.
    comp_src = dict(body_src)
    for _ in range(4):
        changed = False
        for r in records:
            if r["src"] is not None:
                for callee in r["callees"]:
                    if callee not in comp_src:
                        comp_src[callee] = r["src"]
                        changed = True
            elif r["comp"] in comp_src:
                r["src"] = comp_src[r["comp"]]
                changed = True
        if not changed:
            break

    # Last resort for non-movement stragglers (bounds checks and
    # select/compare glue in while bodies whose operands are all loop
    # state): inherit the cost-weighted majority source of the
    # surrounding computation.
    comp_vote: dict = {}
    for r in records:
        if r["src"]:
            tally = comp_vote.setdefault(r["comp"], {})
            w = r["bytes"] + r["flops"] + 1.0
            tally[r["src"]] = tally.get(r["src"], 0.0) + w
    for r in records:
        if (r["src"] is None and r["opcode"] not in _MOVEMENT_OPS
                and r["comp"] in comp_vote):
            tally = comp_vote[r["comp"]]
            r["src"] = max(tally, key=tally.get)

    # -- fold into the three cost buckets -----------------------------------
    buckets = {k: {"bytes": 0.0, "flops": 0.0, "ops": 0}
               for k in ("protection", "model", "other")}
    by_file: dict = {}
    for r in records:
        nbytes, flops = r["bytes"], r["flops"]
        if nbytes == 0.0 and flops == 0.0:
            continue
        if r["src"] is None and r["opcode"] in _MOVEMENT_OPS:
            # Unattributable pure data movement (XLA-inserted loop
            # -carried copies, layout shuffles of model tensors) is
            # model-side glue: counting it as model is conservative —
            # it can only *under*state the protection-overhead ratio.
            buckets["model"]["bytes"] += nbytes
            buckets["model"]["flops"] += flops
            buckets["model"]["ops"] += 1
            continue
        if r["src"] is not None:
            src, lineno = r["src"]
            kind = classify_source(src, lineno)
            key = src.replace("\\", "/")
            if "/repro/" in key:
                key = key.split("/repro/", 1)[1]
            f = by_file.setdefault(key, {"bytes": 0.0, "flops": 0.0,
                                         "ops": 0})
            f["bytes"] += nbytes
            f["flops"] += flops
            f["ops"] += 1
        else:
            kind = "other"
        b = buckets[kind]
        b["bytes"] += nbytes
        b["flops"] += flops
        b["ops"] += 1
    total = {k: sum(buckets[c][k] for c in buckets)
             for k in ("bytes", "flops")}
    total["ops"] = sum(buckets[c]["ops"] for c in buckets)
    return {**buckets, "total": total, "by_file": by_file}


# -- the profile object ------------------------------------------------------

def _ratio(num: float, den: float) -> float:
    return num / den if den else 0.0


@dataclass
class CostProfile:
    """Attributed device cost of one compiled decode variant."""

    scheme: str
    bucket: int
    uniform: bool
    protection: dict
    model: dict
    other: dict
    total: dict
    by_file: dict = field(default_factory=dict)
    xla_cost: dict = field(default_factory=dict)
    tick_seconds_p50: Optional[float] = None

    @property
    def overhead_bytes_ratio(self) -> float:
        """Protection bytes per model byte (the SeDA overhead claim)."""
        return _ratio(self.protection["bytes"], self.model["bytes"])

    @property
    def overhead_flops_ratio(self) -> float:
        return _ratio(self.protection["flops"], self.model["flops"])

    @property
    def coverage(self) -> dict:
        """Fraction of total bytes/flops the protection+model split
        accounts for (the rest carried no source attribution)."""
        acc_b = self.protection["bytes"] + self.model["bytes"]
        acc_f = self.protection["flops"] + self.model["flops"]
        return {"bytes": _ratio(acc_b, self.total["bytes"]),
                "flops": _ratio(acc_f, self.total["flops"])}

    def roofline(self) -> dict:
        """Roofline time of the attributed cost, and — when a measured
        median tick is available — the achieved fraction of it."""
        t_compute = self.total["flops"] / PEAK_FLOPS
        t_memory = self.total["bytes"] / HBM_BW
        t_roof = max(t_compute, t_memory)
        out = {"compute_s": t_compute, "memory_s": t_memory,
               "roofline_s": t_roof,
               "bound": "compute" if t_compute >= t_memory else "memory"}
        if self.tick_seconds_p50 and self.tick_seconds_p50 > 0:
            out["measured_tick_s"] = self.tick_seconds_p50
            out["utilization"] = t_roof / self.tick_seconds_p50
        return out

    def to_dict(self) -> dict:
        return {
            "scheme": self.scheme, "bucket": self.bucket,
            "uniform": self.uniform,
            "protection": dict(self.protection), "model": dict(self.model),
            "other": dict(self.other), "total": dict(self.total),
            "overhead_bytes_ratio": self.overhead_bytes_ratio,
            "overhead_flops_ratio": self.overhead_flops_ratio,
            "coverage": self.coverage,
            "roofline": self.roofline(),
            "xla_cost": dict(self.xla_cost),
            "by_file": {k: dict(v) for k, v in sorted(self.by_file.items())},
        }


def profile_decode(engine, bucket: Optional[int] = None,
                   uniform: bool = False) -> CostProfile:
    """Lower + compile one decode variant and attribute its HLO cost.

    This is the expensive explicit path (one XLA compile per new
    (bucket, uniform) pair — cached by the engine's jit cache); the
    lazy gauges only ever read profiles already computed this way.
    """
    if bucket is None:
        bucket = engine.pages_per_slot
    args = engine._decode_analysis_args(bucket)
    compiled = engine._decode_fn_for(bucket, uniform).lower(*args).compile()
    attr = attribute_hlo(compiled.as_text())
    xla = compiled.cost_analysis()
    if isinstance(xla, (list, tuple)):
        xla = xla[0] if xla else {}
    xla = {k: v for k, v in dict(xla or {}).items()
           if k in ("flops", "bytes accessed")}
    tick_hist = engine.metrics.histograms.get("tick_seconds")
    p50 = None
    if tick_hist is not None and tick_hist.count:
        p50 = tick_hist.percentile(50)
        if math.isnan(p50):
            p50 = None
    return CostProfile(
        scheme=engine.scheme, bucket=bucket, uniform=uniform,
        protection=attr["protection"], model=attr["model"],
        other=attr["other"], total=attr["total"], by_file=attr["by_file"],
        xla_cost=xla, tick_seconds_p50=p50)
