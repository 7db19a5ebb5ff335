"""Static FLOPs/bytes accounting over lowered HLO — the cost half of the
roofline planner (docs/analysis.md "Cost model & planner").

``analysis/hlo.py`` already turns HLO text into structured collective
records; this module walks the SAME text for the compute side: per
instruction, how many floating-point operations it performs and how many
HBM bytes it moves (operand + result traffic), rolled up per op *family*
— the PERF.md roofline families, classified by the ONE shared classifier
(``utils/profiling.op_family``) the xplane trace summarizer also uses, so
a static cost row and a measured trace row can never disagree about what
"multiply_add_fusion" means.

Accounting rules (a planning model, not a simulator):

- ``dot``           — 2 · output elements · contraction extent (from the
                      lhs operand shape + ``lhs_contracting_dims``).
- ``convolution``   — 2 · output elements · kernel taps (spatial extents ·
                      input features, from ``dim_labels``); padding is NOT
                      subtracted, so SAME-padded convs overcount by the
                      border fraction — which is why ``audit`` cross-checks
                      against XLA's own ``cost_analysis()`` and scales the
                      family split to the exact total when available.
- reduce / window ops — one flop per reduced element.
- elementwise/transcendental — one flop per output element.
- **HBM bytes** — operand + result bytes of every *top-level* instruction
  (entry computation); instructions inside fused computations move no HBM
  (their intermediates live in registers/VMEM), so only the fusion's own
  boundary traffic counts. Zero-cost ops (bitcast, tuple plumbing,
  parameters, constants) are skipped. On UNOPTIMIZED HLO (``lower()``
  without ``compile()``, the trainer's cheap path) nothing is fused yet,
  so bytes are an upper bound — ``StepCost.source`` records which flavor
  produced the numbers.
- **ICI bytes** — the auditor's per-collective ring estimates
  (``hlo.CollectiveOp.est_ici_bytes``), summed.
- While/scan bodies are counted ONCE per step (static trip counts are not
  recoverable from HLO); ``loop_flops`` records how much of the total sits
  inside loops so a scanned step's undercount is visible.

Family attribution: fusion instructions classify by their content-derived
name (shared classifier); standalone flop-bearing ops (convs/dots that XLA
did not fuse — the CPU backend mostly) classify by their jax metadata
direction: an op whose ``op_name`` path crosses ``transpose(`` is backward
(``multiply_add_fusion`` — wgrad + update territory), else forward
(``convert_reduce_fusion``), mirroring what the TPU fusion names encode.

Everything here is pure text processing — no jax import — so the cost
model is usable from host-side tools (obs, report rendering) for free.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from pytorch_distributed_nn_tpu.analysis import hlo as hlo_mod
from pytorch_distributed_nn_tpu.utils.profiling import (  # noqa: F401
    FAMILIES,
    op_family,
)

__all__ = [
    "FAMILIES",
    "op_family",
    "FamilyCost",
    "StepCost",
    "step_cost_from_hlo",
    "DecodeCost",
    "decode_phase_cost",
]

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.-]+)\s*=\s*"
    r"(?P<type>\([^)]*\)|\S+)\s+(?P<op>[\w-]+)\((?P<rest>.*)$"
)
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_WINDOW_SIZE_RE = re.compile(r"window=\{[^}]*?size=([\dx]+)")
_DIM_LABELS_RE = re.compile(r"dim_labels=\w+_(\w+)->")
_CALLS_RE = re.compile(r"calls=%?([\w.-]+)")
_OPERAND_NAME_RE = re.compile(r"%?([A-Za-z_][\w.-]*)")

#: ops that move no bytes and perform no flops (shape/layout/plumbing)
_FREE_OPS = frozenset((
    "parameter", "constant", "bitcast", "tuple", "get-tuple-element",
    "after-all", "opt-barrier", "partition-id", "replica-id",
))

#: one flop per OUTPUT element
_EW_FLOP_OPS = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "logistic", "rsqrt", "sqrt", "cbrt", "power", "negate", "abs", "sign",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even", "atan2",
    "compare", "select", "and", "or", "not", "xor", "clamp", "cosine",
    "sine", "is-finite", "remainder", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "erf", "expm1",
))

#: one flop per INPUT (first operand) element
_REDUCE_FLOP_OPS = frozenset((
    "reduce", "select-and-scatter", "scatter", "sort",
))


def _num_elements(dims: Tuple[int, ...]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _shape_bytes(shapes) -> int:
    return sum(
        hlo_mod._DTYPE_BYTES.get(dt, 4) * _num_elements(dims)
        for dt, dims in shapes
    )


def _split_call(rest: str) -> Tuple[str, str]:
    """Split an instruction tail into (operand region, attribute tail) at
    the opcode's matching close paren. ``rest`` starts right after the
    opening paren."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i], rest[i + 1:]
    return rest, ""


@dataclasses.dataclass
class _Instr:
    name: str
    op: str
    shapes: tuple           # result (dtype, dims) tuple(s)
    operands: List[str]     # operand value names (same computation)
    attrs: str              # text after the call's close paren
    computation: str


@dataclasses.dataclass
class FamilyCost:
    """Per-family accumulator of the static step cost."""

    flops: float = 0.0
    hbm_bytes: float = 0.0
    count: int = 0

    def to_dict(self) -> dict:
        return {
            "flops": round(self.flops, 1),
            "hbm_bytes": round(self.hbm_bytes, 1),
            "count": self.count,
        }


@dataclasses.dataclass
class StepCost:
    """Static cost of one compiled step program (per program instance:
    per-device for SPMD-partitioned text, global for pre-partition text).
    """

    families: Dict[str, FamilyCost]
    flops: float                     # best estimate (XLA-scaled if known)
    hlo_flops: float                 # raw text-walk total
    hbm_bytes: float
    ici_bytes: float
    xla_flops: Optional[float] = None
    xla_bytes: Optional[float] = None
    loop_flops: float = 0.0
    source: str = "optimized"        # optimized | lowered

    def to_dict(self) -> dict:
        return {
            "flops": round(self.flops, 1),
            "hlo_flops": round(self.hlo_flops, 1),
            "xla_flops": self.xla_flops,
            "hbm_bytes": round(self.hbm_bytes, 1),
            "xla_bytes": self.xla_bytes,
            "ici_bytes": round(self.ici_bytes, 1),
            "loop_flops": round(self.loop_flops, 1),
            "source": self.source,
            "families": {
                f: fc.to_dict() for f, fc in sorted(self.families.items())
            },
        }

    def to_text(self) -> str:
        lines = [
            f"step cost ({self.source} HLO):",
            f"  FLOPs: {self.flops / 1e9:.3f} GFLOP"
            + (f" (XLA cost_analysis: {self.xla_flops / 1e9:.3f})"
               if self.xla_flops else "")
            + (f", {self.loop_flops / 1e9:.3f} G inside loop bodies "
               "(counted once)" if self.loop_flops else ""),
            f"  HBM bytes: {self.hbm_bytes / 1e6:.2f} MB (operand+result)",
            f"  ICI bytes: {self.ici_bytes / 1e6:.3f} MB (ring estimate)",
            "  per family:",
        ]
        for fam in FAMILIES:
            fc = self.families.get(fam, FamilyCost())
            lines.append(
                f"    {fam:<24} {fc.flops / 1e9:>10.3f} GFLOP  "
                f"{fc.hbm_bytes / 1e6:>9.2f} MB  x{fc.count}"
            )
        return "\n".join(lines)


def _dot_flops(instr: _Instr, table: Dict[str, tuple]) -> float:
    out = sum(_num_elements(dims) for _, dims in instr.shapes)
    m = _LHS_CONTRACT_RE.search(instr.attrs)
    contract = 1
    if m and instr.operands:
        lhs = table.get(instr.operands[0])
        if lhs:
            dims = lhs[0][1]
            for idx in (int(i) for i in m.group(1).split(",") if i):
                if idx < len(dims):
                    contract *= dims[idx]
    return 2.0 * out * contract


def _conv_flops(instr: _Instr, table: Dict[str, tuple]) -> float:
    out = sum(_num_elements(dims) for _, dims in instr.shapes)
    taps = 1
    if len(instr.operands) >= 2:
        rhs = table.get(instr.operands[1])
        m = _DIM_LABELS_RE.search(instr.attrs)
        if rhs and m:
            kdims = rhs[0][1]
            labels = m.group(1)
            for pos, ch in enumerate(labels):
                if pos < len(kdims) and (ch.isdigit() or ch == "i"):
                    taps *= kdims[pos]
    return 2.0 * out * taps


def _window_flops(instr: _Instr) -> float:
    out = sum(_num_elements(dims) for _, dims in instr.shapes)
    m = _WINDOW_SIZE_RE.search(instr.attrs)
    window = 1
    if m:
        for d in m.group(1).split("x"):
            if d:
                window *= int(d)
    return float(out * window)


def _instr_flops(instr: _Instr, table: Dict[str, tuple]) -> float:
    op = instr.op
    if op == "dot":
        return _dot_flops(instr, table)
    if op == "convolution":
        return _conv_flops(instr, table)
    if op == "reduce-window":
        return _window_flops(instr)
    if op in _REDUCE_FLOP_OPS:
        first = table.get(instr.operands[0]) if instr.operands else None
        return float(_num_elements(first[0][1])) if first else 0.0
    if op in _EW_FLOP_OPS:
        return float(sum(_num_elements(d) for _, d in instr.shapes))
    return 0.0


def _classify(instr: _Instr, owner_family: Optional[str]) -> str:
    """Family of one instruction.

    Flop-dominant standalone ops (conv/dot) split forward vs backward on
    their jax metadata path (``transpose(`` marks the cotangent program);
    everything else takes the shared name classifier — with instructions
    inside a fused computation inheriting the calling fusion's family
    (that name is what a trace would show).
    """
    if instr.op in ("dot", "convolution"):
        m = _OPNAME_RE.search(instr.attrs)
        if m and "transpose(" in m.group(1):
            return "multiply_add_fusion"
        return "convert_reduce_fusion"
    if owner_family is not None:
        return owner_family
    return op_family(instr.name)


def _parse_instructions(hlo_text: str):
    """Per computation: symbol table + instruction list."""
    spans = hlo_mod._computation_spans(hlo_text)
    lines = hlo_text.splitlines()
    if not spans:  # headerless fragment (tests): treat as one computation
        spans = [("<main>", 0, len(lines) - 1)]
    per_comp = {}
    for comp, lo, hi in spans:
        table: Dict[str, tuple] = {}
        instrs: List[_Instr] = []
        for line in lines[lo:hi + 1]:
            m = _INSTR_RE.match(line)
            if m is None:
                continue
            shapes = hlo_mod.parse_shapes(m.group("type"))
            name = m.group("name")
            table[name] = shapes
            call, attrs = _split_call(m.group("rest"))
            # operands reference earlier definitions of the SAME
            # computation (HLO prints topologically); restricting to the
            # symbol table drops inline operand types ("f32[...]" tokens
            # of optimized HLO) and attribute noise in one stroke
            seen = set()
            operands = []
            for t in _OPERAND_NAME_RE.findall(call):
                if t in table and t not in seen:
                    seen.add(t)
                    operands.append(t)
            instrs.append(_Instr(
                name=name, op=m.group("op"), shapes=shapes,
                operands=operands, attrs=attrs, computation=comp,
            ))
        per_comp[comp] = (table, instrs)
    return per_comp


@dataclasses.dataclass
class DecodeCost:
    """Static per-token cost of one autoregressive decode step
    (docs/analysis.md "Decode roofline").

    Decode is the serving path where the roofline's BANDWIDTH term
    finally bites: each generated token re-reads every weight byte
    (amortized over the decode batch) plus the sequence's whole KV
    cache, against a few FLOPs per weight — arithmetic intensity of
    O(batch) FLOP/byte, far left of any ridge point. The model here is
    the planning twin of :class:`StepCost`: closed-form from the decoder
    config. No benchmark cell serves a decoder yet, so its prediction has
    not been checked against tokens/s measured on the chip.
    """

    flops_per_token: float          # matmul + attention FLOPs, one token
    attn_flops_per_token: float     # the cache-length-dependent share
    weight_bytes: float             # params read per decode STEP (batch)
    kv_read_bytes_per_token: float  # cache panel read, one token
    kv_write_bytes_per_token: float
    batch: int
    cache_len: int

    @property
    def hbm_bytes_per_token(self) -> float:
        """HBM traffic billed to ONE token: its KV traffic plus its
        1/batch share of the weight read."""
        return (
            self.weight_bytes / max(1, self.batch)
            + self.kv_read_bytes_per_token
            + self.kv_write_bytes_per_token
        )

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops_per_token / max(1.0, self.hbm_bytes_per_token)

    def predicted_tokens_per_s(
        self, peak_flops_per_s: float, hbm_peak_bytes_per_s: float
    ) -> float:
        """Roofline-predicted per-sequence rate: each token pays the
        LARGER of its compute time and its HBM time (the classic
        max(flops/peak, bytes/bw) step model)."""
        t_flops = self.flops_per_token / max(1.0, peak_flops_per_s)
        t_hbm = self.hbm_bytes_per_token / max(1.0, hbm_peak_bytes_per_s)
        return 1.0 / max(t_flops, t_hbm, 1e-12)

    def to_dict(self) -> dict:
        return {
            "flops_per_token": round(self.flops_per_token, 1),
            "attn_flops_per_token": round(self.attn_flops_per_token, 1),
            "weight_bytes": round(self.weight_bytes, 1),
            "kv_read_bytes_per_token": round(
                self.kv_read_bytes_per_token, 1
            ),
            "kv_write_bytes_per_token": round(
                self.kv_write_bytes_per_token, 1
            ),
            "hbm_bytes_per_token": round(self.hbm_bytes_per_token, 1),
            "arithmetic_intensity": round(self.arithmetic_intensity, 3),
            "batch": self.batch,
            "cache_len": self.cache_len,
        }

    def to_text(self) -> str:
        return "\n".join([
            f"decode cost (batch {self.batch}, cache length "
            f"{self.cache_len}):",
            f"  FLOPs/token: {self.flops_per_token / 1e6:.3f} MFLOP "
            f"({self.attn_flops_per_token / 1e6:.3f} attention)",
            f"  HBM bytes/token: {self.hbm_bytes_per_token / 1e6:.3f} MB "
            f"(weights {self.weight_bytes / max(1, self.batch) / 1e6:.3f}"
            f" + KV read {self.kv_read_bytes_per_token / 1e6:.3f}"
            f" + KV write {self.kv_write_bytes_per_token / 1e6:.4f})",
            f"  arithmetic intensity: {self.arithmetic_intensity:.2f} "
            "FLOP/byte (decode is HBM-bound left of any ridge point)",
        ])


def decode_phase_cost(
    num_layers: int,
    d_model: int,
    d_ff: int,
    vocab_size: int,
    cache_len: int,
    batch: int = 1,
    weight_bytes_per_param: int = 4,
    kv_bytes_per_elem: int = 4,
) -> DecodeCost:
    """Closed-form per-token decode cost of a standard pre-LN decoder.

    Per layer, one token: QKV + output projections (4·d²) and the two
    MLP matmuls (2·d·d_ff), 2 FLOPs per MAC; attention reads the
    ``cache_len`` K/V panel twice (scores + weighted sum, 4·d·S). The
    tied LM head adds 2·d·vocab. Weight traffic per decode STEP is the
    full matmul parameter set (amortized over ``batch`` sequences); KV
    traffic is per token and does NOT amortize — which is why decode
    throughput scales with batch until the KV term dominates.
    """
    d, L = float(d_model), int(num_layers)
    matmul_params = L * (4 * d * d + 2 * d * d_ff) + d * vocab_size
    mm_flops = 2.0 * matmul_params
    attn_flops = 4.0 * d * float(cache_len) * L
    kv_read = 2.0 * float(cache_len) * d * L * kv_bytes_per_elem
    kv_write = 2.0 * d * L * kv_bytes_per_elem
    return DecodeCost(
        flops_per_token=mm_flops + attn_flops,
        attn_flops_per_token=attn_flops,
        weight_bytes=matmul_params * weight_bytes_per_param,
        kv_read_bytes_per_token=kv_read,
        kv_write_bytes_per_token=kv_write,
        batch=int(batch),
        cache_len=int(cache_len),
    )


def step_cost_from_hlo(
    hlo_text: str,
    xla_flops: Optional[float] = None,
    xla_bytes: Optional[float] = None,
    ici_bytes: Optional[float] = None,
    source: str = "optimized",
) -> StepCost:
    """Walk one HLO module's text into a :class:`StepCost`.

    ``xla_flops`` (from ``compiled.cost_analysis()`` / ``lowered
    .cost_analysis()``) is the exact-counting oracle: when given, the
    family split keeps the walk's *shares* but is scaled so the total
    matches XLA's number (padding-exact conv counts, etc.). ``ici_bytes``
    overrides the collective ring estimate (callers that already hold an
    audit Report pass its inventory through).
    """
    per_comp = _parse_instructions(hlo_text)
    loop_comps = hlo_mod.loop_computations(hlo_text)

    # computation -> family of the fusion instruction that calls it (the
    # name a trace row would carry); reducer regions inherit the caller's
    # family transitively via their own caller.
    owner: Dict[str, Optional[str]] = {}
    called = set()
    for _, instrs in per_comp.values():
        for ins in instrs:
            for ref in hlo_mod._CALLED_RE.findall(ins.attrs):
                called.add(ref)
            m = _CALLS_RE.search(ins.attrs)
            if m:
                called.add(m.group(1))
                if ins.op == "fusion":
                    owner[m.group(1)] = op_family(ins.name)

    families = {f: FamilyCost() for f in FAMILIES}
    total_flops = 0.0
    total_bytes = 0.0
    loop_flops = 0.0
    for comp, (table, instrs) in per_comp.items():
        top_level = comp not in called
        comp_owner = owner.get(comp)
        for ins in instrs:
            if ins.op in _FREE_OPS:
                continue
            flops = _instr_flops(ins, table)
            fam = _classify(ins, comp_owner)
            if flops:
                families[fam].flops += flops
                total_flops += flops
                if comp in loop_comps:
                    loop_flops += flops
            if top_level:
                nbytes = _shape_bytes(ins.shapes) + sum(
                    _shape_bytes(table[o]) for o in ins.operands
                    if o in table
                )
                if nbytes:
                    families[fam].hbm_bytes += nbytes
                    total_bytes += nbytes
            if flops or top_level:
                families[fam].count += 1

    if ici_bytes is None:
        ici_bytes = float(sum(
            op.est_ici_bytes for op in hlo_mod.parse_collectives(hlo_text)
        ))

    flops = total_flops
    if xla_flops and total_flops > 0:
        # exact-counting oracle: keep the walk's family SHARES, adopt
        # XLA's total (it subtracts conv padding, counts custom calls
        # it knows, etc.)
        scale = float(xla_flops) / total_flops
        for fc in families.values():
            fc.flops *= scale
        loop_flops *= scale
        flops = float(xla_flops)

    return StepCost(
        families=families,
        flops=flops,
        hlo_flops=total_flops,
        hbm_bytes=total_bytes,
        ici_bytes=float(ici_bytes),
        xla_flops=float(xla_flops) if xla_flops else None,
        xla_bytes=float(xla_bytes) if xla_bytes else None,
        loop_flops=loop_flops,
        source=source,
    )
