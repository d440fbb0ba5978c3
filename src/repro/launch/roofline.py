"""Roofline accounting from compiled HLO (ROOFLINE ANALYSIS deliverable).

``compiled.cost_analysis()`` counts `while` bodies ONCE (verified on this
jax build), which would undercount a scan-over-layers model by ~n_layers×.
This module therefore does its own HLO-text accounting with trip-count
multipliers:

- **dot FLOPs**: every ``dot`` op contributes 2·|out|·K (K = contracted
  extent from the lhs shape + contracting dims); dots inside fusions are
  counted via the fusion's called computation.
- **memory bytes**: per top-level op, output bytes + operand bytes — the
  post-fusion HBM-traffic model (each fusion reads its inputs and writes
  its outputs once).
- **collective bytes**: payload (output) bytes of all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute, bucketed by kind.
- `while` ops multiply their body+condition cost by the
  ``known_trip_count`` from backend_config; conditionals take the max
  branch; fusions/calls recurse for FLOPs only.

The compiled module is already SPMD-partitioned, so all numbers are
PER-DEVICE. Hardware constants come from :data:`PEAKS`, one entry per
``jax.Device.device_kind``; a kind that is not in the table is an error.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Roofline hardware constants bundled with the time formulas — shared
    by the HLO report below and the plan-time cost model in
    :mod:`repro.core.plan_search` (which scores candidate schedules before
    any HLO exists)."""

    peak_flops: float             # FLOP/s per chip
    hbm_bw: float                 # bytes/s per chip
    ici_bw: float                 # bytes/s per interconnect link

    def compute_s(self, flops: float) -> float:
        return flops / self.peak_flops

    def memory_s(self, nbytes: float) -> float:
        return nbytes / self.hbm_bw

    def collective_s(self, nbytes: float) -> float:
        return nbytes / self.ici_bw

    def bound_s(self, flops: float, mem_bytes: float,
                coll_bytes: float) -> float:
        """Roofline bound: on-chip terms overlap (max), network adds."""
        return max(self.compute_s(flops), self.memory_s(mem_bytes)) \
            + self.collective_s(coll_bytes)


#: ``device_kind`` of a TPU v5e chip as JAX reports it.
V5E = "TPU v5 lite"

#: Published per-chip peaks keyed by ``jax.Device.device_kind``. Source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
#: at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (four links of
#: 50 GB/s).
PEAKS = {
    V5E: HardwareModel(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def hardware_model(device_kind: str) -> HardwareModel:
    """The peaks of ``device_kind``; raises on a kind with no published
    entry rather than reporting a share of someone else's peak."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\([^)]*\)|[a-z]+\d*\[[\d,]*\]\S*)\s*"
    r"([\w\-]+)\((.*)$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"(?:calls|body|to_apply)=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_LHS_BDIMS_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    mem_bytes: float = 0.0
    coll_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVE_KINDS})

    def add(self, other: "Cost", mult: float = 1.0,
            mem: bool = True) -> None:
        self.flops += other.flops * mult
        if mem:
            self.mem_bytes += other.mem_bytes * mult
        for k in COLLECTIVE_KINDS:
            self.coll_bytes[k] += other.coll_bytes[k] * mult

    @property
    def total_coll(self) -> float:
        return sum(self.coll_bytes.values())


class HloAnalyzer:
    def __init__(self, hlo_text: str):
        self.computations: Dict[str, List[dict]] = {}
        self.entry: Optional[str] = None
        self._parse(hlo_text)
        self._cost_cache: Dict[str, Cost] = {}

    # ------------------------------------------------------------------
    def _parse(self, text: str) -> None:
        cur_name = None
        cur_ops: List[dict] = []
        shapes: Dict[str, str] = {}
        for line in text.splitlines():
            # computation header: `%name (params...) -> type {` or
            # `ENTRY %name (...) ... {` — params may nest parens/brackets,
            # so key off the leading `%name (` + trailing `{` instead.
            header = re.match(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(", line)
            if (header and line.rstrip().endswith("{")
                    and "=" not in line.split("(")[0]):
                if cur_name is not None:
                    self.computations[cur_name] = cur_ops
                cur_name = header.group(2)
                cur_ops = []
                shapes = {}
                if header.group(1):
                    self.entry = cur_name
                continue
            if line.strip() == "}":
                if cur_name is not None:
                    self.computations[cur_name] = cur_ops
                    cur_name = None
                continue
            m = _OP_RE.match(line)
            if not m or cur_name is None:
                continue
            name, type_str, opcode, rest = m.groups()
            shapes[name] = type_str
            cur_ops.append({
                "name": name, "type": type_str, "opcode": opcode,
                "line": line, "shapes": shapes,
            })
        if cur_name is not None:
            self.computations[cur_name] = cur_ops

    # ------------------------------------------------------------------
    def _dot_flops(self, op: dict) -> float:
        out_dims = _shape_dims(op["type"])
        out_elems = 1
        for d in out_dims:
            out_elems *= d
        # contracted extent from the lhs operand's shape
        lhs_name_m = re.search(r"\(%?([\w\.\-]+)", op["line"].split("(", 1)[1]
                               if "(" in op["line"] else "")
        # simpler: first operand inside dot(...)
        args = op["line"].split(op["opcode"] + "(", 1)[-1]
        first = re.match(r"\s*%?([\w\.\-]+)", args)
        K = 1
        if first:
            lhs_shape = op["shapes"].get(first.group(1))
            if lhs_shape:
                dims = _shape_dims(lhs_shape)
                cm = _LHS_CDIMS_RE.search(op["line"])
                if cm and cm.group(1):
                    for idx in cm.group(1).split(","):
                        i = int(idx)
                        if i < len(dims):
                            K *= dims[i]
        return 2.0 * out_elems * K

    def _op_operand_bytes(self, op: dict) -> int:
        """HBM reads for one top-level op.

        For fusions, an operand that is only dynamic-sliced/gathered inside
        the fused computation is charged at the slice-output size, not the
        full array — otherwise a scan-over-layers model would be charged
        L× its weight stack (the slice-per-iteration pattern).
        """
        args = op["line"].split(op["opcode"] + "(", 1)
        if len(args) != 2:
            return 0
        # operand list ends at the first ')' (attrs like calls=%comp follow)
        operand_names = [m.group(1) for m in
                         re.finditer(r"%([\w\.\-]+)", args[1].split(")", 1)[0])]
        sliced_params = {}
        if op["opcode"] == "fusion":
            cm = _CALLS_RE.search(op["line"])
            comp = cm.group(1) if cm else None
            if comp in self.computations:
                sliced_params = self._sliced_param_reads(comp)
        total = 0
        for idx, name in enumerate(operand_names):
            t = op["shapes"].get(name)
            if not t:
                continue
            full = _shape_bytes(t)
            if idx in sliced_params:
                total += min(full, sliced_params[idx])
            else:
                total += full
        return total

    def _sliced_param_reads(self, comp: str) -> Dict[int, int]:
        """param index -> effective read bytes, for params consumed ONLY by
        dynamic-slice/gather ops inside the fused computation."""
        ops = self.computations.get(comp, [])
        param_idx: Dict[str, int] = {}
        for o in ops:
            if o["opcode"] == "parameter":
                pm = re.search(r"parameter\((\d+)\)", o["line"])
                if pm:
                    param_idx[o["name"]] = int(pm.group(1))
        uses: Dict[str, List[Tuple[str, int]]] = {n: [] for n in param_idx}
        for o in ops:
            if o["opcode"] == "parameter":
                continue
            args = o["line"].split(o["opcode"] + "(", 1)
            if len(args) != 2:
                continue
            for m in re.finditer(r"%([\w\.\-]+)", args[1]):
                if m.group(1) in uses:
                    uses[m.group(1)].append(
                        (o["opcode"], _shape_bytes(o["type"])))
        out: Dict[int, int] = {}
        for name, idx in param_idx.items():
            us = uses.get(name, [])
            # dynamic-update-slice writes into the param in place: it reads
            # nothing of it, so a param consumed only by slices/gathers/dus
            # is charged at the slice-read sizes (ds/gather outputs).
            slicelike = ("dynamic-slice", "gather", "dynamic-update-slice",
                         "convert", "bitcast")
            if us and all(k in slicelike for k, _ in us):
                out[idx] = sum(b for k, b in us if k in
                               ("dynamic-slice", "gather"))
        return out

    def _flops_only(self, comp: str) -> float:
        ops = self.computations.get(comp, [])
        total = 0.0
        for op in ops:
            if op["opcode"] == "dot":
                total += self._dot_flops(op)
            elif op["opcode"] in ("fusion", "call"):
                cm = _CALLS_RE.search(op["line"])
                if cm and cm.group(1) in self.computations:
                    total += self._flops_only(cm.group(1))
        return total

    def cost(self, comp: Optional[str] = None) -> Cost:
        comp = comp or self.entry
        if comp in self._cost_cache:
            return self._cost_cache[comp]
        c = Cost()
        for op in self.computations.get(comp, []):
            opcode = op["opcode"]
            out_bytes = _shape_bytes(op["type"])
            if opcode in ("parameter", "constant", "tuple",
                          "get-tuple-element", "bitcast", "copy"):
                # copy: XLA's copy-elision/donation removes loop-carry
                # copies at runtime; charging them would bill every scan
                # iteration the full carried state (verified to dominate
                # decode cells spuriously).
                continue
            if opcode == "while":
                trip = 1
                tm = _TRIP_RE.search(op["line"])
                if tm:
                    trip = int(tm.group(1))
                body = _CALLS_RE.search(op["line"])
                cond = _COND_RE.search(op["line"])
                if body and body.group(1) in self.computations:
                    c.add(self.cost(body.group(1)), mult=trip)
                if cond and cond.group(1) in self.computations:
                    c.add(self.cost(cond.group(1)), mult=trip)
                continue
            if opcode == "conditional":
                bm = _BRANCHES_RE.search(op["line"])
                if bm:
                    best = Cost()
                    for b in re.findall(r"%?([\w\.\-]+)", bm.group(1)):
                        if b in self.computations:
                            bc = self.cost(b)
                            if bc.flops >= best.flops:
                                best = bc
                    c.add(best)
                continue
            if opcode == "call":
                cm = _CALLS_RE.search(op["line"])
                if cm and cm.group(1) in self.computations:
                    c.add(self.cost(cm.group(1)))
                continue
            # leaf op: memory traffic model = out + operands.
            # dynamic-update-slice writes only the update in place — charge
            # the update operand, not the full array (otherwise a decode
            # step's KV-cache write would be charged cache_size × layers).
            # The same applies to fusions whose ROOT is a dus (scan ys
            # writes land in such fusions).
            is_dus = opcode == "dynamic-update-slice"
            if opcode == "fusion":
                cm0 = _CALLS_RE.search(op["line"])
                if cm0 and cm0.group(1) in self.computations:
                    inner = self.computations[cm0.group(1)]
                    # in-place fusion: contains a dus as large as the fusion
                    # output (possibly followed by converts/bitcasts)
                    for io in inner:
                        if io["opcode"] == "dynamic-update-slice" and \
                                _shape_bytes(io["type"]) >= out_bytes // 2:
                            is_dus = True
                            break
            if is_dus:
                ops_bytes = self._op_operand_bytes(op)
                c.mem_bytes += max(ops_bytes - out_bytes, 0)
                if opcode == "fusion":
                    cm0 = _CALLS_RE.search(op["line"])
                    if cm0 and cm0.group(1) in self.computations:
                        c.flops += self._flops_only(cm0.group(1))
                continue
            if opcode == "convert":
                # pure dtype conversions are XLA:CPU artifacts — the CPU
                # backend upconverts bf16 dot operands to f32 (whole-KV-cache
                # converts on decode cells). TPU's MXU is natively
                # bf16×bf16→f32, so these ops don't exist on the target.
                continue
            c.mem_bytes += out_bytes + self._op_operand_bytes(op)
            if opcode == "dot":
                c.flops += self._dot_flops(op)
            elif opcode == "fusion":
                cm = _CALLS_RE.search(op["line"])
                if cm and cm.group(1) in self.computations:
                    c.flops += self._flops_only(cm.group(1))
            for kind in COLLECTIVE_KINDS:
                if opcode.startswith(kind):
                    c.coll_bytes[kind] += out_bytes
                    break
        self._cost_cache[comp] = c
        return c


def roofline_report(hlo_text: str, *, device_kind: str,
                    model_flops_per_device: float = 0.0) -> Dict:
    """Per-device roofline terms from a compiled SPMD HLO module, against
    the peaks of ``device_kind`` (raises on a kind not in :data:`PEAKS`)."""
    hw = hardware_model(device_kind)
    an = HloAnalyzer(hlo_text)
    c = an.cost()
    compute_t = hw.compute_s(c.flops)
    memory_t = hw.memory_s(c.mem_bytes)
    coll_t = hw.collective_s(c.total_coll)
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": coll_t}
    dominant = max(terms, key=terms.get)
    bound = max(compute_t, memory_t, coll_t)
    out = {
        "hlo_dot_flops_per_dev": c.flops,
        "hlo_mem_bytes_per_dev": c.mem_bytes,
        "hlo_coll_bytes_per_dev": c.coll_bytes,
        **terms,
        "dominant": dominant,
        "roofline_bound_s": bound,
        "compute_fraction_at_bound": (compute_t / bound) if bound else 0.0,
    }
    if model_flops_per_device:
        out["model_flops_per_dev"] = model_flops_per_device
        out["useful_flops_ratio"] = (model_flops_per_device / c.flops
                                     if c.flops else 0.0)
    return out
