"""Roofline-term extraction from compiled HLO.

``cost_analysis`` provides per-device FLOPs and HBM bytes, but NOT
collective traffic — we parse the optimized HLO text, summing output bytes
of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, with while-loop trip-count multipliers inferred from
the loop condition (layer scans execute their collectives n_layers times).

Roofline terms are priced against ``PEAKS``, keyed by the chip's
``Device.device_kind``; a kind missing from the table is an error, never a
default.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

# Published per-chip peaks keyed by jax ``Device.device_kind``: dense bf16
# FLOP/s, HBM bytes/s, and bytes/s per ICI link. Source for "TPU v5 lite"
# (v5e): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect over 4 links.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add them to hlo_analysis.PEAKS with their "
            f"source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """bytes of 'bf16[16,32]' or tuple '(f32[2]{0}, f32[3]{0})'."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo: str) -> Dict[str, list]:
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->.*\{\s*$",
                     line)
        if m is None:
            m2 = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\{\s*$", line)
            m = m2
        if m:
            cur = m.group(1)
            comps[cur] = []
            if "ENTRY" in line:
                comps["__entry__"] = comps[cur]
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    return comps


_WHILE_RE = re.compile(
    r"while\(.*?\),\s*(?:condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)"
    r"|body=%?([\w\.\-]+),\s*condition=%?([\w\.\-]+))")


def _trip_count(cond_lines) -> int:
    best = 1
    for line in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", line):
            best = max(best, int(m.group(1)))
    return best


def collective_bytes(hlo: str) -> dict:
    """Per-device collective bytes (output sizes, trip-count weighted)."""
    comps = _split_computations(hlo)
    # multiplier per computation from (possibly nested) while loops
    mult: Dict[str, float] = {name: 1.0 for name in comps}
    changed = True
    iters = 0
    while changed and iters < 10:
        changed = False
        iters += 1
        for name, lines in comps.items():
            for line in lines:
                for wm in _WHILE_RE.finditer(line):
                    cond = wm.group(1) or wm.group(4)
                    body = wm.group(2) or wm.group(3)
                    trip = _trip_count(comps.get(cond, []))
                    for target in (body, cond):
                        if target in mult:
                            new = mult[name] * (trip if target == body else trip)
                            if new > mult[target]:
                                mult[target] = new
                                changed = True
    per_kind: Dict[str, float] = {}
    count = 0
    for name, lines in comps.items():
        m = mult.get(name, 1.0)
        for line in lines:
            lm = re.match(r"\s*(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(.+?)\s+"
                          r"([a-z\-]+)(?:-start)?\(", line)
            if not lm:
                continue
            op = lm.group(2)
            if op.endswith("-done"):
                continue
            base = None
            for c in _COLLECTIVES:
                if op == c or op == c + "-start":
                    base = c
            if base is None:
                continue
            b = _shape_bytes(lm.group(1)) * m
            per_kind[base] = per_kind.get(base, 0.0) + b
            count += 1
    return {"total_bytes": sum(per_kind.values()), "by_kind": per_kind,
            "n_collective_ops": count}


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float
    n_devices: int
    device_kind: str
    model_flops: float = 0.0

    def __post_init__(self):
        self.peaks = peaks(self.device_kind)

    @property
    def compute_s(self):
        return self.flops_per_device / self.peaks["flops"]

    @property
    def memory_s(self):
        return self.hbm_bytes_per_device / self.peaks["hbm_bw"]

    @property
    def collective_s(self):
        return self.collective_bytes_per_device / self.peaks["ici_bw"]

    @property
    def bottleneck(self):
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self):
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self):
        total = self.flops_per_device * self.n_devices
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self):
        """MODEL_FLOPS-based MFU at the roofline step time: the score."""
        if self.step_time_s == 0:
            return 0.0
        return (self.model_flops / self.n_devices / self.step_time_s
                / self.peaks["flops"])

    def to_dict(self):
        return {
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "n_devices": self.n_devices,
            "device_kind": self.device_kind,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time_s,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def analyze(compiled, model_flops: float, n_devices: int,
            device_kind: str) -> dict:
    """Roofline terms from the compiled artifact, priced for a chip of
    ``device_kind`` (see ``PEAKS``).

    FLOPs/bytes come from the HLO-text cost model (launch.hlo_cost) because
    XLA's cost_analysis visits while bodies once — layer scans would be
    undercounted x n_layers. The raw cost_analysis numbers are recorded for
    reference.
    """
    from .hlo_cost import HloModule
    cost = compiled.cost_analysis()
    mod = HloModule(compiled.as_text())
    flops = float(max(mod.flops(), float(cost.get("flops", 0.0))))
    byts = float(max(mod.bytes_accessed(),
                     float(cost.get("bytes accessed", 0.0))))
    coll = mod.collective_bytes()
    rl = Roofline(flops, byts, coll["total_bytes"], n_devices, device_kind,
                  model_flops)
    mem = compiled.memory_analysis()
    return {
        "roofline": rl.to_dict(),
        "collectives": coll,
        "xla_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                              "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        },
    }
