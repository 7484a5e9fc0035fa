"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), all in seconds, against the peaks
of the chip named by its ``device_kind`` (:data:`PEAKS`):

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bandwidth
    collective = collective_bytes_per_device / ICI_link_bandwidth

``compiled.cost_analysis()`` is per-device (the SPMD-partitioned module).
Collective bytes are not in cost_analysis: we parse the optimized HLO text
and sum the *result* sizes of all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute ops (result size ~= ring-transfer bytes per
device up to the 2(n-1)/n factor; the convention is recorded in
EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses
import json
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Per-chip peaks: bf16 FLOP/s, HBM bytes/s, one ICI link's bytes/s."""

    flops_bf16: float
    hbm_bw: float
    ici_link_bw: float
    source: str


#: Peaks keyed by ``jax.Device.device_kind``.  A kind missing here is an
#: error (:func:`peaks_for`), never a silent default.
PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "819 GB/s HBM, 1,600 Gbit/s ICI per chip (4 links)",
    ),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; raises ``ValueError`` for a chip with
    no entry in :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device_kind {device_kind!r}; known kinds: "
            f"{', '.join(sorted(PEAKS))}"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every array shape in an HLO type string (handles
    tuples like (f32[8,128], f32[8,128]))."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind result bytes summed over the module (per device).

    Start/done async pairs are counted once (the -start op carries the
    shape; '-done' lines are skipped)."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        # Match "  %name = <type> op-name(" or "name = <type> op-name("
        m = re.match(r"(?:%|\w|\.|-)+\s*=\s*(.+?)\s+([\w-]+)\(", line)
        if not m:
            continue
        type_str, op = m.groups()
        for kind in _COLLECTIVES:
            if op == kind or op == kind + "-start":
                out[kind] += _shape_bytes(type_str)
    return out


@dataclasses.dataclass
class Roofline:
    device_kind: str
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_breakdown: dict
    model_flops_global: float
    peak_memory_per_device: float

    def __post_init__(self) -> None:
        peaks_for(self.device_kind)  # reject an unknown chip up front

    @property
    def peaks(self) -> ChipPeaks:
        return peaks_for(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.peaks.flops_bf16

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_device / self.peaks.ici_link_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO FLOPs x devices): how much compiled compute
        is 'useful' (catches remat recompute, causal-mask waste, MoE
        capacity padding)."""
        total = self.flops_per_device * self.n_devices
        return self.model_flops_global / total if total else 0.0

    @property
    def roofline_time(self) -> float:
        """Lower-bound step time: max of the three terms (perfect overlap)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute roofline fraction (the score): time the chips
        *must* spend on model FLOPs divided by the bound step time."""
        ideal = self.model_flops_global / (
            self.n_devices * self.peaks.flops_bf16)
        return ideal / self.roofline_time if self.roofline_time else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(
            t_compute=self.t_compute, t_memory=self.t_memory,
            t_collective=self.t_collective, bottleneck=self.bottleneck,
            useful_flops_ratio=self.useful_flops_ratio,
            roofline_time=self.roofline_time,
            roofline_fraction=self.roofline_fraction,
        )
        return d


def analyze(compiled, *, device_kind: str, arch: str, shape: str,
            mesh_name: str, n_devices: int,
            model_flops_global: float) -> Roofline:
    """Roofline terms from the compiled artifact, against the peaks of
    ``device_kind`` (the chip the artifact was compiled for).

    Uses the loop-aware HLO walker (repro.roofline.hlo_costs) rather than
    ``compiled.cost_analysis()``: XLA's built-in analysis counts ``while``
    bodies once, which undercounts every scanned stack by ~depth x
    (validated to 0.1% on known workloads; see tests/test_roofline.py).
    """
    from repro.roofline import hlo_costs

    hlo = compiled.as_text()
    costs = hlo_costs.analyze_hlo(hlo)
    coll = {k: float(v) for k, v in costs.collective.items()}
    mem = compiled.memory_analysis()
    peak = (
        getattr(mem, "temp_size_in_bytes", 0)
        + getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        - getattr(mem, "alias_size_in_bytes", 0)
    )
    return Roofline(
        device_kind=device_kind, arch=arch, shape=shape, mesh=mesh_name, n_devices=n_devices,
        flops_per_device=float(costs.flops),
        bytes_per_device=float(costs.bytes),
        coll_bytes_per_device=float(sum(coll.values())),
        coll_breakdown=coll,
        model_flops_global=model_flops_global,
        peak_memory_per_device=float(peak),
    )


def save_json(path: str, roof: Roofline) -> None:
    with open(path, "w") as f:
        json.dump(roof.to_dict(), f, indent=1)
