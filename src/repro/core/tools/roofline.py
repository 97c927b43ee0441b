"""Roofline tool — derives the three roofline terms from dry-run artifacts,
plus a batch-consuming :class:`RooflineTool` that accumulates the same terms
live from the columnar event stream.

Terms (per the assignment; the compiled SPMD module is the *per-device*
program, so parsed FLOPs/bytes are already per-chip and divide by per-chip
peaks — algebraically identical to global/(chips×peak)):

    compute    = HLO_FLOPs_per_chip    / peak_FLOP/s
    memory     = HLO_bytes_per_chip    / HBM_bw
    collective = coll_bytes_per_chip   / link_bw

Hardware constants: :data:`PEAKS`, keyed by ``jax.Device.device_kind``.
"""

from __future__ import annotations

import dataclasses

#: Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s inter-chip interconnect (``ici_bw`` is that split
#: over a v5e chip's four ICI links).  ``dci_bw`` and ``ici_latency`` are
#: modelled, not published.
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,      # bf16 FLOP/s per chip
        "hbm_bw": 819e9,           # bytes/s per chip
        "ici_bw": 50e9,            # bytes/s per ICI link (intra-pod)
        "dci_bw": 12.5e9,          # bytes/s inter-pod (DCI — the slow link
                                   # the compressed/overlapped pod sync
                                   # targets)
        "ici_latency": 1e-6,       # per-collective launch/sync latency
        "hbm_bytes": 16e9,         # capacity per chip
    },
}

#: the chip the analytic models (dry-run, lint, HLO overlap) target
ANALYTIC_TARGET = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a chip missing from :data:`PEAKS` is an
    error, never a default."""
    try:
        return dict(PEAKS[device_kind])
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to PEAKS with their "
                       f"source") from None


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    peak_flops: float
    model_flops_per_chip: float = 0.0
    hlo_flops_per_chip: float = 0.0

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Lower-bound step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved at the bound step time:
        useful-FLOPs/chip / peak / step_time."""
        if self.step_time_s <= 0:
            return 0.0
        return (self.model_flops_per_chip / self.peak_flops) / self.step_time_s

    @property
    def useful_flops_ratio(self) -> float:
        if self.hlo_flops_per_chip <= 0:
            return 0.0
        return self.model_flops_per_chip / self.hlo_flops_per_chip

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "step_time_lb_s": self.step_time_s,
            "roofline_fraction": self.roofline_fraction,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def roofline(flops_per_chip: float, hbm_bytes_per_chip: float,
             coll_bytes_per_chip: float, hw: dict,
             model_flops_per_chip: float = 0.0) -> Roofline:
    return Roofline(
        compute_s=flops_per_chip / hw["peak_flops"],
        memory_s=hbm_bytes_per_chip / hw["hbm_bw"],
        collective_s=coll_bytes_per_chip / hw["ici_bw"],
        peak_flops=hw["peak_flops"],
        model_flops_per_chip=model_flops_per_chip,
        hlo_flops_per_chip=flops_per_chip,
    )


def model_flops(n_params: float, n_tokens: float, training: bool = True,
                n_active_params: float | None = None) -> float:
    """MODEL_FLOPS = 6·N·D (train) or 2·N·D (inference fwd); MoE uses
    N_active."""
    n = n_active_params if n_active_params is not None else n_params
    return (6.0 if training else 2.0) * n * n_tokens


# ---------------------------------------------------------------------------
# Event-stream roofline accumulator (columnar tool)
# ---------------------------------------------------------------------------

import numpy as np                                        # noqa: E402

from ..events import EventKind                            # noqa: E402
from .base import PastaTool, register                     # noqa: E402


@register("roofline")
class RooflineTool(PastaTool):
    """Accumulates the three roofline terms from the event stream itself:
    per-chip HBM traffic from KERNEL_LAUNCH batches (``bytes × count``),
    wire bytes from COLLECTIVE batches (``size × mult``), and FLOPs from the
    COMPILE event's cost analysis.  Batch consumption is vectorized over the
    size/count columns; attrs are only touched on the (few) rows that carry
    them."""

    EVENTS = (EventKind.KERNEL_LAUNCH, EventKind.COLLECTIVE,
              EventKind.COMPILE)

    def __init__(self, device_kind: str = ANALYTIC_TARGET,
                 model_flops_per_chip: float = 0.0, **knobs):
        super().__init__(**knobs)
        self.hw = peaks(device_kind)
        self.model_flops_per_chip = model_flops_per_chip
        self.flops = 0.0
        self.hbm_bytes = 0.0
        self.coll_bytes = 0.0
        self.kernel_invocations = 0

    # scalar hooks — kept equivalent to on_batch (single-row fast path)
    def on_kernel_launch(self, ev):
        n = int(ev.attrs.get("count", 1))
        self.kernel_invocations += n
        self.hbm_bytes += float(ev.attrs.get("bytes", 0)) * n

    def on_collective(self, ev):
        self.coll_bytes += float(ev.size) * float(ev.attrs.get("mult", 1))

    def on_compile(self, ev):
        ca = ev.attrs.get("cost_analysis") or {}
        self.flops += float(ca.get("flops", 0.0))

    def on_batch(self, batch):
        kidx = batch.rows(EventKind.KERNEL_LAUNCH)
        if kidx.size:
            counts = (batch.counts[kidx] if batch.counts is not None
                      else np.ones(kidx.size, dtype=np.int64))
            self.kernel_invocations += int(counts.sum())
            byts = batch.attr_column("bytes", 0, rows=kidx, dtype=np.float64)
            self.hbm_bytes += float((byts * counts).sum())
        cidx = batch.rows(EventKind.COLLECTIVE)
        if cidx.size:
            mult = batch.attr_column("mult", 1, rows=cidx, dtype=np.float64)
            self.coll_bytes += float((batch.sizes[cidx] * mult).sum())
        for i in batch.rows(EventKind.COMPILE):
            a = batch.attrs_at(int(i))
            if a:
                ca = a.get("cost_analysis") or {}
                self.flops += float(ca.get("flops", 0.0))

    def finalize(self) -> dict:
        rl = roofline(self.flops, self.hbm_bytes, self.coll_bytes, self.hw,
                      model_flops_per_chip=self.model_flops_per_chip)
        out = rl.as_dict()
        out.update(kernel_invocations=self.kernel_invocations,
                   hbm_bytes=self.hbm_bytes, coll_bytes=self.coll_bytes,
                   flops=self.flops)
        return out
