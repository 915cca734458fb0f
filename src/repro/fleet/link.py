"""Compressed link boundary for the fleet engine.

Wires the existing-but-previously-unused ``kernels/quant`` int8 Pallas link
compressor into ``SplitStep`` as an opt-in boundary, and turns smashed
tensor shapes into the per-step link constants (wire bytes / time / energy)
that flow into the campaign's energy accounting next to the FLOP-derived
compute constants from ``core.flops``.

Byte accounting follows ``core.link.LinkConfig.wire_bytes``: the int8 wire
format is 1 byte per element (``dtype_bytes=1`` effective payload) plus one
f32 scale per quantizer row — the kernel scales per row of the flattened
(rows, last_dim) tensor, so the overhead is 4/last_dim bytes per element
(``scale_block=last_dim`` is passed through). That makes the shrink vs f32
shape-dependent: ~3.98x for wide (>=256-channel) smashed tensors, ~3.2x for
a 16-channel CNN cut. The compressor itself is the
straight-through estimator from ``kernels.quant.ops``: forward
quantize→dequantize, backward identity, so the cut gradient keeps flowing
through one autodiff program (and vmaps over the fleet's client axis).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

from ..core.link import LinkConfig
from ..core.split import SplitStep


@dataclasses.dataclass(frozen=True)
class FleetLink:
    """One edge<->server link: config + the kernel path of its compressor.

    ``use_pallas`` selects the Pallas kernel vs its jnp reference;
    ``interpret`` (set from ``kernels.dispatch.resolve_link_kernel``) runs
    that kernel in interpret mode off-accelerator. ``mesh`` is the fleet
    mesh the kernel runs per device on.
    """
    config: LinkConfig = LinkConfig()
    use_pallas: bool = False
    interpret: bool = False
    mesh: Optional[Any] = None

    @property
    def compressed(self) -> bool:
        return self.config.compress == "int8"

    def boundary(self) -> Optional[Callable]:
        """The smashed-tensor boundary fn, or None for an uncompressed link."""
        if not self.compressed:
            return None
        from ..kernels.quant.ops import make_link_compress
        return make_link_compress(use_pallas=self.use_pallas,
                                  interpret=self.interpret, mesh=self.mesh)

    def attach(self, step: SplitStep) -> SplitStep:
        """Opt the split step into this link (compose with any existing
        boundary, e.g. a sharding constraint: compress first, constrain the
        compressed activations after)."""
        boundary = self.boundary()
        if boundary is None:
            return step
        existing = step.link_constraint
        if existing is not None:
            inner = boundary
            boundary = lambda sm: existing(inner(sm))  # noqa: E731
        return dataclasses.replace(step, link_constraint=boundary)

    # ---- per-step link constants (hoisted out of the hot loop) ----

    def step_wire_bytes(self, smashed_sd) -> float:
        """Wire bytes of ONE split step: smashed fwd + cut-gradient return,
        both compressed when the link is int8. The scale overhead uses the
        actual quantizer row length (the smashed tensor's last dim)."""
        sm_bytes = float(smashed_sd.size) * smashed_sd.dtype.itemsize
        return self.config.roundtrip_bytes(sm_bytes, smashed_sd.dtype.itemsize,
                                           scale_block=smashed_sd.shape[-1])

    def step_time_s(self, smashed_sd) -> float:
        """Eq. (8) on the roundtrip wire volume (delegates to LinkConfig so
        the formula lives in one place)."""
        sm_bytes = float(smashed_sd.size) * smashed_sd.dtype.itemsize
        return 2.0 * self.config.transfer_time_s(
            sm_bytes, smashed_sd.dtype.itemsize,
            scale_block=smashed_sd.shape[-1])

    def step_energy_j(self, smashed_sd) -> float:
        """Radio energy of one step's link roundtrip (edge-side transmit)."""
        return self.step_time_s(smashed_sd) * self.config.radio_power_w
