"""Public entry points for the model-side Pallas kernels.

Each kernel resolves ``interpret=None`` by backend (see
:func:`repro.kernels.resolve_interpret`): compiled through Mosaic on a
TPU, the same kernel body in interpret mode elsewhere (unit tests on the
CPU), validated against ``ref.py``.
"""
from __future__ import annotations

from repro.kernels.adaln import adaln_modulate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.groupnorm_silu import groupnorm_silu

__all__ = ["adaln_modulate", "flash_attention", "groupnorm_silu"]
