"""A configuration's design, built from the compiler's gallery.

Without a schedule the design keeps the gallery's hand-written schedule.
With one (a configuration's ``schedule``, or a DSE variant's knobs: the
fields of ``DSEConfig``), its schedule is erased, the structural knobs are
applied and ``hls_schedule`` schedules it again, as design-space
exploration does.
"""

from __future__ import annotations

from typing import Optional

from repro.core.gallery import GALLERY
from repro.core.hls import erase_schedule, hls_schedule
from repro.core.hls.dse import DSEConfig, apply_structural_knobs


def build(config: dict, knobs: Optional[dict] = None):
    """``(module, entry)`` of the configuration's design under ``knobs``
    (default: the configuration's own ``schedule``, if it has one)."""
    module, entry = GALLERY[config["design"]].build(**config["build"])
    knobs = config.get("schedule") if knobs is None else knobs
    if knobs is None:
        return module, entry
    module = erase_schedule(module)
    variant = DSEConfig(**knobs)
    apply_structural_knobs(module, variant)
    hls_schedule(module, options=variant.scheduler_options())
    return module, entry
