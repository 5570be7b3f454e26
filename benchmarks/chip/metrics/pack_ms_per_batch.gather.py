"""``pack_ms_per_batch.gather``: read as ``pack_ms_per_batch.bulk`` is, for
the bulk cell whose scan reads and writes a memory at data-dependent
addresses (per-lane gathers and scatters).  It reports ``verify_vcps``
beside the register-heavy cell, so its layers have metrics of their own
(PERF.md section 3)."""

from pathlib import Path

import harness

_SAME = Path(__file__).with_name("pack_ms_per_batch.bulk.py")
read = harness.load_source(_SAME).read
