"""``scan_ms_per_cycle.memory``: read as ``scan_ms_per_cycle.bulk`` is, for
the bulk cells whose scan is bound by per-lane memories.  Their runs spread far
less than the register-heavy cell's, so they have an end-to-end metric and
a bound of their own (PERF.md section 2)."""

from pathlib import Path

import harness

_SAME = Path(__file__).with_name("scan_ms_per_cycle.bulk.py")
read = harness.load_source(_SAME).read
