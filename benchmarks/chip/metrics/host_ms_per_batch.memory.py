"""``host_ms_per_batch.memory``: read as ``host_ms_per_batch.bulk`` is, for
the bulk cells whose scan is bound by per-lane memories.  Their runs spread far
less than the register-heavy cell's, so they have an end-to-end metric and
a bound of their own (PERF.md section 2)."""

from pathlib import Path

import harness

_SAME = Path(__file__).with_name("host_ms_per_batch.bulk.py")
read = harness.load_source(_SAME).read
