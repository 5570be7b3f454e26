"""``transfer_ms_per_batch.memory``: read as ``transfer_ms_per_batch.bulk``
is, for the bulk cells whose scan is bound by per-lane memories, which
have an end-to-end metric of their own (PERF.md section 2)."""

from pathlib import Path

import harness

_SAME = Path(__file__).with_name("transfer_ms_per_batch.bulk.py")
read = harness.load_source(_SAME).read
