"""The checks that every cell of a benchmark spec gets at CPU test size.

Which end-to-end metrics a cell reports (the rule of ``harness.load_cell``),
what a sound run must read, the faults that are planted under a cell's
timed path, and what a faulted run or the control must read.  The tests of
the real cells and of a cell added as new files use the same checks.
"""

import numpy as np

from repro.core.codegen import sim as rsim


def end_to_end(spec: dict, workload: str) -> set[str]:
    """The end-to-end metrics a run of ``workload`` reports: those with no
    ``workloads`` list and those whose list names it."""
    return {m["name"] for m in spec["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}


def check_correct(r: dict, spec: dict, workload: str) -> None:
    """A sound run: correct, every number compared exact and last, every
    end-to-end metric of the cell above 0, set-up split into phases."""
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    want = end_to_end(spec, workload)
    assert "setup_s" in want and len(want) >= 2
    assert set(r["metrics"]) == want
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert all(c["limit"] == 0 for c in r["checks"].values())
    phases = r["setup_phases"]
    assert "init_s" in phases and "warmup_s" in phases
    assert (phases["init_s"] + phases["warmup_s"]
            <= r["metrics"]["setup_s"]["value"])


def check_not_correct(r: dict) -> None:
    """A run whose timed path gave wrong answers."""
    assert not r["correct"]
    assert r["failed"] > 0
    assert r["checks"]["mismatched_lanes"]["value"] > 0


def _state_unchanged(monkeypatch):
    orig = rsim.RTLSimulator.scan_program

    def scan_program(self, trace=False):
        scanner, names = orig(self, trace)

        def frozen(state, xs):
            _, ys = scanner(state, xs)
            return state, ys

        return frozen, names

    monkeypatch.setattr(rsim.RTLSimulator, "scan_program", scan_program)


def _half_batch(monkeypatch):
    orig = rsim.RTLSimulator.run

    def run(self, args, cycles, batched=False, **kw):
        lanes = np.asarray(args[0]).shape[0]
        half = (lanes + 1) // 2
        res = orig(self, [np.asarray(a)[:half] for a in args], cycles,
                   batched=batched, **kw)
        for k, a in res.arrays.items():
            res.arrays[k] = np.concatenate([a, a[:lanes - half]])
        return res

    monkeypatch.setattr(rsim.RTLSimulator, "run", run)


def _altered_answer(monkeypatch):
    orig = rsim.RTLSimulator._collect

    def collect(self, *a, **kw):
        res = orig(self, *a, **kw)
        out = res.arrays[max(res.arrays)]
        out.reshape(out.shape[0], -1)[-1, -1] ^= 1
        return res

    monkeypatch.setattr(rsim.RTLSimulator, "_collect", collect)


#: the faults a one-chip verification cell can have, each planted with
#: ``fault(monkeypatch)``: a scan that returns its state unchanged, half
#: of the batch simulated and the rest copied, one output word altered
FAULTS = [_state_unchanged, _half_batch, _altered_answer]
