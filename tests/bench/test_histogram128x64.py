"""The ``histogram128x64`` configuration, its reference and control, and
its cell's metrics.

The reference is the gallery histogram's oracle, lane by lane.  The control
is the answer of the realistic fault, the bin read-modify-write scheduled
at II=1: it equals what the batched simulator computes for the gallery's
histogram rebuilt with an II=1 main loop, and it differs from the reference
exactly on the lanes in which two consecutive pixels are equal.
"""

import json

import numpy as np
import pytest
from conftest import BENCH, ROOT

import harness
from repro.core import ir
from repro.core.builder import Builder
from repro.core.codegen import sim as rsim
from repro.core.gallery import histogram

CONFIG = json.loads((BENCH / "configs" / "histogram128x64.json").read_text())
REF = harness.load_source(BENCH / "reference" / "histogram.py")
N, BINS = CONFIG["build"]["n"], CONFIG["build"]["bins"]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "histogram128x64.bulk"
#: the prefixes of the cell's ``.gather`` metrics, each read as ``.bulk``
GATHER = ["scan_ms_per_cycle", "transfer_ms_per_batch", "pack_ms_per_batch",
          "device_idle", "host_ms_per_batch"]


def _tiles(lanes: int = 12) -> np.ndarray:
    """Random 8-bit tiles, and tiles with no two equal consecutive pixels
    (a ramp, a reversed ramp and an alternation of two values) but for one
    ramp whose last pixel repeats the one before it."""
    img = np.random.default_rng(7).integers(0, BINS, size=(lanes, N))
    ramp = np.arange(N) % BINS
    img[0], img[1], img[2] = ramp, ramp[::-1], np.arange(N) % 2 * 255
    img[3] = ramp
    img[3, -1] = img[3, -2]
    return img


def _histogram_ii1(n: int, bins: int):
    """The gallery's histogram with its main loop at II=1: a pixel reads
    its bin in the cycle in which the pixel before it writes back."""
    b = Builder(ir.Module("histogram_ii1"))
    rmem = ir.MemrefType((n,), ir.i32, ir.PORT_R)
    wmem = ir.MemrefType((bins,), ir.i32, ir.PORT_W)
    with b.func("histogram_ii1", [rmem, wmem], ["Img", "Out"]) as f:
        Img, Out = f.args
        Hr, Hw = b.alloc(ir.MemrefType((bins,), ir.i32, kind=ir.KIND_BRAM),
                         names=["Hr", "Hw"])
        with b.for_(0, bins, 1, at=f.t + 1) as lc:
            b.yield_(at=lc.time + 1)
            b.write(0, Hw, [lc.iv], at=lc.time)
        with b.for_(0, n, 1, at=lc.end + 1) as li:
            b.yield_(at=li.time + 1)
            v = b.read(Img, [li.iv], at=li.time)
            h1 = b.add(b.read(Hr, [v], at=li.time + 1), 1)
            b.write(h1, Hw, [b.delay(v, 1, at=li.time + 1)],
                    at=li.time + 2)
        with b.for_(0, bins, 1, at=li.end + 2) as ld:
            b.yield_(at=ld.time + 1)
            hv = b.read(Hr, [ld.iv], at=ld.time)
            b.write(hv, Out, [b.delay(ld.iv, 1, at=ld.time)],
                    at=ld.time + 1)
        b.ret()
    return b.module, "histogram_ii1"


def test_configuration_is_the_published_size():
    assert (N, BINS, CONFIG["datapath_bits"]) == (8192, 256, 32)
    assert CONFIG["reduced"] == [] and CONFIG["assumed"]
    entry = {c["name"]: c for c in SPEC["configs"]}["histogram128x64"]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]


def test_cell_reports_verify_vcps():
    """The cell shares ``verify_vcps`` with gemm16.bulk: its runs take host
    stalls that spread a set of 6 by more than half of
    ``verify_vcps.memory``'s bound.  Its ``.gather`` per-layer metrics move
    it."""
    reported = {m["name"] for m in SPEC["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {"verify_vcps", "setup_s"}
    gather = {m["name"]: m for m in SPEC["per_layer"]
              if m["name"].endswith(".gather")}
    assert set(gather) == {f"{p}.gather" for p in GATHER}
    for m in gather.values():
        assert m["moves"] == "verify_vcps" and m["workloads"] == [CELL]


@pytest.mark.parametrize("prefix", ["scan_ms_per_cycle", "device_idle",
                                    "host_ms_per_batch"])
def test_gather_metrics_read_as_the_bulk_metrics(prefix):
    """The readers that take their numbers from the run alone; the span
    readers are run in the cell by ``test_program_spans.py``."""
    units = [{"lanes": 512, "cycles": 16916, "run_s": 1.6}] * 3
    run = harness.Run("bulk", 1.0, 6.1, units,
                      {"busy_s": 0.78, "window_s": 1.0})
    read = [harness.load_source(BENCH / "metrics" / f"{n}.py").read(run)
            for n in (f"{prefix}.gather", f"{prefix}.bulk")]
    assert read[0] == read[1] is not None


def test_control_differs_exactly_where_two_consecutive_pixels_agree():
    img = _tiles()
    exact = REF.reference(CONFIG, [img])
    assert exact.shape == (len(img), BINS) and exact.dtype == np.int64
    for lane, tile in enumerate(img):
        np.testing.assert_array_equal(exact[lane],
                                      histogram.oracle(tile, bins=BINS))
    wrong = REF.control(CONFIG, [img])
    repeats = (img[:, 1:] == img[:, :-1]).any(axis=1)
    assert list(repeats[:4]) == [False, False, False, True]
    assert repeats[4:].all()
    np.testing.assert_array_equal((wrong != exact).any(axis=1), repeats)
    assert (wrong <= exact).all()
    # uniform 8-bit pixels: about 8191 / 256 = 32 repeated pairs per lane
    lost = (exact - wrong).sum(axis=1)[4:]
    assert 10 < lost.mean() < 60


def test_control_is_the_answer_of_an_ii1_schedule():
    """Runs of two, three and more equal pixels: the simulator of the II=1
    design gives what ``control`` gives, and not the reference."""
    n, bins, lanes = 64, 4, 32
    img = np.random.default_rng(11).integers(0, bins, size=(lanes, n))
    img[0] = 0
    img[1, :7] = 3
    config = {"build": {"n": n, "bins": bins}}
    module, entry = _histogram_ii1(n, bins)
    sim, prepared = rsim.simulator_for(module, entry)
    out = np.zeros((lanes, bins), dtype=np.int64)
    cycles = rsim.probe_cycles(prepared, entry, [img[0], out[0]])
    got = sim.run([img, out], cycles, batched=True).arrays[1]
    np.testing.assert_array_equal(got, REF.control(config, [img]))
    assert (got != REF.reference(config, [img])).any(axis=1).all()
    assert list(got[0]) == [n // 2, 0, 0, 0]
