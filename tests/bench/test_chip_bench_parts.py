"""The benchmark's yardstick, piece by piece: references against the
gallery's oracles, the stimulus generator, the DSE variant list, and the
reduction from a profiler trace to device numbers."""

import gzip
import json

import numpy as np
import pytest
from conftest import BENCH, ROOT

import harness
import stimulus
import xplane
from repro.core.gallery import GALLERY


SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _config(name):
    entry = {c["name"]: c for c in SPEC["configs"]}[name]
    return json.loads((ROOT / entry["file"]).read_text())


def _reference(config):
    return harness.load_source(BENCH / "reference"
                               / f"{config['reference']}.py")


@pytest.mark.parametrize("name,oracle_args", [
    (c["name"], sum("fill" not in arg for arg in _config(c["name"])["inputs"]))
    for c in SPEC["configs"]])
def test_reference_matches_gallery_oracle(name, oracle_args):
    """Every configuration's reference against its design's gallery
    oracle, which takes the configuration's drawn inputs."""
    config = _config(name)
    gal = GALLERY[config["design"]]
    args = stimulus.domain_args(
        config["inputs"], stimulus.batch(config["inputs"], 8, 12345, 0))
    assert len(args) == oracle_args
    got = _reference(config).reference(config, args)
    for lane in range(8):
        want = gal.oracle(*[a[lane] for a in args])
        np.testing.assert_array_equal(got[lane], want)


@pytest.mark.parametrize("name", ["gemm16", "conv2d128x64"])
def test_control_precision_changes_the_answer(name):
    """int16 differs from the datapath on (nearly) every lane."""
    config = _config(name)
    args = stimulus.domain_args(config["inputs"], stimulus.batch(
        config["inputs"], 64, 9, 0))
    ref = _reference(config)
    exact = ref.reference(config, args)
    low = ref.reference(config, args, dtype=np.int16)
    differs = (exact.reshape(64, -1) != low.reshape(64, -1)).any(axis=1)
    assert differs.mean() > 0.9


def test_reference_wraps_to_the_datapath():
    config = dict(_config("gemm16"), datapath_bits=32)
    a = np.full((1, 16, 16), 2**16, dtype=np.int64)
    got = _reference(config).reference(config, [a, a])
    assert (got == 0).all()  # 16 * 2**32 wraps to 0 in 32 bits


def test_stimulus_is_seeded_and_in_domain():
    config = _config("gemm16")
    a = stimulus.batch(config["inputs"], 32, 2**31 + 11, 3)
    b = stimulus.batch(config["inputs"], 32, 2**31 + 11, 3)
    c = stimulus.batch(config["inputs"], 32, 2**31 + 11, 4)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    assert a[0].min() >= -1024 and a[0].max() < 1024
    assert not a[2].any()
    assert [x.shape for x in a] == [(32, 16, 16)] * 3


def test_variant_list_is_distinct_and_schedules():
    from repro.core.hls import erase_schedule, hls_schedule
    from repro.core.hls.dse import (DSEConfig, apply_structural_knobs,
                                    fingerprint_module)

    traffic = json.loads((BENCH / "traffic" / "conv2d16x64_variants.json")
                         .read_text())
    config = _config("conv2d16x64")
    module, _ = GALLERY[config["design"]].build(**config["build"])
    fps = []
    for knobs in (traffic["warmup"] + traffic["trace_variants"]
                  + traffic["variants"]):
        m = erase_schedule(module.clone())
        v = DSEConfig(**knobs)
        assert v.partition == 0
        assert not (v.tile == 2 and v.interchange)  # fails to verify
        apply_structural_knobs(m, v)
        hls_schedule(m, options=v.scheduler_options())
        fps.append(fingerprint_module(m))
    assert len(set(fps)) == len(fps) >= 31
    assert len(traffic["trace_variants"]) == traffic["trace_units"]


@pytest.mark.parametrize("name,same", [
    ("verify_vcps.memory", "verify_vcps"),
    ("scan_ms_per_cycle.memory", "scan_ms_per_cycle.bulk"),
    ("host_ms_per_batch.memory", "host_ms_per_batch.bulk"),
    ("device_idle.memory", "device_idle.bulk")])
def test_memory_cells_read_as_the_bulk_metrics(name, same):
    units = [{"lanes": 512, "cycles": 100, "run_s": 0.5}] * 3
    run = harness.Run("bulk", 1.0, 2.0, units,
                      {"busy_s": 0.75, "window_s": 1.0})
    read = [harness.load_source(BENCH / "metrics" / f"{n}.py").read(run)
            for n in (name, same)]
    assert read[0] == read[1] is not None


def _trace(device_events, spans, window=(0, 1000)):
    host = [["bench.window", window[0], window[1] - window[0]]]
    host += [[n, s, e - s] for n, s, e in spans]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": device_events}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}


def test_reduce_on_a_hand_made_trace():
    ops = [["fusion.1", 100, 200],     # 100-300
           ["fusion.2", 300, 100],     # 300-400, right after it
           ["copy", 600, 100],         # 600-700
           ["fusion.1", 950, 100]]     # 950-1050, cut at the window's end
    spans = [("bench.stimulus", 0, 100),
             ("sim.run", 100, 900),
             ("run_differential", 50, 1000)]
    r = xplane.reduce(_trace(ops, spans), harness.HOST_SPANS)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(1000 * ns)
    assert r["busy_s"] == pytest.approx((300 + 100 + 50) * ns)
    assert dict((n, v) for n, v in r["device_ops"]) == pytest.approx(
        {"fusion.1": 250 * ns, "fusion.2": 100 * ns, "copy": 100 * ns})
    # idle: 0-100 stimulus (innermost), 400-600 and 700-900 sim.run,
    # 900-950 run_differential
    assert dict((n, v) for n, v in r["idle_gaps"]) == pytest.approx(
        {"bench.stimulus": 100 * ns, "sim.run": 400 * ns,
         "run_differential": 50 * ns})


def test_reduce_on_a_recorded_chip_trace():
    """A trace recorded on one TPU v5e (gemm n=4, one batch of 1024 lanes
    under the profiler), kept as ``load_xplane`` gives it."""
    with gzip.open(BENCH / "testdata" / "gemm4_v5e_trace.json.gz") as f:
        trace = json.load(f)
    r = xplane.reduce(trace, harness.HOST_SPANS)
    # the run that recorded it reported these (busy is a union of 10,867
    # op intervals, the window one span)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.062069997, abs=1e-9)
    assert r["window_s"] == pytest.approx(0.127800478, abs=1e-9)
    assert len(r["device_ops"]) == 10
    assert all(n.startswith("fusion.") for n, _ in r["device_ops"])
    total_self = sum(
        v for v in dict((n, v) for n, v in r["device_ops"]).values())
    assert total_self < r["busy_s"]
    idle = dict((n, v) for n, v in r["idle_gaps"])
    assert set(idle) <= set(harness.HOST_SPANS) | {xplane.NO_SPAN}
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    assert max(idle, key=idle.get) == "sim.run"


def test_self_time_of_nested_ops():
    ops = [["while.1", 0, 1000], ["fusion.1", 100, 300],
           ["fusion.2", 500, 100], ["copy.1", 1000, 50]]
    r = xplane.reduce(_trace(ops, [], window=(0, 2000)), harness.HOST_SPANS)
    assert dict((n, v) for n, v in r["device_ops"]) == pytest.approx(
        {"while.1": 600e-9, "fusion.1": 300e-9, "fusion.2": 100e-9,
         "copy.1": 50e-9})
    assert r["busy_s"] == pytest.approx(1050e-9)
    assert xplane.op_name("%fusion.12 = (u32[4]) fusion(u32[4] %p)") == \
        "fusion.12"


def test_load_xplane_keeps_spans_and_device_lines(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("sim.run"):
                jax.jit(lambda x: x * 2)(jnp.arange(8)).block_until_ready()
            with jax.profiler.TraceAnnotation("not.kept"):
                pass
    finally:
        jax.profiler.stop_trace()
    t = xplane.load_xplane(tmp_path, ["sim.run"])
    names = {e[0] for p in t["planes"] for line in p["lines"]
             for e in line["events"]}
    assert {xplane.WINDOW_SPAN, "sim.run"} <= names
    assert "not.kept" not in names
