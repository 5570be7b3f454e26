"""Launch-layer integration: the dry-run machinery (specs, shardings,
lower+compile, loop-aware HLO analysis) exercised end-to-end on a small
8-device mesh with smoke configs — the 512-device production run uses the
identical code path (subprocess: device count locks at first jax init)."""

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(code: str) -> str:
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout[-3000:]}\nstderr:\n{out.stderr[-3000:]}"
    return out.stdout


MINI = """
import jax, json
import jax.numpy as jnp
from repro.configs.base import ShapeCfg
from repro.configs.registry import get_smoke_config
from repro.launch import hlo_analysis
from repro.launch.specs import cell_abstract_inputs
from repro.optim.adamw import OptCfg
from repro.parallel.api import use_rules
from repro.parallel.rules import rules_for
from repro.train.steps import make_serve_step, make_train_step

cfg = get_smoke_config({arch!r})
shape = ShapeCfg("mini", seq_len=16, global_batch=8, kind={kind!r})
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rules = rules_for(cfg, mesh, {mode!r}, batch=8)
with use_rules(rules, mesh):
    args, in_sh, out_sh = cell_abstract_inputs(cfg, shape, rules, mesh)
    step = (make_train_step(cfg, OptCfg(), mesh=mesh) if {kind!r} == "train"
            else make_serve_step(cfg))
    with mesh:
        lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()

st = hlo_analysis.analyze(hlo)
assert st.flops > 0, "dot FLOPs must be attributed"
assert st.mem_bytes > 0
# the layer scan must be trip-count-multiplied (no unknown whiles)
assert st.unknown_trip_whiles == 0, st.unknown_trip_whiles
terms = hlo_analysis.roofline_terms(st.flops, st.mem_bytes, st.coll_bytes)
assert terms["bottleneck"] in ("compute", "memory", "collective")
print("MINI_OK", json.dumps({{"flops": st.flops, "coll": st.coll_bytes}}))
"""


def test_mini_dryrun_train_dense():
    out = _run(MINI.format(arch="tinyllama-1.1b", kind="train", mode="train"))
    assert "MINI_OK" in out
    stats = json.loads(out.split("MINI_OK", 1)[1])
    assert stats["coll"] > 0  # FSDP/TP training must communicate


def test_mini_dryrun_train_moe():
    out = _run(MINI.format(arch="qwen2-moe-a2.7b", kind="train", mode="train"))
    assert "MINI_OK" in out


def test_mini_dryrun_decode():
    out = _run(MINI.format(arch="tinyllama-1.1b", kind="decode", mode="decode"))
    assert "MINI_OK" in out
