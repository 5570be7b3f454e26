"""Vectorized cycle-accurate RTL simulation.

Compiles a structured RTL design (``rtl.RTLModule``/``rtl.RTLDesign``) into a
pure array-program step function and runs whole stimulus *batches* through it:

  * the design is flattened (``RTLDesign.flatten``) and its external memref
    interface ports are *closed* — replaced by internal storage models that
    reproduce the interface timing exactly (register banks respond
    combinationally, RAM ports one cycle later);
  * combinational items are topologically sorted and compiled to a linear
    tape of ``int64`` array operations with explicit width masking;
  * ``ShiftReg``/``RegAssign``/``Memory``/``LoopController`` state is
    threaded through the step function with nonblocking (read-old,
    write-new) semantics;
  * on the JAX backend (the default) the step is ``jax.lax.scan``-ed over
    cycles under a scoped ``jax.enable_x64`` (the global x64 flag is never
    touched) and runs on JAX's default device.  A static analysis splits
    the nets into lane-uniform and lane-varying (``RTLSimulator.varying``:
    whatever reads a scalar input, a register-bank cell or memory read
    data, transitively).  Each cycle the uniform part — ``t_start``, the
    controllers of fixed loops, counters, delayed control — is evaluated
    once on unbatched scalars, and only the lane-varying part is
    ``jax.vmap``-ed over the stimulus batch axis.  A memory port whose
    address is uniform (a *row port*) reads or writes one row of lanes of
    the memory, held lanes-minor inside the scan, as a dynamic slice in
    place; a port with a data-dependent address keeps a per-lane gather
    and scatter.  The NumPy backend runs the whole tape batch-first on
    the host with a Python cycle loop — still vectorized over stimulus —
    and serves as the host-side cross-check in ``verify_rtl_passes``.

Semantics follow the event-driven oracle (``lower.to_sim``): values are bit
patterns masked to their net width, ``Signed`` sign-extends, division is
floor division (``//``), right shift is arithmetic on signed operands, and
division by zero yields 0 (the event simulator would fault; random stimulus
must not rely on it).  Widths above 63 bits are rejected.

On top of the simulator, ``run_differential`` is the verification harness:
it checks the vectorized simulator against the event-driven oracle lane by
lane, and ``verify_rtl_passes`` checks every RTL pass in
``RTL_PIPELINE_SPEC`` by comparing per-cycle result-port traces and final
memory/return state of each pass's input design against its output design.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import ir
from ..ir import FuncOp, IntType, MemrefType, Module
from ..passmgr import PassManager
from ..trace import count, span
from . import rtl
from .rtl import (REG, WIRE, Binop, CombAssign, Const, Expr, Instance,
                  LoopController, MemRead, Memory, MemWrite, Mux, Net,
                  PortConflictAssert, Ref, Repeat, RegAssign, RTLDesign,
                  RTLModule, ShiftReg, Signed, Unop)

I64 = np.int64


class RTLSimError(Exception):
    pass


def _clog2(n: int) -> int:
    return max(1, (n - 1).bit_length())


def _mask_of(w: int) -> int:
    """Python-int AND mask for a ``w``-bit pattern."""
    if w >= 64:
        raise RTLSimError(f"width {w} exceeds the 63-bit simulation domain")
    return (1 << w) - 1


def _signed_fix(p: np.ndarray, w: int, signed: bool) -> np.ndarray:
    """Pattern -> math value (sign-extend when the element type is signed)."""
    p = np.asarray(p, dtype=I64)
    if not signed or w >= 64:
        return p
    s = I64(1) << I64(w - 1)
    return ((p & ((I64(1) << I64(w)) - I64(1))) ^ s) - s


# ---------------------------------------------------------------------------
# Array-op backends.  The compiled tape is backend-agnostic: every closure
# takes (env, ops).  _JaxOps values are scalars: lane-uniform ones unbatched,
# lane-varying ones per lane (vmap adds the batch axis); _NumpyOps values are
# batch-first (B,) arrays.
# ---------------------------------------------------------------------------


class _JaxOps:
    def __init__(self):
        self.zero = jnp.int64(0)
        self.one = jnp.int64(1)

    def where(self, c, a, b):
        return jnp.where(c, a, b)

    def minimum(self, a, b):
        return jnp.minimum(a, b)

    def b2i(self, c):
        return jnp.where(c, self.one, self.zero)

    def sr_out(self, chain):
        return chain[-1]

    def sr_push(self, chain, v):
        head = jnp.asarray(v, dtype=jnp.int64).reshape(1)
        return jnp.concatenate([head, chain[:-1]])

    def read_mem(self, mem, addr):
        a = jnp.clip(jnp.asarray(addr, dtype=jnp.int64), 0, mem.shape[0] - 1)
        return mem[a]

    def write_mem(self, mem, addr, data, enb):
        a = jnp.clip(jnp.asarray(addr, dtype=jnp.int64), 0, mem.shape[0] - 1)
        return mem.at[a].set(jnp.where(enb, data, mem[a]))

    # Row ports: the address is lane-uniform (unbatched under the vmap), so
    # the batching rules turn these into one dynamic slice / in-place update
    # of a whole row of lanes instead of a per-lane gather / scatter.
    @staticmethod
    def _row(mem, addr):
        a = jnp.clip(jnp.asarray(addr, dtype=jnp.int64), 0, mem.shape[0] - 1)
        return a.astype(jnp.int32)

    def read_row(self, mem, addr):
        return jax.lax.dynamic_index_in_dim(mem, self._row(mem, addr), 0,
                                            keepdims=False)

    def write_row(self, mem, addr, data, enb):
        a = self._row(mem, addr)
        old = jax.lax.dynamic_index_in_dim(mem, a, 0, keepdims=False)
        new = jnp.asarray(jnp.where(enb, data, old), dtype=mem.dtype)
        return jax.lax.dynamic_update_index_in_dim(mem, new, a, 0)


class _NumpyOps:
    def __init__(self, batch: int):
        self.B = int(batch)
        self.zero = I64(0)
        self.one = I64(1)

    def where(self, c, a, b):
        return np.where(c, a, b)

    def minimum(self, a, b):
        return np.minimum(a, b)

    def b2i(self, c):
        return np.where(c, self.one, self.zero)

    def _bcast(self, v):
        return np.broadcast_to(np.asarray(v, dtype=I64), (self.B,))

    def sr_out(self, chain):
        return chain[:, -1]

    def sr_push(self, chain, v):
        return np.concatenate([self._bcast(v)[:, None], chain[:, :-1]],
                              axis=1)

    def read_mem(self, mem, addr):
        a = np.clip(self._bcast(addr), 0, mem.shape[1] - 1)
        return np.take_along_axis(mem, a[:, None], axis=1)[:, 0]

    def write_mem(self, mem, addr, data, enb):
        a = np.clip(self._bcast(addr), 0, mem.shape[1] - 1)
        cur = np.take_along_axis(mem, a[:, None], axis=1)[:, 0]
        d = self._bcast(np.where(enb, data, cur))
        out = mem.copy()
        np.put_along_axis(out, a[:, None], d[:, None], axis=1)
        return out


# ---------------------------------------------------------------------------
# Expression compiler: Expr -> closure(env, ops) returning the *math* value
# (exact modulo 2**64; patterns are materialized by masking at assignment).
# Static widths mirror backends.NetlistPrinter.expr_width.
# ---------------------------------------------------------------------------

_CMP_FNS = {
    "<": (lambda a, b: a < b), "<=": (lambda a, b: a <= b),
    ">": (lambda a, b: a > b), ">=": (lambda a, b: a >= b),
    "==": (lambda a, b: a == b), "!=": (lambda a, b: a != b),
}
_ARITH_FNS = {
    "+": (lambda a, b: a + b), "-": (lambda a, b: a - b),
    "*": (lambda a, b: a * b), "&": (lambda a, b: a & b),
    "|": (lambda a, b: a | b), "^": (lambda a, b: a ^ b),
}


def _compile_expr(e: Expr, widths: dict[str, int]):
    """Return ``(fn, width)``; ``fn(env, ops)`` evaluates the math value."""
    if isinstance(e, Const):
        if not isinstance(e.value, int):
            raise RTLSimError(f"non-integer constant {e.value!r} unsupported")
        v = int(e.value)
        w = e.width if e.width is not None else max(1, v.bit_length())
        return (lambda env, ops: v), w
    if isinstance(e, Ref):
        nm = e.name
        if nm not in widths:
            raise RTLSimError(f"reference to undeclared net {nm!r}")
        return (lambda env, ops: env[nm]), widths[nm]
    if isinstance(e, Signed):
        fa, w = _compile_expr(e.a, widths)
        m, s = _mask_of(w), 1 << (w - 1)
        return (lambda env, ops: ((fa(env, ops) & m) ^ s) - s), w
    if isinstance(e, Unop):
        if e.op != "~":
            raise RTLSimError(f"unop {e.op!r} unsupported")
        fa, w = _compile_expr(e.a, widths)
        return (lambda env, ops: ~fa(env, ops)), w
    if isinstance(e, Mux):
        fc, _ = _compile_expr(e.cond, widths)
        fa, wa = _compile_expr(e.a, widths)
        fb, wb = _compile_expr(e.b, widths)
        return (lambda env, ops: ops.where(
            fc(env, ops) != 0, fa(env, ops), fb(env, ops))), max(wa, wb)
    if isinstance(e, Repeat):
        fa, wa = _compile_expr(e.a, widths)
        w = e.n * wa
        if w >= 64:
            if isinstance(e.a, Const) and int(e.a.value) == 0:
                return (lambda env, ops: 0), 63
            raise RTLSimError(f"repeat to {w} bits unsupported")
        m = _mask_of(wa)
        factor = sum(1 << (i * wa) for i in range(e.n))
        return (lambda env, ops: (fa(env, ops) & m) * factor), w
    if isinstance(e, Binop):
        fa, wa = _compile_expr(e.a, widths)
        fb, wb = _compile_expr(e.b, widths)
        op = e.op
        if op in _CMP_FNS:
            cf = _CMP_FNS[op]
            return (lambda env, ops: ops.b2i(cf(fa(env, ops),
                                               fb(env, ops)))), 1
        if op == "&&":
            return (lambda env, ops: ops.b2i(
                (fa(env, ops) != 0) & (fb(env, ops) != 0))), 1
        if op == "||":
            return (lambda env, ops: ops.b2i(
                (fa(env, ops) != 0) | (fb(env, ops) != 0))), 1
        w = max(wa, wb)
        if op in _ARITH_FNS:
            af = _ARITH_FNS[op]
            return (lambda env, ops: af(fa(env, ops), fb(env, ops))), w
        if op == "/":
            # floor division, matching the event-driven oracle's `//`;
            # division by zero yields 0 instead of faulting per lane.
            def fdiv(env, ops):
                a, b = fa(env, ops), fb(env, ops)
                z = (b == 0)
                return ops.where(z, 0, a // ops.where(z, 1, b))
            return fdiv, w
        if op == "<<":
            if isinstance(e.b, Const):
                k = int(e.b.value)
                if k >= 64:
                    return (lambda env, ops: 0), w
                return (lambda env, ops: fa(env, ops) << k), w

            def fshl(env, ops):
                a, b = fa(env, ops), fb(env, ops)
                return ops.where(b >= 63, 0, a << ops.minimum(b, 62))
            return fshl, w
        if op == ">>":
            if isinstance(e.b, Const):
                k = min(int(e.b.value), 63)
                return (lambda env, ops: fa(env, ops) >> k), w

            def fshr(env, ops):
                a, b = fa(env, ops), fb(env, ops)
                return a >> ops.minimum(b, 63)
            return fshr, w
        raise RTLSimError(f"binop {op!r} unsupported")
    raise RTLSimError(f"expression {type(e).__name__} unsupported")


# ---------------------------------------------------------------------------
# Closing the external interface: memref argument ports become internal
# storage with the exact interface timing of verilog.FuncLowering.
# ---------------------------------------------------------------------------


@dataclass
class _Bind:
    index: int
    kind: str                      # "scalar" | "bank" | "ram"
    port: str = ""                 # scalar input port
    width: int = 0
    signed: bool = False
    mt: Optional[MemrefType] = None
    cells: list = field(default_factory=list)  # bank: [[net per elem]/bank]
    memkey: str = ""               # ram: state key of the backing array


def close_module(flat: RTLModule, func: FuncOp
                 ) -> tuple[list[_Bind], list[str]]:
    """Convert ``flat``'s memref interface ports into internal storage items
    (mutating ``flat``), returning ``(bindings, traced)``: the argument
    bindings the runner uses to load stimulus and read back final state, and
    the demoted interface-port nets (the design's observable boundary — what
    per-cycle differential checks compare).  Register-bank arguments become
    per-cell registers with combinational (same-cycle) read response and
    address-decoded clocked writes; packed arguments become a ``Memory`` with
    the interface's one-cycle read latency."""
    binds: list[_Bind] = []
    traced: list[str] = []
    port_by = {p.name: p for p in flat.ports}
    for i, a in enumerate(func.args):
        ports = flat.arg_ports.get(i, [])
        if not isinstance(a.type, MemrefType):
            if not ports:
                raise RTLSimError(f"argument {i} has no interface ports")
            pname = ports[0][0]
            w = port_by[pname].width
            signed = isinstance(a.type, IntType) and a.type.signed
            binds.append(_Bind(i, "scalar", port=pname, width=w,
                               signed=signed))
            continue
        mt = a.type
        dw = mt.elem_bits()
        roles: dict[tuple[str, int], str] = {}
        for pname, _pdir, role, bank in ports:
            roles[(role, bank)] = pname
            p = port_by.pop(pname, None)
            if p is not None:
                flat.ports.remove(p)
                traced.append(pname)
                kind = REG if (role == "rd_data" and bank == -1) else WIRE
                if pname not in flat.nets:
                    flat.nets[pname] = Net(pname, p.width, kind, False,
                                           f"extif:{i}", "")
        signed = isinstance(mt.elem, IntType) and mt.elem.signed
        if mt.distributed:
            aw = _clog2(mt.bank_elems)
            cells: list[list[str]] = []
            for bk in range(mt.num_banks):
                row = []
                for d in range(mt.bank_elems):
                    cn = f"__ext{i}_b{bk}_{d}"
                    flat.nets[cn] = Net(cn, dw, REG, False, "extbank", "")
                    row.append(cn)
                cells.append(row)
                rd = roles.get(("rd_data", bk))
                if rd is not None:
                    ra = roles.get(("rd_addr", bk))
                    ex: Expr = Ref(row[0])
                    if ra is not None and mt.bank_elems > 1:
                        for d in range(1, mt.bank_elems):
                            ex = Mux(Binop("==", Ref(ra), Const(d, aw),
                                           free=True), Ref(row[d]), ex, dw)
                    flat.items.append(CombAssign(rd, ex))
                we = roles.get(("wr_en", bk))
                if we is not None:
                    wa = roles.get(("wr_addr", bk))
                    wd = roles[("wr_data", bk)]
                    for d in range(mt.bank_elems):
                        en: Expr = Ref(we)
                        if wa is not None:
                            en = Binop("&&", Ref(we),
                                       Binop("==", Ref(wa), Const(d, aw),
                                             free=True), free=True)
                        flat.items.append(RegAssign(row[d], Ref(wd), en))
            binds.append(_Bind(i, "bank", width=dw, signed=signed, mt=mt,
                               cells=cells))
        else:
            memname = f"__ext{i}"
            flat.items.append(Memory(memname, 1, mt.bank_elems, dw, "bram"))
            rd = roles.get(("rd_data", -1))
            if rd is not None:
                flat.items.append(MemRead(
                    rd, memname, 0, Ref(roles[("rd_addr", -1)]),
                    Ref(roles[("rd_en", -1)])))
            we = roles.get(("wr_en", -1))
            if we is not None:
                flat.items.append(MemWrite(
                    memname, 0, Ref(roles[("wr_addr", -1)]),
                    Ref(roles[("wr_data", -1)]), Ref(we)))
            binds.append(_Bind(i, "ram", width=dw, signed=signed, mt=mt,
                               memkey=f"mem:{memname}:0"))
    return binds, traced


# ---------------------------------------------------------------------------
# The compiled step program and the batched runner
# ---------------------------------------------------------------------------


@dataclass
class SimResult:
    """Outcome of one batched run.  All arrays are batch-first numpy int64.

    ``returns[j]``/``returns_valid[j]`` are the captured ``result_j`` values
    (sign-corrected per the function's result types) and whether the valid
    pulse fired; ``arrays[i]`` is the final content of memref argument ``i``
    in its original tensor shape; ``conflicts`` counts §4.5 port-conflict
    cycles per lane; ``trace[p]`` is the per-cycle (T, B) pattern of output
    port ``p`` when tracing was requested.  ``platform`` names where the
    run executed (the JAX platform of its arrays, ``"host"`` for the NumPy
    backend); ``compile_s`` is what this run paid to build the step
    function, trace and lower the scan and compile it (the ``hir.sim.lower``
    and ``hir.sim.compile`` spans; 0.0 when the executable was already
    compiled) and ``run_s`` the simulation time alone, taken after
    ``block_until_ready`` (the ``hir.sim.scan`` span)."""

    backend: str
    cycles: int
    batch: int
    returns: list[np.ndarray]
    returns_valid: list[np.ndarray]
    arrays: dict[int, np.ndarray]
    conflicts: np.ndarray
    conflict_buses: list[str]
    trace: Optional[dict[str, np.ndarray]] = None
    platform: str = "host"
    compile_s: float = 0.0
    run_s: float = 0.0


class RTLSimulator:
    """Batched cycle-accurate interpreter for one RTL design entry.

    ``design`` is the (possibly hierarchical) RTL design; ``func`` the
    originating ``hir.func`` (argument/result types and memory layout).
    ``backend`` is ``"jax"`` or ``"numpy"``, the host-side twin.
    """

    @span("hir.sim.build")
    def __init__(self, design: RTLDesign, func: FuncOp,
                 entry: Optional[str] = None, backend: str = "jax"):
        entry = entry or design.entry
        assert entry is not None, "entry module required"
        self.entry = entry
        self.func = func
        flat = design.flatten(entry)
        self.binds, self._ext_traced = close_module(flat, func)
        self.flat = flat
        if backend not in ("jax", "numpy"):
            raise RTLSimError(f"unknown simulator backend {backend!r}")
        self.backend = backend
        # compiled scan executables keyed by (trace, batch, cycles)
        self._compiled: dict[tuple, tuple[Any, list[str]]] = {}
        self._build()

    # -- compilation ---------------------------------------------------------
    def _build(self) -> None:
        m = self.flat
        rtl._ensure_recursion_headroom()
        widths = {n: v.width for n, v in m.nets.items()}
        for p in m.ports:
            widths.setdefault(p.name, p.width)
        self.widths = widths

        mems: dict[str, Memory] = {}
        driven: set[str] = set()
        for it in m.items:
            if isinstance(it, Instance):
                raise RTLSimError("flatten left an Instance behind")
            if isinstance(it, Memory):
                mems[it.name] = it
            driven.update(it.writes())
        inputs = {p.name for p in m.ports if p.dir == "input"}
        driven |= inputs | {"clk", "rst"}

        # undriven wires read somewhere float to 0 (Verilog would read X;
        # the lowering never relies on such reads — this keeps the tape total)
        tied: list[tuple[str, int]] = []
        for it in m.items:
            for r in it.reads():
                if r not in driven:
                    driven.add(r)
                    tied.append((r, widths.get(r, 1)))

        self.state_nets: list[str] = []                 # REG nets, per lane
        self.sr_loads: list[tuple[str, str]] = []       # (dest, state key)
        self.scalar_inputs = [(b.port, f"in:{b.port}") for b in self.binds
                              if b.kind == "scalar"]
        state_shape: dict[str, tuple] = {k: () for _, k in self.scalar_inputs}
        seen_state: set[str] = set()

        def mark_state(net: str) -> None:
            if net and net not in seen_state:
                seen_state.add(net)
                self.state_nets.append(net)
                state_shape[net] = ()

        for nm, mem in mems.items():
            for bk in range(mem.banks):
                state_shape[f"mem:{nm}:{bk}"] = (mem.depth,)

        # comb node: (dest, kind, payload, reads) — kind "assign" payload is
        # (fn, mask); kind "ctrl" payload is the controller spec (iter pulse)
        comb_nodes: list[tuple] = []
        clocked: list[tuple] = []
        asserts: list[tuple[str, list]] = []

        for nm, w in tied:
            comb_nodes.append((nm, "assign", ((lambda env, ops: 0),
                                              _mask_of(w)), ()))

        self.varying = varying = self._lane_varying()
        # (tag, memory state key, row path?) per memory port
        self.mem_ports: list[tuple[str, str, bool]] = []

        def row_port(tag: str, memkey: str, addr: Expr) -> bool:
            row = not any(r in varying for r in addr.refs())
            self.mem_ports.append((tag, memkey, row))
            return row

        for it in m.items:
            if isinstance(it, CombAssign):
                fn, _ = _compile_expr(it.expr, widths)
                w = widths.get(it.dest)
                if w is None:
                    raise RTLSimError(f"assign to undeclared {it.dest!r}")
                comb_nodes.append((it.dest, "assign", (fn, _mask_of(w)),
                                   tuple(it.reads())))
            elif isinstance(it, ShiftReg):
                key = f"sr:{it.dest}"
                state_shape[key] = (it.depth,)
                self.sr_loads.append((it.dest, key))
                fn, _ = _compile_expr(it.src, widths)
                clocked.append(("sr", key, fn, _mask_of(it.width)))
            elif isinstance(it, RegAssign):
                mark_state(it.dest)
                fn, _ = _compile_expr(it.src, widths)
                en = (None if it.en is None
                      else _compile_expr(it.en, widths)[0])
                clocked.append(("reg", it.dest, fn, en,
                                _mask_of(widths[it.dest])))
            elif isinstance(it, MemRead):
                mark_state(it.dest)
                memkey = f"mem:{it.mem}:{it.bank}"
                afn, _ = _compile_expr(it.addr, widths)
                efn, _ = _compile_expr(it.en, widths)
                clocked.append(("memrd", it.dest, memkey, afn, efn,
                                _mask_of(widths[it.dest]),
                                row_port("memrd", memkey, it.addr)))
            elif isinstance(it, MemWrite):
                memkey = f"mem:{it.mem}:{it.bank}"
                afn, _ = _compile_expr(it.addr, widths)
                dfn, _ = _compile_expr(it.data, widths)
                efn, _ = _compile_expr(it.en, widths)
                clocked.append(("memwr", memkey, afn, dfn, efn,
                                _mask_of(mems[it.mem].width),
                                row_port("memwr", memkey, it.addr)))
            elif isinstance(it, LoopController):
                mark_state(it.iv)
                mark_state(it.active)
                if it.endp:
                    mark_state(it.endp)
                if it.iicnt:
                    mark_state(it.iicnt)
                spec = {
                    "iv": it.iv, "active": it.active, "endp": it.endp,
                    "iicnt": it.iicnt, "ii": it.ii,
                    "ivmask": _mask_of(it.ivw),
                    "start": _compile_expr(it.start, widths)[0],
                    "lb": _compile_expr(it.lb, widths)[0],
                    "ub": _compile_expr(it.ub, widths)[0],
                    "step": _compile_expr(it.step, widths)[0],
                    "inner": (None if it.inner_end is None
                              else _compile_expr(it.inner_end, widths)[0]),
                }
                clocked.append(("ctrl", spec))
                deps = tuple(r for e in it.exprs() for r in e.refs())
                comb_nodes.append((it.iter_net, "ctrl", spec, deps))
            elif isinstance(it, Memory):
                pass
            elif isinstance(it, PortConflictAssert):
                ens = [_compile_expr(e, widths)[0] for e in it.ens]
                asserts.append((it.bus, ens))
            else:
                raise RTLSimError(f"item {type(it).__name__} unsupported")

        self.clocked = clocked
        self.asserts = asserts
        self.conflict_buses = [bus for bus, _ in asserts]
        if asserts:
            state_shape["cf"] = (len(asserts),)
        self.results = list(m.result_ports)
        for j in range(len(self.results)):
            state_shape[f"ret:{j}:val"] = ()
            state_shape[f"ret:{j}:seen"] = ()
        self.state_shape = state_shape
        self.trace_names = ([p.name for p in m.ports if p.dir == "output"]
                            + list(self._ext_traced))
        self.comb_tape = self._topo_sort(comb_nodes)
        self.row_ports = sum(row for _t, _k, row in self.mem_ports)
        self.gather_ports = len(self.mem_ports) - self.row_ports
        self.uniform_nets = sum(1 for n in widths if n not in varying)

    def _lane_varying(self) -> set[str]:
        """The nets whose value may differ between lanes: the least set that
        holds the per-lane sources (scalar inputs, the cells of register-bank
        arguments, every memory read's data) and every net written by a
        ``CombAssign``, ``RegAssign``, ``ShiftReg`` or ``LoopController``
        (all its nets at once) that reads one of them.  Every other net is
        lane-uniform.  That is sound because every state leaf outside the
        sources starts at 0 in every lane (``_init_state``), so by induction
        on the cycle a uniform net computes the same value in every lane;
        memories, the captured returns and the conflict counts are per-lane
        state whatever their inputs."""
        varying = {b.port for b in self.binds if b.kind == "scalar"}
        varying.update(cn for b in self.binds for row in b.cells
                       for cn in row)
        deps: list[tuple[set[str], tuple[str, ...]]] = []
        for it in self.flat.items:
            if isinstance(it, MemRead):
                varying.add(it.dest)
            elif isinstance(it, (CombAssign, RegAssign, ShiftReg,
                                 LoopController)):
                deps.append((set(it.reads()), tuple(it.writes())))
        changed = True
        while changed:
            changed = False
            for reads, writes in deps:
                if not varying.issuperset(writes) and not reads.isdisjoint(
                        varying):
                    varying.update(writes)
                    changed = True
        return varying

    @staticmethod
    def _topo_sort(nodes: list[tuple]) -> list[tuple]:
        """Order combinational nodes so every read of a comb-driven net
        follows its producer (state nets and input ports are leaves)."""
        producer: dict[str, int] = {}
        for i, (dest, _k, _p, _r) in enumerate(nodes):
            if dest in producer:
                raise RTLSimError(
                    f"multiple combinational drivers of {dest!r}")
            producer[dest] = i
        succs: list[list[int]] = [[] for _ in nodes]
        indeg = [0] * len(nodes)
        for i, (_d, _k, _p, reads) in enumerate(nodes):
            for r in set(reads):
                j = producer.get(r)
                if j is not None and j != i:
                    succs[j].append(i)
                    indeg[i] += 1
        ready = [i for i, d in enumerate(indeg) if d == 0]
        order: list[int] = []
        while ready:
            i = ready.pop()
            order.append(i)
            for j in succs[i]:
                indeg[j] -= 1
                if indeg[j] == 0:
                    ready.append(j)
        if len(order) != len(nodes):
            cyc = [nodes[i][0] for i, d in enumerate(indeg) if d > 0]
            raise RTLSimError(f"combinational cycle through {cyc[:8]}")
        return [nodes[i] for i in order]

    # -- the per-cycle step --------------------------------------------------
    def _trace_ports(self, trace: bool) -> list[str]:
        return self.trace_names if trace else [
            p for pair in self.results for p in pair]

    @staticmethod
    def _load(env, state, scalar_inputs, state_nets, sr_loads, ops) -> None:
        for pn, key in scalar_inputs:
            env[pn] = state[key]
        for n in state_nets:
            env[n] = state[n]
        for dest, key in sr_loads:
            env[dest] = ops.sr_out(state[key])

    @staticmethod
    def _comb(tape, env, state, ops) -> None:
        """Evaluate the combinational ``tape`` (in order) into ``env``."""
        for dest, kind, payload, _reads in tape:
            if kind == "assign":
                fn, mk = payload
                env[dest] = fn(env, ops) & mk
            else:  # controller iter pulse
                c = payload
                act = state[c["active"]]
                iv = state[c["iv"]]
                sv = c["start"](env, ops) != 0
                step_up = iv + c["step"](env, ops)
                more = step_up < c["ub"](env, ops)
                if c["ii"] is not None:
                    cn = (state[c["iicnt"]] == c["ii"] - 1) \
                        if c["ii"] > 1 else (act == act)
                else:
                    cn = c["inner"](env, ops) != 0
                env[dest] = ops.b2i(sv | ((act != 0) & cn & more))

    @staticmethod
    def _clock(clocked, env, state, pend, ops) -> None:
        """Next values of the state the ``clocked`` entries write, into
        ``pend`` (read-old, write-new; writes to one key chain in order).
        On the JAX backend row ports take ``read_row``/``write_row``."""
        rows = isinstance(ops, _JaxOps)

        def cur(k):
            return pend[k] if k in pend else state[k]

        for ent in clocked:
            tag = ent[0]
            if tag == "sr":
                _t, key, fn, mk = ent
                pend[key] = ops.sr_push(cur(key), fn(env, ops) & mk)
            elif tag == "reg":
                _t, dest, fn, en, mk = ent
                enb = True if en is None else (en(env, ops) != 0)
                pend[dest] = ops.where(enb, fn(env, ops) & mk, cur(dest))
            elif tag == "memrd":
                _t, dest, memkey, afn, efn, mk, row = ent
                read = ops.read_row if rows and row else ops.read_mem
                enb = efn(env, ops) != 0
                v = read(state[memkey], afn(env, ops)) & mk
                pend[dest] = ops.where(enb, v, cur(dest))
            elif tag == "memwr":
                _t, memkey, afn, dfn, efn, mk, row = ent
                write = ops.write_row if rows and row else ops.write_mem
                enb = efn(env, ops) != 0
                pend[memkey] = write(
                    cur(memkey), afn(env, ops), dfn(env, ops) & mk, enb)
            else:  # controller clocked half
                c = ent[1]
                act = state[c["active"]]
                iv = state[c["iv"]]
                actb = act != 0
                sv = c["start"](env, ops) != 0
                lbv = c["lb"](env, ops)
                stepv = c["step"](env, ops)
                ubv = c["ub"](env, ops)
                step_up = iv + stepv
                more = step_up < ubv
                if c["ii"] is not None:
                    if c["ii"] > 1:
                        iicnt = state[c["iicnt"]]
                        cn = iicnt == c["ii"] - 1
                        nxt = ops.where(cn, ops.zero, iicnt + ops.one)
                        pend[c["iicnt"]] = ops.where(
                            sv, ops.zero, ops.where(actb, nxt, iicnt))
                    else:
                        cn = actb | True  # constant true, array-shaped
                else:
                    cn = c["inner"](env, ops) != 0
                ivm = c["ivmask"]
                pend[c["iv"]] = ops.where(
                    sv, lbv & ivm,
                    ops.where(actb & cn & more, step_up & ivm, iv))
                pend[c["active"]] = ops.where(
                    sv, ops.one,
                    ops.where(actb & cn & (step_up >= ubv), ops.zero, act))
                if c["endp"]:
                    pend[c["endp"]] = ops.b2i(actb & cn & (step_up >= ubv))

    def _capture(self, env, state, pend, ops) -> None:
        """The captured returns and the port-conflict counts, into
        ``pend``."""
        for j, (dp, vp) in enumerate(self.results):
            validb = env[vp] != 0
            seen = state[f"ret:{j}:seen"]
            pend[f"ret:{j}:val"] = ops.where(
                validb & (seen == 0), env[dp], state[f"ret:{j}:val"])
            pend[f"ret:{j}:seen"] = ops.where(validb, ops.one, seen)
        if self.asserts:
            viols = [ops.b2i(sum(ops.b2i(en(env, ops) != 0)
                                 for en in ens) > 1)
                     for _bus, ens in self.asserts]
            stacked = (jnp if ops.__class__ is _JaxOps
                       else np).stack(viols, axis=-1)
            pend["cf"] = state["cf"] + stacked

    def _make_step(self, ops, trace: bool):
        """The whole design's step over batch-first state (the NumPy
        backend: every value is a (B,) array)."""
        names = self._trace_ports(trace)

        def step(state, t_start):
            env: dict[str, Any] = {"t_start": t_start, "clk": 0, "rst": 0}
            self._load(env, state, self.scalar_inputs, self.state_nets,
                       self.sr_loads, ops)
            self._comb(self.comb_tape, env, state, ops)
            pend: dict[str, Any] = {}
            self._clock(self.clocked, env, state, pend, ops)
            self._capture(env, state, pend, ops)
            ns = dict(state)
            ns.update(pend)
            return ns, tuple(env[p] for p in names)

        return step, names

    def _split(self):
        """Partition the step by lane-uniformity: ``(uniform, varying)``,
        each ``(state keys, state nets, shift-register loads, comb tape,
        clocked entries)``.  Scalar inputs, memories, returns and conflict
        counts are varying state."""
        varying = self.varying

        def lanes(ent) -> bool:
            tag = ent[0]
            if tag == "sr":
                return ent[1][len("sr:"):] in varying
            if tag == "reg":
                return ent[1] in varying
            if tag == "ctrl":
                return ent[1]["iv"] in varying
            return True  # memory ports

        parts = []
        for side in (False, True):
            nets = [n for n in self.state_nets if (n in varying) == side]
            srs = [(d, k) for d, k in self.sr_loads if (d in varying) == side]
            parts.append([nets + [k for _d, k in srs], nets, srs,
                          [nd for nd in self.comb_tape
                           if (nd[0] in varying) == side],
                          [e for e in self.clocked if lanes(e) == side]])
        uniform = set(parts[0][0])
        parts[1][0] = [k for k in self.state_shape if k not in uniform]
        return parts

    def scan_program(self, trace: bool = False):
        """The whole batched run as one traceable function
        ``(state, t_start_per_cycle) -> (final_state, per_cycle_outputs)``
        (a ``lax.scan`` over cycles), and the port names of its per-cycle
        outputs.  State and outputs keep the host layout: (B, ...) leaves,
        (T, B) outputs.  Trace it under ``jax.enable_x64(True)``: state is
        int64.

        Each cycle evaluates the lane-uniform nets (outside ``varying``)
        once, on unbatched scalars, and ``jax.vmap``-s only the
        lane-varying rest over the lanes, with the uniform values closed
        over (unbatched).  Uniform state is taken from lane 0 at the start
        and broadcast back at the end: ``_init_state`` gives it the same
        value in every lane.  Memories are held lanes-minor,
        ``(depth, B)``, inside the scan, so a row port reads or writes one
        contiguous row of lanes in place."""
        ops = _JaxOps()
        names = self._trace_ports(trace)
        (ukeys, unets, usrs, utape, uclocked), \
            (vkeys, vnets, vsrs, vtape, vclocked) = self._split()
        varying = self.varying
        vnames = [p for p in names if p in varying]
        axes = {k: 1 if k.startswith("mem:") else 0 for k in vkeys}

        def scanner(state, xs):
            B = next(iter(state.values())).shape[0]

            def step(carry, t_start):
                ust, vst = carry
                env: dict[str, Any] = {"t_start": t_start, "clk": 0,
                                       "rst": 0}
                self._load(env, ust, (), unets, usrs, ops)
                self._comb(utape, env, ust, ops)
                upend: dict[str, Any] = {}
                self._clock(uclocked, env, ust, upend, ops)

                def lane(vs):
                    lenv = dict(env)
                    self._load(lenv, vs, self.scalar_inputs, vnets, vsrs, ops)
                    self._comb(vtape, lenv, vs, ops)
                    pend: dict[str, Any] = {}
                    self._clock(vclocked, lenv, vs, pend, ops)
                    self._capture(lenv, vs, pend, ops)
                    return ({k: pend.get(k, v) for k, v in vs.items()},
                            tuple(lenv[p] for p in vnames))

                nvs, vouts = jax.vmap(lane, in_axes=(axes,),
                                      out_axes=(axes, 0), axis_size=B)(vst)
                nus = {k: jnp.asarray(upend.get(k, v), dtype=jnp.int64)
                       for k, v in ust.items()}
                by = dict(zip(vnames, vouts))
                outs = tuple(by[p] if p in by
                             else jnp.asarray(env[p], dtype=jnp.int64)
                             for p in names)
                return (nus, nvs), outs

            ust = {k: state[k][0] for k in ukeys}
            vst = {k: jnp.swapaxes(state[k], 0, 1) if axes[k] else state[k]
                   for k in vkeys}
            (ust, vst), ys = jax.lax.scan(step, (ust, vst), xs)
            final = {k: jnp.broadcast_to(v[None], (B,) + v.shape)
                     for k, v in ust.items()}
            final.update({k: jnp.swapaxes(v, 0, 1) if axes[k] else v
                          for k, v in vst.items()})
            ys = tuple(y if p in varying
                       else jnp.broadcast_to(y[:, None], (y.shape[0], B))
                       for p, y in zip(names, ys))
            return final, ys

        return scanner, names

    # -- stimulus packing ----------------------------------------------------
    def _layout(self, b: _Bind, arr: np.ndarray) -> np.ndarray:
        """(B, *shape) tensor -> (B, banks, elems) interface layout."""
        mt = b.mt
        perm = (0,) + tuple(d + 1 for d in mt.distributed) \
            + tuple(d + 1 for d in mt.packed)
        r = np.ascontiguousarray(np.transpose(arr, perm))
        return r.reshape(arr.shape[0], mt.num_banks, mt.bank_elems)

    def _unlayout(self, b: _Bind, r: np.ndarray) -> np.ndarray:
        mt = b.mt
        B = r.shape[0]
        dist_shape = tuple(mt.shape[d] for d in mt.distributed)
        packed_shape = tuple(mt.shape[d] for d in mt.packed)
        r = r.reshape((B,) + dist_shape + packed_shape)
        perm = (0,) + tuple(d + 1 for d in mt.distributed) \
            + tuple(d + 1 for d in mt.packed)
        inv = np.argsort(perm)
        return np.ascontiguousarray(np.transpose(r, inv))

    @span("hir.sim.layout")
    def _init_state(self, args: Sequence[Any], B: int) -> dict[str, np.ndarray]:
        state: dict[str, np.ndarray] = {}
        for key, shape in self.state_shape.items():
            state[key] = np.zeros((B,) + shape, dtype=I64)
        for b in self.binds:
            a = args[b.index]
            if b.kind == "scalar":
                v = np.broadcast_to(np.asarray(a, dtype=I64), (B,))
                state[f"in:{b.port}"] = (v & _mask_of(b.width)).astype(I64)
                continue
            arr = np.asarray(a, dtype=I64)
            if arr.shape != (B,) + b.mt.shape:
                raise RTLSimError(
                    f"arg {b.index}: expected batch shape {(B,) + b.mt.shape},"
                    f" got {arr.shape}")
            r = self._layout(b, arr & _mask_of(b.width))
            if b.kind == "ram":
                state[b.memkey] = r[:, 0, :].copy()
            else:
                for bk, row in enumerate(b.cells):
                    for d, cn in enumerate(row):
                        state[cn] = r[:, bk, d].copy()
        return state

    # -- execution -----------------------------------------------------------
    @span("hir.sim.run")
    def run(self, args: Sequence[Any], cycles: int, batched: bool = False,
            check_conflicts: bool = True, trace: bool = False) -> SimResult:
        """Simulate ``cycles`` cycles of the design over a stimulus batch.

        ``args`` mirrors the hir.func arguments: scalars (python ints or
        (B,) arrays) and numpy arrays of the memref shape ((B, *shape) when
        ``batched``).  ``t_start`` pulses at cycle 0.  Unlike the
        event-driven simulator the input arrays are never mutated."""
        if not batched:  # one lane
            args = [np.asarray([a], dtype=I64) if b.kind == "scalar"
                    else np.asarray(a, dtype=I64)[None]
                    for b, a in zip(self.binds, list(args))]
        if len(args) != len(self.binds):
            raise RTLSimError(f"expected {len(self.binds)} args")
        B = None
        for b, a in zip(self.binds, args):
            if b.kind != "scalar":
                B = np.asarray(a).shape[0]
                break
            a = np.asarray(a)
            if a.ndim == 1:
                B = a.shape[0]
        if B is None:
            B = 1
        state = self._init_state(args, B)
        xs = np.zeros(cycles, dtype=I64)
        xs[0] = 1
        if self.backend == "jax":
            final, ys, timing = self._run_jax(state, xs, B, trace)
        else:
            final, ys, timing = self._run_numpy(state, xs, B, trace)
        res = self._collect(final, ys, B, cycles, check_conflicts, trace)
        res.platform, res.compile_s, res.run_s = timing
        return res

    def _run_jax(self, state, xs, B: int, trace: bool):
        key = (trace, B, len(xs))
        with jax.enable_x64(True):
            with span("hir.sim.put"):
                s0 = jax.device_put(state)
                xs_d = jax.device_put(xs)
                jax.block_until_ready((s0, xs_d))
            count("hir.sim.row_ports", self.row_ports)
            count("hir.sim.gather_ports", self.gather_ports)
            count("hir.sim.uniform_nets", self.uniform_nets)
            count("hir.sim.leaves_in", len(state) + 1)
            count("hir.sim.bytes_in",
                  sum(a.nbytes for a in state.values()) + xs.nbytes)
            compile_s = 0.0
            if key not in self._compiled:
                count("hir.sim.compiles")
                with span("hir.sim.lower") as lower:
                    scanner, names = self.scan_program(trace)
                    lowered = jax.jit(scanner).lower(s0, xs_d)
                with span("hir.sim.compile") as comp:
                    self._compiled[key] = (lowered.compile(), names)
                compile_s = lower.seconds + comp.seconds
            exe, names = self._compiled[key]
            with span("hir.sim.scan") as scan:
                final, ys = jax.block_until_ready(exe(s0, xs_d))
            platform = xs_d.devices().pop().platform
            with span("hir.sim.fetch"):
                final = {k: np.asarray(v) for k, v in final.items()}
                ys = {n: np.asarray(y) for n, y in zip(names, ys)}
            count("hir.sim.leaves_out", len(final) + len(ys))
            count("hir.sim.bytes_out",
                  sum(a.nbytes for a in final.values())
                  + sum(y.nbytes for y in ys.values()))
        return final, ys, (platform, compile_s, scan.seconds)

    def _run_numpy(self, state, xs, B: int, trace: bool):
        with span("hir.sim.scan") as scan:
            step, names = self._make_step(_NumpyOps(B), trace)
            recs: list[tuple] = []
            for t in range(len(xs)):
                state, outs = step(state, I64(xs[t]))
                recs.append(outs)
            ys = {n: np.stack([np.broadcast_to(np.asarray(r[i], dtype=I64),
                                               (B,))
                               for r in recs])
                  for i, n in enumerate(names)}
        return state, ys, ("host", 0.0, scan.seconds)

    @span("hir.sim.collect")
    def _collect(self, final, ys, B, cycles, check_conflicts, trace):
        rts = self.func.attrs.get("result_types", [])
        returns, valids = [], []
        for j, (dp, _vp) in enumerate(self.results):
            p = np.asarray(final[f"ret:{j}:val"], dtype=I64)
            w = self.widths[dp]
            signed = (isinstance(rts[j], IntType) and rts[j].signed
                      if j < len(rts) else True)
            returns.append(_signed_fix(p, w, signed))
            valids.append(np.asarray(final[f"ret:{j}:seen"], dtype=I64))
        arrays: dict[int, np.ndarray] = {}
        for b in self.binds:
            if b.kind == "scalar":
                continue
            if b.kind == "ram":
                r = np.asarray(final[b.memkey], dtype=I64)[:, None, :]
            else:
                r = np.zeros((B, b.mt.num_banks, b.mt.bank_elems), dtype=I64)
                for bk, row in enumerate(b.cells):
                    for d, cn in enumerate(row):
                        r[:, bk, d] = np.asarray(final[cn], dtype=I64)
            r = r.reshape(B, b.mt.num_banks, b.mt.bank_elems)
            arr = self._unlayout(b, r)
            arrays[b.index] = _signed_fix(arr, b.width, b.signed)
        if self.asserts:
            per_bus = np.asarray(final["cf"], dtype=I64).reshape(
                B, len(self.asserts))
            conflicts = per_bus.sum(axis=1)
        else:
            conflicts = np.zeros(B, dtype=I64)
        if check_conflicts and conflicts.any():
            lanes = np.nonzero(conflicts)[0][:4].tolist()
            raise RTLSimError(
                f"port conflict (UB 4.5) in lanes {lanes}; "
                f"buses={self.conflict_buses[:4]}")
        tr = None
        if trace:
            tr = {n: np.asarray(y, dtype=I64) for n, y in ys.items()}
        return SimResult(self.backend, cycles, B, returns, valids, arrays,
                         conflicts, list(self.conflict_buses), tr)


# ---------------------------------------------------------------------------
# Convenience drivers
# ---------------------------------------------------------------------------


def design_of(mods: dict[str, Any], entry: str) -> RTLDesign:
    """Rebuild an ``RTLDesign`` from ``generate_verilog``'s output map."""
    d = RTLDesign(entry=entry)
    for name, vm in mods.items():
        m = getattr(vm, "rtl", None) or vm
        if not isinstance(m, RTLModule):
            raise RTLSimError(f"module {name} carries no RTL structure")
        d.add(m)
    return d


def simulator_for(module: Module, entry: str, *, hierarchy: str = "inline",
                  backend: str = "jax", rtl_spec: Optional[str] = "default",
                  ) -> tuple[RTLSimulator, Module]:
    """Clone ``module``, run the codegen pipeline and build a simulator.

    Returns ``(sim, prepared)`` where ``prepared`` is the cloned module
    *after* the pre-codegen pipeline — the exact HIR the event-driven oracle
    (``lower.simulate``) should run for lane-by-lane comparison."""
    from .verilog import generate_verilog

    prepared = module.clone()
    kw = {} if rtl_spec == "default" else {"rtl_spec": rtl_spec}
    mods = generate_verilog(prepared, entry, hierarchy=hierarchy, **kw)
    design = design_of(mods, entry)
    sim = RTLSimulator(design, prepared.funcs[entry], entry, backend=backend)
    return sim, prepared


@span("hir.sim.probe")
def probe_cycles(prepared: Module, entry: str, args: Sequence[Any],
                 margin: int = 16) -> int:
    """Cycle budget for a batched run: one event-driven simulation on fresh
    zero-filled copies of ``args`` (loop trip counts are static in this flow,
    so the latency is data-independent)."""
    from ..lower.to_sim import simulate

    probe_args = []
    for a in args:
        if isinstance(a, np.ndarray):
            probe_args.append(np.zeros_like(a))
        else:
            probe_args.append(0)
    res = simulate(prepared, entry, probe_args)
    return int(res["cycles"]) + margin


def stack_stimulus(make_inputs: Callable[..., list], n_vectors: int,
                   base_seed: int = 0, **kw) -> list[np.ndarray]:
    """Stack ``n_vectors`` calls of a gallery-style ``make_inputs(seed=k)``
    into batch-first arrays — domain-respecting random stimulus."""
    cols = None
    for k in range(n_vectors):
        row = make_inputs(seed=base_seed + k, **kw)
        if cols is None:
            cols = [[] for _ in row]
        for c, v in zip(cols, row):
            c.append(np.asarray(v))
    return [np.stack(c).astype(I64) for c in cols]


def fold_in_stimulus(widths: Sequence[int], n_lanes: int,
                     seed: int = 0) -> list[np.ndarray]:
    """Per-lane random stimulus from jax-native counter-based PRNG streams:
    one scalar per (input, lane), each drawn from an independent stream
    derived by ``jax.random.fold_in(fold_in(key(seed), input), lane)`` and
    masked to the input's bit width.  Unlike sequential generators, fold_in
    streams are stable under lane/input reordering — adding a lane never
    perturbs the values the existing lanes see, so seed-pinned differential
    suites stay reproducible as they grow.  Each input's lanes are drawn
    in one jitted ``vmap`` over the lane index (one device round trip per
    input)."""
    key = jax.random.key(seed)
    lanes = jnp.arange(n_lanes, dtype=jnp.uint32)
    out: list[np.ndarray] = []
    for i, w in enumerate(widths):
        words = np.asarray(_lane_words(jax.random.fold_in(key, i), lanes))
        hi = words[:, 0].astype(np.uint64)
        lo = words[:, 1].astype(np.uint64)
        mask = np.uint64((1 << min(int(w), 63)) - 1)
        out.append((((hi << np.uint64(32)) | lo) & mask).astype(I64))
    return out


@jax.jit
def _lane_words(key, lanes):
    """Two uint32 words per lane from the stream ``fold_in(key, lane)``."""
    return jax.vmap(lambda lane: jax.random.bits(
        jax.random.fold_in(key, lane), (2,), dtype=jnp.uint32))(lanes)


# ---------------------------------------------------------------------------
# Differential verification harness
# ---------------------------------------------------------------------------


@dataclass
class DiffReport:
    """Outcome of ``run_differential``.  ``platform``, ``compile_s`` and
    ``run_s`` describe the batched simulator run over all ``n_vectors``
    lanes (see ``SimResult``): ``compile_s`` is the step build, trace,
    lowering and XLA compile of the scan, ``run_s`` the scan alone."""

    kernel: str
    hierarchy: str
    backend: str
    n_vectors: int
    cycles: int
    event_lanes_checked: int
    event_ok: bool
    oracle_ok: Optional[bool]
    passes_ok: Optional[dict[str, bool]]
    mismatches: list[str] = field(default_factory=list)
    platform: str = "host"
    compile_s: float = 0.0
    run_s: float = 0.0

    @property
    def ok(self) -> bool:
        return (self.event_ok and self.oracle_ok in (None, True)
                and (self.passes_ok is None
                     or all(self.passes_ok.values())))


def _result_args(sim: RTLSimulator, res: SimResult, lane: int,
                 args_batch: Sequence[np.ndarray]) -> list[np.ndarray]:
    """Final memref contents for one lane, in argument order."""
    out = []
    for b in sim.binds:
        if b.kind == "scalar":
            out.append(None)
        else:
            out.append(res.arrays[b.index][lane])
    return out


@span("hir.diff")
def run_differential(module: Module, entry: str,
                     args_batch: Sequence[np.ndarray], *,
                     kernel: str = "", hierarchy: str = "inline",
                     backend: str = "jax", event_lanes: int = 2,
                     oracle: Optional[Callable] = None,
                     oracle_nargs: int = 0, result_arg: int = -1,
                     check_passes: bool = True,
                     pass_lanes: int = 16) -> DiffReport:
    """Differentially verify one kernel over a stimulus batch.

    (a) runs the vectorized simulator over the whole batch and re-runs
    ``event_lanes`` sample lanes through the event-driven oracle, comparing
    final memory arrays and scalar returns; (b) when ``oracle`` is given,
    checks the memref written by the design (``result_arg``) against
    ``oracle(*args[:oracle_nargs])`` on every lane; (c) when
    ``check_passes``, re-lowers without RTL passes and replays the pass
    pipeline one pass at a time, asserting per-cycle result-port traces and
    final state match between every pass input and output
    (``verify_rtl_passes``)."""
    mismatches: list[str] = []
    sim, prepared = simulator_for(module, entry, hierarchy=hierarchy,
                                  backend=backend)
    B = int(np.asarray(args_batch[0]).shape[0]) if args_batch else 1
    single0 = [np.asarray(a)[0] for a in args_batch]
    cycles = probe_cycles(prepared, entry, single0)
    res = sim.run(args_batch, cycles, batched=True)

    lanes = list(range(min(event_lanes, B)))
    event_ok = _check_event_lanes(sim, prepared, entry, args_batch, res,
                                  lanes, mismatches)
    oracle_ok: Optional[bool] = None
    if oracle is not None:
        ridx = result_arg if result_arg >= 0 else len(args_batch) - 1
        oracle_ok = _check_oracle(oracle, oracle_nargs, args_batch,
                                  res.arrays[ridx], mismatches)

    passes_ok = None
    if check_passes:
        sub = [np.asarray(a)[:min(pass_lanes, B)] for a in args_batch]
        with span("hir.diff.passes"):
            passes_ok, pmism = verify_rtl_passes(
                prepared, entry, sub, cycles, hierarchy=hierarchy)
        mismatches.extend(pmism)

    return DiffReport(kernel or entry, hierarchy, sim.backend, B, cycles,
                      len(lanes), event_ok, oracle_ok, passes_ok, mismatches,
                      res.platform, res.compile_s, res.run_s)


@span("hir.diff.event_lanes")
def _check_event_lanes(sim: RTLSimulator, prepared: Module, entry: str,
                       args_batch: Sequence[np.ndarray], res: SimResult,
                       lanes: list[int], mismatches: list[str]) -> bool:
    """Re-run ``lanes`` through the event-driven simulator; compare final
    memory arrays and scalar returns with the batched run."""
    from ..lower.to_sim import simulate

    event_ok = True
    for k in lanes:
        ev_args: list[Any] = []
        for b, a in zip(sim.binds, args_batch):
            al = np.asarray(a)[k]
            ev_args.append(int(al) if b.kind == "scalar" else al.copy())
        ev = simulate(prepared, entry, ev_args)
        for b in sim.binds:
            if b.kind == "scalar":
                continue
            got = res.arrays[b.index][k]
            want = ev_args[b.index]
            if not np.array_equal(got, want):
                event_ok = False
                mismatches.append(
                    f"lane {k} arg {b.index}: vectorized != event-driven")
        ev_rets = ev.get("returns") or {}
        for j in range(len(sim.results)):
            if f"ret{j}" not in ev_rets:
                continue
            rv = ev_rets[f"ret{j}"]
            if res.returns_valid[j][k] == 0:
                event_ok = False
                mismatches.append(f"lane {k} result_{j}: no valid pulse")
            elif int(res.returns[j][k]) != int(rv):
                event_ok = False
                mismatches.append(
                    f"lane {k} result_{j}: {int(res.returns[j][k])} != {rv}")
    return event_ok


@span("hir.diff.oracle")
def _check_oracle(oracle: Callable, oracle_nargs: int,
                  args_batch: Sequence[np.ndarray], got: np.ndarray,
                  mismatches: list[str]) -> bool:
    """Every lane of the design's result memref against the functional
    oracle; stops at the first mismatch."""
    for k in range(got.shape[0]):
        want = np.asarray(oracle(*[np.asarray(args_batch[i])[k]
                                   for i in range(oracle_nargs)]))
        if not np.array_equal(got[k].astype(I64), want.astype(I64)):
            mismatches.append(f"lane {k}: vectorized != oracle")
            return False
    return True


def verify_rtl_passes(prepared: Module, entry: str,
                      args_batch: Sequence[np.ndarray], cycles: int, *,
                      hierarchy: str = "inline",
                      spec: Optional[str] = None,
                      backend: str = "numpy",
                      ) -> tuple[dict[str, bool], list[str]]:
    """Per-pass differential check: starting from the raw lowering, run each
    RTL pass of ``spec`` on a copy of the design and assert the pass output
    is cycle-accurate-equivalent to its input (result-port traces every
    cycle, final memref arrays, captured returns).  ``prepared`` must
    already be through the pre-codegen pipeline (see ``simulator_for``)."""
    from .verilog import RTL_PIPELINE_SPEC, lower_to_rtl

    spec = spec if spec is not None else RTL_PIPELINE_SPEC
    func = prepared.funcs[entry]
    rtl.clear_key_intern()
    emit = [entry] if hierarchy == "inline" else None
    design = lower_to_rtl(prepared, emit or [entry], hierarchy=hierarchy,
                          entry=entry)

    def signature(d: RTLDesign):
        s = RTLSimulator(d.copy(), func, entry, backend=backend)
        r = s.run(args_batch, cycles, batched=True, check_conflicts=False,
                  trace=True)
        return r

    ok: dict[str, bool] = {}
    mism: list[str] = []
    prev = signature(design)
    for name in [p.strip() for p in spec.split(",") if p.strip()]:
        pm = PassManager.from_spec(name)
        pm.run(design)
        cur = signature(design)
        good = True
        for p, tr in prev.trace.items():
            if p not in cur.trace or not np.array_equal(tr, cur.trace[p]):
                good = False
                mism.append(f"{name}: trace of {p} diverged")
        for i, arr in prev.arrays.items():
            if not np.array_equal(arr, cur.arrays[i]):
                good = False
                mism.append(f"{name}: final arg {i} diverged")
        if not np.array_equal(prev.conflicts, cur.conflicts):
            good = False
            mism.append(f"{name}: conflict counts diverged")
        ok[name] = good
        prev = cur
    return ok, mism


__all__ = [
    "RTLSimError", "RTLSimulator", "SimResult", "DiffReport",
    "close_module", "design_of", "simulator_for", "probe_cycles",
    "stack_stimulus", "run_differential", "verify_rtl_passes",
]
