"""jaxpr device-loop hygiene pass (DESIGN.md §7).

Traces every program in the canonical inventory (``analysis/programs.py``)
and walks the jaxpr — structurally, before XLA sees it:

  host-callback-in-loop   ``pure_callback``/``io_callback``/
                          ``debug_callback``/``device_put`` inside a
                          ``while_loop``/``scan`` body.  One of these turns
                          the O(visits/K) host-sync story into O(visits) —
                          the exact regression the megastep exists to
                          prevent, caught as a trace property.
  host-callback           the same primitives anywhere else in the program
                          (warning: suspicious in a hot program, fatal in
                          a loop).
  x64-promotion           any intermediate or I/O aval in f64/s64/u64/c128
                          — the engine's dtype story is f32 values + exact
                          int32 (hi, lo) edge counters; a silent upcast
                          doubles every HBM tile.
  weak-output             a weakly-typed program output — a literal leaked
                          past the declared dtypes and will re-promote at
                          the next op.
  counter-dtype           the program's exact-edge counters are not int32.
  donation-unsafe         a donation-candidate state output whose avals no
                          longer match its input (shape/dtype drift breaks
                          buffer reuse even before ``donate_argnums`` is
                          requested).

``check_program`` is the per-program unit so tests can feed seeded-violation
programs straight in.
"""
from __future__ import annotations

from typing import List

from repro.analysis import Finding, PassContext

CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback")
TRANSFER_PRIMS = ("device_put",)
LOOP_PRIMS = ("while", "scan")
BAD_DTYPES = ("float64", "int64", "uint64", "complex128")


def _subjaxprs(value):
    """Yield every Jaxpr hiding in an eqn param value."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _subjaxprs(v)


def _walk(jaxpr, in_loop: bool, visit):
    for eqn in jaxpr.eqns:
        visit(eqn, in_loop)
        child_in_loop = in_loop or eqn.primitive.name in LOOP_PRIMS
        for value in eqn.params.values():
            for sub in _subjaxprs(value):
                _walk(sub, child_in_loop, visit)


def check_program(program) -> List[Finding]:
    import jax

    findings: List[Finding] = []
    key = program.key

    def finding(code, severity, message):
        findings.append(Finding(pass_name="jaxpr.hygiene", code=code,
                                severity=severity, location=key,
                                message=message))

    closed = jax.make_jaxpr(program.fn)(*program.args)

    callbacks_in_loop: List[str] = []
    callbacks_outside: List[str] = []
    bad_dtype_prims: List[str] = []

    def visit(eqn, in_loop):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS + TRANSFER_PRIMS:
            (callbacks_in_loop if in_loop else callbacks_outside).append(name)
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and str(dtype) in BAD_DTYPES:
                bad_dtype_prims.append(f"{name}->{dtype}")

    _walk(closed.jaxpr, False, visit)

    if callbacks_in_loop:
        finding("host-callback-in-loop", "error",
                f"{len(callbacks_in_loop)} host callback/transfer op(s) "
                f"inside a device loop body ({sorted(set(callbacks_in_loop))})"
                f" — every loop iteration would sync the host")
    if callbacks_outside:
        finding("host-callback", "warning",
                f"{len(callbacks_outside)} host callback/transfer op(s) in "
                f"the program ({sorted(set(callbacks_outside))})")
    if bad_dtype_prims:
        finding("x64-promotion", "error",
                f"{len(bad_dtype_prims)} 64-bit intermediate(s): "
                f"{sorted(set(bad_dtype_prims))[:4]} — the engine dtype "
                f"contract is f32 values + int32 counters")

    out_shape = jax.eval_shape(program.fn, *program.args)
    leaves = jax.tree_util.tree_leaves(out_shape)
    for i, leaf in enumerate(leaves):
        if str(getattr(leaf, "dtype", "")) in BAD_DTYPES:
            finding("x64-promotion", "error",
                    f"program output {i} is {leaf.dtype}")
        if getattr(leaf, "weak_type", False):
            finding("weak-output", "error",
                    f"program output {i} ({leaf.dtype}) is weakly typed — "
                    f"a literal leaked past the declared dtypes")

    for name, sds in program.counters(out_shape).items():
        if str(sds.dtype) != "int32":
            finding("counter-dtype", "error",
                    f"exact-edge counter {name} is {sds.dtype}, not the "
                    f"int32 (hi, lo) contract")

    for name, in_tree, out_tree in program.donation(program.args, out_shape):
        in_leaves = jax.tree_util.tree_leaves(in_tree)
        out_leaves = jax.tree_util.tree_leaves(out_tree)
        in_avals = [(tuple(l.shape), str(l.dtype)) for l in in_leaves]
        out_avals = [(tuple(l.shape), str(l.dtype)) for l in out_leaves]
        if in_avals != out_avals:
            finding("donation-unsafe", "error",
                    f"state {name!r} comes back with different avals than "
                    f"it went in ({in_avals} -> {out_avals}) — the state "
                    f"planes must stay donation-compatible")
    return findings


def run_pass(ctx: PassContext) -> List[Finding]:
    from repro.analysis.programs import build_programs

    findings: List[Finding] = []
    for program in build_programs(only=ctx.only_programs):
        findings.extend(check_program(program))
    return findings
