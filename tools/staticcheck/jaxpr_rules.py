"""Layer 2a: jaxpr lints over registry trace entries (HMG101, HMG102).

Each registry entry is traced with ``jax.make_jaxpr`` at its canonical
shapes; the resulting jaxpr is walked recursively (descending into
``pjit``/``scan``/``while``/``cond`` sub-jaxprs) and linted. ``pallas_call``
equations are deliberately NOT descended into: the in-kernel int8 -> f32
register cast is the design — the rule targets dequant that leaks *outside*
the kernel into an HBM-resident slab.
"""
from __future__ import annotations

import math
from typing import Iterator, List, Tuple

from tools.staticcheck import Violation
from tools.staticcheck.registry import TraceEntry, trace_entries

_TRANSFER_PRIMS = {"device_put", "copy_to_host_async", "io_callback",
                   "pure_callback", "host_callback_call"}


def _iter_eqns(jaxpr, in_pallas: bool = False) -> Iterator[Tuple[object,
                                                                 bool]]:
    """Yield (eqn, inside_pallas) over jaxpr and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        yield eqn, in_pallas
        if prim == "pallas_call":
            continue                     # in-kernel casts are the design
        for val in eqn.params.values():
            for sub in _as_jaxprs(val):
                yield from _iter_eqns(sub, in_pallas)


def _as_jaxprs(val):
    from jax.extend import core

    if isinstance(val, core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, core.Jaxpr):
        yield val
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _as_jaxprs(item)


def lint_jaxpr(entry: TraceEntry, jaxpr) -> List[Violation]:
    out: List[Violation] = []
    for eqn, in_pallas in _iter_eqns(jaxpr):
        prim = eqn.primitive.name
        if prim in _TRANSFER_PRIMS:
            out.append(Violation(
                "HMG102", entry.name, 0,
                f"'{prim}' inside the traced region — host/device "
                "transfers must stay outside jit boundaries"))
        elif (prim == "convert_element_type"
              and entry.max_upcast_elems is not None):
            (invar,) = eqn.invars
            in_dt = getattr(getattr(invar, "aval", None), "dtype", None)
            out_dt = eqn.params.get("new_dtype")
            if in_dt is None or out_dt is None:
                continue
            if str(in_dt) == "int8" and str(out_dt) == "float32":
                shape = getattr(invar.aval, "shape", ())
                n = math.prod(shape) if shape else 1
                if n > entry.max_upcast_elems:
                    out.append(Violation(
                        "HMG101", entry.name, 0,
                        f"slab-scale int8->f32 convert_element_type of "
                        f"shape {tuple(shape)} ({n} elems > budget "
                        f"{entry.max_upcast_elems}) outside the Pallas "
                        "kernel — dequant is leaking into HBM before the "
                        "rescore boundary"))
    return out


def run_trace_rules(names=None) -> List[Violation]:
    """Trace every registry entry and lint its jaxpr."""
    import jax

    out: List[Violation] = []
    for entry in trace_entries():
        if names and entry.name not in names:
            continue
        try:
            fn, args, kwargs = entry.build()
            jaxpr = jax.make_jaxpr(fn)(*args, **kwargs).jaxpr
        except Exception as e:            # a broken entry must fail loudly
            out.append(Violation(
                "HMG101", entry.name, 0,
                f"registry entry failed to trace: {type(e).__name__}: {e}"))
            continue
        out.extend(lint_jaxpr(entry, jaxpr))
    return out
