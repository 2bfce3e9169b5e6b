"""Shared jaxpr-introspection helpers for the tier-2 rules (DESIGN.md §15).

The tier-1 rules read *source text*; this tier reads what the compiler
actually traces.  Everything here is rule-agnostic plumbing:

* :func:`iter_eqns` — recursive equation walk through every sub-jaxpr
  (scan/while/cond bodies, pjit calls, custom_jvp wrappers …), yielding
  each equation with its nesting context (are we inside a ``scan`` body?);
* :func:`source_site` — map an equation back to a repo-relative
  ``(file, line, function)`` anchor via JAX's source_info, so jaxpr
  findings share the tier-1 ``Finding`` type and the baseline's
  (rule, file, symbol) matching;
* :func:`trace32_64` — trace a callable under default x32 *and* under
  ``jax.enable_x64(True)`` for the J002 drift comparison.

Nothing in this module imports the simulator — target construction lives
in ``targets.py`` so the walker stays reusable for fixture programs in
tests.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Iterator, Optional, Tuple

# Deliberately lazy/defensive: the analysis CLI must keep working (tier-1
# at least) on a host without jax; the jaxpr tier gates itself.
try:
    import jax
    from jax._src import source_info_util
    HAVE_JAX = True
except Exception:                                    # pragma: no cover
    jax = None
    source_info_util = None
    HAVE_JAX = False

REPO_MARKER = os.sep + "src" + os.sep + "repro" + os.sep

#: primitives that open a scan body — reductions inside them repeat per
#: step and (for J001) interact with the batch axis
_SCAN_PRIMS = {"scan"}
#: primitives whose sub-jaxprs are control flow but *not* a scan body
_FLOW_PRIMS = {"while", "cond", "pjit", "custom_jvp_call",
               "custom_vjp_call", "custom_vjp_call_jaxpr", "remat", "xla_call",
               "closed_call", "core_call", "checkpoint"}


@dataclass(frozen=True)
class EqnSite:
    """One traced equation plus its walk context."""
    eqn: object              # jax.core.JaxprEqn
    in_scan: bool            # nested (at any depth) inside a scan body
    depth: int               # sub-jaxpr nesting depth


def _sub_jaxprs(eqn) -> Iterator[object]:
    """Yield every jaxpr hiding in an equation's params."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                yield v.jaxpr                        # ClosedJaxpr
            elif hasattr(v, "eqns"):
                yield v                              # raw Jaxpr


def iter_eqns(jaxpr, in_scan: bool = False,
              depth: int = 0) -> Iterator[EqnSite]:
    """Depth-first walk over every equation of ``jaxpr`` and its children.

    ``in_scan`` is sticky: once the walk enters a ``scan`` body, all
    nested equations (including deeper scans and conds) report
    ``in_scan=True`` — J001's "inside the scan body" is about runtime
    repetition, not immediate nesting.
    """
    for eqn in jaxpr.eqns:
        yield EqnSite(eqn, in_scan, depth)
        child_in_scan = in_scan or eqn.primitive.name in _SCAN_PRIMS
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, child_in_scan, depth + 1)


def source_site(eqn) -> Tuple[Optional[str], int, str]:
    """(repo-relative file, line, function) of an equation's user frame.

    Returns ``(None, 0, "<unknown>")`` when the equation has no user
    frame (jax-internal lowering helpers) — rules treat those as
    unanchorable and attribute them to the target instead.
    """
    frame = None
    if source_info_util is not None:
        try:
            frame = source_info_util.user_frame(eqn.source_info.traceback)
        except Exception:                            # pragma: no cover
            frame = None
    if frame is None:
        return None, 0, "<unknown>"
    fn = frame.file_name
    if REPO_MARKER in fn:
        fn = "src" + os.sep + "repro" + os.sep + fn.split(REPO_MARKER, 1)[1]
    return fn, int(frame.start_line), frame.function_name


def out_signature(closed_jaxpr) -> Tuple[str, ...]:
    """Canonical output-aval signature: ``f32[13,4]``-style strings."""
    return tuple(str(v.aval) for v in closed_jaxpr.jaxpr.outvars)


def trace32_64(fn, *args):
    """Trace ``fn(*args)`` under x32 and x64; returns (jaxpr32, jaxpr64,
    error64).  ``jaxpr64``/``error64`` are mutually exclusive: a raise
    under x64 is itself a J002 signal (the program's types depend on the
    global flag), so the caller gets the exception instead of a crash.
    """
    j32 = jax.make_jaxpr(fn)(*args)
    try:
        import warnings
        with warnings.catch_warnings():
            # promotion FutureWarnings are the *mechanism* J002 reports
            # via avals; don't spam the CLI while retracing
            warnings.simplefilter("ignore")
            with jax.enable_x64(True):
                j64 = jax.make_jaxpr(fn)(*args)
        return j32, j64, None
    except Exception as err:
        return j32, None, err


def aval_size_bytes(aval) -> int:
    """Total byte size of a shaped aval (0 when unknown)."""
    try:
        import numpy as np
        return int(np.prod(aval.shape, dtype="int64")) * aval.dtype.itemsize
    except Exception:                                # pragma: no cover
        return 0
