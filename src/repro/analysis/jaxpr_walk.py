"""Recursive jaxpr traversal with source attribution.

``walk(closed_jaxpr)`` yields every equation of the program, descending into
the sub-jaxprs carried in equation params — ``pjit`` bodies, ``scan``/
``while`` bodies, ``cond`` branches, ``custom_vjp``/``custom_jvp`` wrappers
and ``shard_map`` bodies — so a checker sees the whole traced computation,
not just the top level.

``pallas_call`` internals are deliberately **not** descended into: a Pallas
kernel body is written against device-local refs with its own (audited)
dtype discipline, and its jaxpr primitives (``get``/``swap``/masked loads)
don't obey the array-level rules the checkers encode. The call-site
operands/results of the ``pallas_call`` itself still flow through the
enclosing jaxpr and stay checked.

Every yielded item carries the innermost *user* stack frame of the
equation's source info — the line whose Python executed the op. That makes
attribution actionable (point at ``serve/cells.py:198``, not at jnp
internals) and is what lets the precision pass distinguish a dequant routed
through ``core/quantizer.py`` (sanctioned) from the same convert inlined at
a call site (flagged).
"""
from __future__ import annotations

from typing import Iterator, NamedTuple

from jax._src import source_info_util
from jax.extend import core as jex_core

#: eqn param values holding sub-jaxprs are discovered structurally, but
#: these primitives' bodies are skipped outright.
SKIP_PRIMITIVES = frozenset({"pallas_call"})


class WalkItem(NamedTuple):
    eqn: object                # jax JaxprEqn
    path: tuple[str, ...]      # enclosing primitive names, outermost first
    file: str | None           # innermost user frame, when known
    line: int | None


def _user_frame(eqn):
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None, None
    return frame.file_name, frame.start_line


def _sub_jaxprs(params: dict):
    """Every Jaxpr/ClosedJaxpr reachable from an eqn's params (one level)."""
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jex_core.Jaxpr):
                yield v


def _walk_jaxpr(jaxpr, path) -> Iterator[WalkItem]:
    for eqn in jaxpr.eqns:
        file, line = _user_frame(eqn)
        yield WalkItem(eqn, path, file, line)
        name = eqn.primitive.name
        if name in SKIP_PRIMITIVES:
            continue
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk_jaxpr(sub, path + (name,))


def walk(closed_jaxpr) -> Iterator[WalkItem]:
    """Yield every equation of ``closed_jaxpr`` (a ClosedJaxpr or Jaxpr),
    sub-jaxprs included, with source attribution."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    yield from _walk_jaxpr(jaxpr, ())


def out_dtypes(eqn):
    """dtypes of the eqn's output avals (skips tokens/abstract units)."""
    out = []
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None:
            out.append(dt)
    return out


def in_dtypes(eqn):
    out = []
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None:
            out.append(dt)
    return out
