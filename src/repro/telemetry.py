"""Stage names on the device program and spans on the host.

Device: the round engine wraps each stage of a round in
``jax.named_scope`` with one of :data:`STAGES`. The names are metadata
only: they reach the compiled HLO's
``metadata={op_name=".../fed.sample/..."}``, fusions included, and change no arithmetic. :func:`stage_seconds` sums a
device trace's per-operation times by stage through that metadata.

Host: :func:`span` marks a phase of the sweep executor. It enters
``jax.profiler.TraceAnnotation`` (so a running profiler records the phase
on the device trace's clock) and appends a :class:`Span` to a bounded
in-memory ring on ``time.perf_counter``, with its parent, so self times
can be read without the profiler. There is no switch: with the profiler
off a span costs a few microseconds, and the executor opens a handful per
``run_sweep`` call, none per round.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import os
import re
import threading
import time
from typing import (Deque, Dict, Iterable, Iterator, List, NamedTuple,
                    Optional, Sequence, Tuple)

import jax

STAGES = ("fed.sample", "fed.link", "fed.broadcast", "fed.local_train",
          "fed.aggregate", "fed.eval")
# scopes below the stages (``repro.models.model``): the cast of float32
# master weights to the compute dtype, and the client model's attention
# core, feed-forward and vocabulary head with the cross-entropy. Read by
# :func:`scope_seconds`; :func:`stage_seconds` still counts their time in
# the stage around them.
SUBSCOPES = ("fed.cast", "lm.attn", "lm.mlp", "lm.head")
OTHER = "other"
# ops that hold other ops: their device time is their children's, so
# counting them too would count that time twice
CONTAINERS = frozenset({"while", "conditional", "call"})
RING_SIZE = 4096


class Span(NamedTuple):
    id: int
    name: str
    parent: Optional[int]   # the id of the enclosing span, None at the top
    start: float            # time.perf_counter seconds
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


_RING: Deque[Span] = collections.deque(maxlen=RING_SIZE)
_IDS = itertools.count()
_OPEN = threading.local()


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Record the enclosed host phase as ``name`` (see the module doc)."""
    stack = _OPEN.__dict__.setdefault("stack", [])
    sid = next(_IDS)
    parent = stack[-1] if stack else None
    stack.append(sid)
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        end = time.perf_counter()
        stack.pop()
        _RING.append(Span(sid, name, parent, start, end))


def records() -> List[Span]:
    """The ring's spans, oldest first (a span is recorded when it ends, so
    children come before their parent)."""
    return list(_RING)


def children(parent: Span, spans: Sequence[Span]) -> List[Span]:
    return [s for s in spans if s.parent == parent.id]


def self_seconds(sp: Span, spans: Sequence[Span]) -> float:
    """``sp``'s duration less that of its direct children."""
    return sp.seconds - sum(c.seconds for c in children(sp, spans))


def last(name: str, n: int, spans: Sequence[Span]) -> List[Span]:
    """The last ``n`` spans named ``name``, oldest first."""
    named = [s for s in spans if s.name == name]
    return named[max(len(named) - n, 0):] if n > 0 else []


# -- device stages ----------------------------------------------------------

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_STAGE = re.compile(r"(?:fed|lm)\.[a-z_]+")


def _parse(text: str) -> Optional[Tuple[str, str, str, str]]:
    """``(name, opcode, signature, text)`` of one HLO instruction's text:
    the signature is ``name = shape opcode``, the text the whole
    instruction, both without ``ROOT`` or ``%`` so that either printing
    compares; None where ``text`` is no instruction."""
    m = _INSTR.match(text)
    if m is None:
        return None
    name, rest = m.groups()
    # the result shape: one token, or a parenthesized tuple of them
    depth = 0
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            opcode = rest[i:].lstrip().split("(", 1)[0].strip()
            break
    else:
        return name, "", name, f"{name} = {rest.strip()}"
    return (name, opcode, f"{name} = {rest[:i]} {opcode}",
            f"{name} = {rest.strip()}")


def stage_of_op_name(op_name: str, scopes: Sequence[str] = STAGES) -> str:
    """The innermost of ``scopes`` (by default :data:`STAGES`) among the
    components of an HLO ``op_name``, or :data:`OTHER`."""
    found = [s for s in _STAGE.findall(op_name) if s in scopes]
    return found[-1] if found else OTHER


def _hlo_index(hlo_texts: Iterable[str], scopes: Sequence[str]
               ) -> Dict[str, List[Tuple[str, str, str]]]:
    """Instruction name -> ``[(signature, text, scope)]`` over the compiled
    HLO modules ``hlo_texts`` (a name repeats only across modules)."""
    index: Dict[str, List[Tuple[str, str, str]]] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            parsed = _parse(line)
            if parsed is None:
                continue
            name, _, sig, instr = parsed
            m = _OP_NAME.search(line)
            stage = stage_of_op_name(m.group(1), scopes) if m else OTHER
            index.setdefault(name, []).append((sig, instr, stage))
    return index


def attribute(device_ops: Iterable[Tuple[str, float]], hlo_texts,
              scopes: Sequence[str] = STAGES
              ) -> List[Tuple[str, Optional[str], float]]:
    """``(op, stage, seconds)`` for every device op that is not a container.
    An op matches an instruction of ``hlo_texts`` with its name, result
    shape and opcode; where no instruction matches (an op of another
    program whose name happens to repeat, say), ``stage`` is None. Where
    modules share a match, the instruction whose text agrees with the op's
    longest decides. ``stage`` is the innermost of ``scopes``."""
    index = _hlo_index(hlo_texts, scopes)
    out = []
    for op, seconds in device_ops:
        parsed = _parse(op)
        if parsed is not None and parsed[1] in CONTAINERS:
            continue
        cands = [] if parsed is None else [
            (instr, stage) for sig, instr, stage in index.get(parsed[0], [])
            if sig == parsed[2]]
        if not cands:
            out.append((op, None, seconds))
            continue
        _, stage = max(cands, key=lambda c: len(
            os.path.commonprefix([c[0], parsed[3]])))
        out.append((op, stage, seconds))
    return out


def stage_seconds(device_ops: Iterable[Tuple[str, float]], hlo_texts
                  ) -> Dict[str, float]:
    """Device self seconds by stage: ``device_ops`` are a profile's
    ``(op, seconds)`` totals, each op named by its HLO instruction text
    (``"%fusion.179 = s32[256000]{0} fusion(...), kind=kLoop, ..."``);
    ``hlo_texts`` the compiled modules that ran. Each op goes to the
    innermost ``fed.*`` scope in its instruction's ``op_name``, or to
    :data:`OTHER` (no scope, or no such instruction); containers are
    skipped."""
    out: Dict[str, float] = {}
    for _, stage, seconds in attribute(device_ops, hlo_texts):
        key = stage or OTHER
        out[key] = out.get(key, 0.0) + seconds
    return out


def scope_seconds(device_ops: Iterable[Tuple[str, float]], hlo_texts,
                  scopes: Sequence[str] = SUBSCOPES) -> Dict[str, float]:
    """Device self seconds by the innermost of ``scopes`` (by default
    :data:`SUBSCOPES`) in each op's ``op_name``, as :func:`stage_seconds`
    reads stages; the rest goes to :data:`OTHER`."""
    out: Dict[str, float] = {}
    for _, scope, seconds in attribute(device_ops, hlo_texts, scopes):
        key = scope or OTHER
        out[key] = out.get(key, 0.0) + seconds
    return out
