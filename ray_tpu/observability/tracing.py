"""Trace-context propagation and span recording.

A span is a plain dict (msgpack-encodable so it crosses the RPC layer
untouched)::

    {"trace_id", "span_id", "parent_id", "name", "kind",
     "start", "end",              # wall-clock seconds (time.time())
     "worker_id", "node_id",      # filled at GCS ingest from the event
     "attrs": {...}}

The active context is thread-local: it is installed explicitly at every
thread hop (``bind``) and by the executor when it runs a task whose
``TaskSpec`` carries trace fields — exactly the places the reference
threads OpenTelemetry context through ``_raylet.pyx``.

Recording goes through the worker's ``TaskEventBuffer`` (status
``SPAN``) so spans share the batched GCS flush with task status events;
processes without a core worker (standalone engine in tests, the GCS
itself) fall back to a bounded process-local buffer readable via
``local_spans()``.

Those spans are on the wall clock. The profiler's trace has a clock of
its own, and ``annotate`` is the door to it: a host event on the trace of
whatever capture is running in this process (``observability/profile.py``
reads them back, and maps the wall-clock spans onto the same clock
through the capture's ``capture_window`` event).
"""

from __future__ import annotations

import contextlib
import copy
import functools
import os
import random
import sys
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable

TRACE_HEADER = "x-raytpu-trace"

_tls = threading.local()

_local_lock = threading.Lock()
_local_spans: list[dict] = []
_LOCAL_MAX = 4096


# Id generation is ON the task-submit hot path (one trace id + one span
# id per submit): uuid4 costs an os.urandom syscall each — ~60% of a
# 100k-no-op submit loop's wall time before PR 6. A process-seeded
# Random gives the same 128/64 bits of collision resistance for tracing
# purposes at ~30x less cost (os.urandom seeds it once; forked workers
# reseed via the pid mix so children never replay the parent's stream).
_id_rng = random.Random()
_id_rng.seed(int.from_bytes(os.urandom(16), "big") ^ os.getpid())
_id_pid = os.getpid()
_id_lock = threading.Lock()


def _id_hex(bits: int) -> str:
    global _id_pid
    with _id_lock:
        if os.getpid() != _id_pid:  # forked child: never replay the parent
            _id_rng.seed(int.from_bytes(os.urandom(16), "big") ^ os.getpid())
            _id_pid = os.getpid()
        return f"{_id_rng.getrandbits(bits):0{bits // 4}x}"


def new_trace_id() -> str:
    return _id_hex(128)


def new_span_id() -> str:
    return _id_hex(64)


@dataclass(frozen=True)
class TraceContext:
    trace_id: str
    span_id: str
    parent_id: str = ""

    def child(self) -> "TraceContext":
        return TraceContext(self.trace_id, new_span_id(), self.span_id)

    def to_wire(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id}


def current() -> TraceContext | None:
    return getattr(_tls, "ctx", None)


def current_wire() -> dict | None:
    ctx = current()
    return ctx.to_wire() if ctx is not None else None


def set_current(ctx: TraceContext | None) -> TraceContext | None:
    """Install ``ctx`` as this thread's active context; returns the
    previous one so callers can restore it."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    return prev


@contextlib.contextmanager
def use_context(ctx: TraceContext | None):
    prev = set_current(ctx)
    try:
        yield ctx
    finally:
        set_current(prev)


def bind(ctx: TraceContext | None, fn: Callable, *args, **kwargs) -> Callable:
    """Wrap ``fn`` so it runs under ``ctx`` on whatever thread executes
    it (thread-locals do not survive ``run_in_executor`` hops)."""

    def _wrapped():
        prev = set_current(ctx)
        try:
            return fn(*args, **kwargs)
        finally:
            set_current(prev)

    return _wrapped


def context_from_headers(headers: dict | None) -> TraceContext:
    """Root context for an ingress request: continue an incoming
    ``x-raytpu-trace: <trace_id>:<span_id>`` header (the remote span
    becomes our parent) or start a fresh trace."""
    raw = (headers or {}).get(TRACE_HEADER, "")
    if raw and ":" in raw:
        trace_id, _, parent = raw.partition(":")
        if trace_id:
            return TraceContext(trace_id, new_span_id(), parent)
    return TraceContext(new_trace_id(), new_span_id())


def make_span(name: str, kind: str, start: float, end: float,
              trace_id: str, parent_id: str = "", span_id: str | None = None,
              attrs: dict | None = None) -> dict:
    return {
        "trace_id": trace_id,
        "span_id": span_id or new_span_id(),
        "parent_id": parent_id,
        "name": name,
        "kind": kind,
        "start": start,
        "end": end,
        "attrs": attrs or {},
    }


class _NoAnnotation:
    """What ``annotate`` gives a process that never imported jax."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **attrs) -> None:
        pass


_NO_ANNOTATION = _NoAnnotation()
# The stat every ``annotate`` event carries: what ``profile.summarize``
# tells the program's spans from XLA's own host events by (on a CPU trace
# those are named alike: ``dot_general.56``).
MARK = "ray_tpu"  # spelled out as a keyword in ``annotate``


def annotate(name: str, **attrs):
    """A host event named ``name`` on the profiler's trace, around a
    ``with`` block: a ``jax.profiler.TraceAnnotation`` where jax is already
    imported in this process, and nothing where it is not (a core worker
    that never computes must not import jax for this). ``attrs`` (numbers
    and short strings: what was processed, how much) become the event's
    stats; ``.set_metadata(**more)`` inside the block adds what is known
    only at its end. With no capture running it costs well under a
    microsecond, so no site is behind a flag. Names are ``<layer>.<what>``;
    the event is marked ``MARK`` so a reader needs no list of layers."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_ANNOTATION
    return jax.profiler.TraceAnnotation(name, ray_tpu=1, **attrs)


# The frontend attribute every op lowered inside a ``device_scope`` carries.
SCOPE_ATTR = "rt_scope"


@contextlib.contextmanager
def device_scope(name: str):
    """Name the device ops a ``with`` block issues after the part of the
    model that issued them: a ``jax.named_scope`` (the instruction's
    ``op_name``, which only the compiled text keeps) AND the frontend
    attribute ``rt_scope="<path>"``, which is printed in the instruction's
    own HLO text, so it is on the profiler's op line, where an event is
    named by that text without its metadata. ``<path>`` is the ``/``-joined
    names of the scopes open around the block (``stack/attn/mla_q``). The
    open path is jax's own metadata context, not a variable beside it: jax
    carries that through ``jvp``, ``transpose``, ``lax.scan`` and
    ``jax.checkpoint``'s recomputation, installs the CALL's again around a
    ``custom_vjp``'s backward rule (so a scope opened inside a rule nests
    under the call's), keys ``jit``'s cache on it and keeps it thread-local;
    a fusion inherits its root's. Text in the executable: nothing at run
    time, a dict merge while tracing. Names are lower case (the attribute's
    value is lowered), without ``/`` or ``"``. ``profile.summarize`` reads
    a capture's table by scope from it."""
    import jax
    from jax._src.xla_metadata_lib import current_xla_metadata
    from jax.experimental.xla_metadata import set_xla_metadata

    outer = (current_xla_metadata() or {}).get(SCOPE_ATTR)
    path = f"{outer}/{name}" if outer else name
    with jax.named_scope(name), set_xla_metadata(**{SCOPE_ATTR: path}):
        yield path


# The frontend attribute every op issued inside a ``with_passes`` function
# carries beside ``rt_scope``: WHEN in the step it runs.
PASS_ATTR = "rt_pass"
# what ``ad_checkpoint._transpose_jaxpr`` extends the name stack with around
# the eqns of a ``jax.checkpoint``'s second run, and around no other
_REMAT_STACK = "rematted_computation"


def with_passes(fn: Callable, has_aux: bool = False) -> Callable:
    """``fn(diff, *rest)`` (a value, or ``(value, aux)`` with ``has_aux``),
    differentiable in ``diff``, with the same value and gradient, and on
    every op it issues the frontend attribute ``rt_pass``: ``"fwd"`` when it
    is merely called and in a differentiation's forward pass, ``"remat"`` on
    a ``jax.checkpoint``'s second run and ``"bwd"`` on the rest of the
    backward pass. Printed beside ``rt_scope`` in the instruction's own text,
    so a TPU's op line splits the step by pass (``profile.summarize``).

    A context cannot tell the second run from the backward pass: jax merges
    an eqn's own metadata OVER the one open when the eqn is evaluated, so what
    the forward trace set wins everywhere. The name stack can: each eqn of
    the second run carries ``rematted_computation``. So the function is a
    ``custom_vjp`` whose forward rule is ``jax.vjp`` (the pull-back is the
    residual) and whose backward rule traces the pull-back to a jaxpr, gives
    every eqn its pass (into ``scan``, ``cond``, ``checkpoint``, ``pjit`` and
    ``shard_map`` bodies, an inner eqn defaulting to the enclosing one's; not
    into a Pallas kernel's body) and evaluates it. Text in the executable and
    nothing at run time; lowering a step traces its backward pass to a jaxpr
    once more. ``rest`` takes no cotangent; ``aux`` comes from the forward
    pass as under ``value_and_grad(has_aux=True)``, and giving it a cotangent
    is refused (a differentiable ``aux`` keeps its tangents' residuals: a
    step without remat would be another program)."""
    import jax
    from jax._src import core
    from jax.custom_derivatives import SymbolicZero
    from jax.experimental.xla_metadata import set_xla_metadata

    def forward(diff, *rest):
        with set_xla_metadata(**{PASS_ATTR: "fwd"}):
            return fn(diff, *rest)

    def marked(jaxpr, default: str, done: dict):
        # one new jaxpr per (jaxpr, pass): two calls of one pjit body stay one
        key = (id(jaxpr), default)
        if key not in done:
            eqns = []
            for eqn in jaxpr.eqns:
                own = ("remat" if _REMAT_STACK in str(eqn.source_info.name_stack)
                       else default)
                params = eqn.params
                if eqn.primitive.name != "pallas_call":
                    params = {k: inside(v, own, done) for k, v in params.items()}
                ctx = copy.copy(eqn.ctx)  # compute type, mesh: as they are
                ctx.xla_metadata = {**(ctx.xla_metadata or {}), PASS_ATTR: own}
                eqns.append(eqn.replace(params=params, ctx=ctx))
            done[key] = (jaxpr, jaxpr.replace(eqns=eqns))  # the key's id stays taken
        return done[key][1]

    def inside(param, default: str, done: dict):
        if isinstance(param, core.ClosedJaxpr):
            return param.replace(jaxpr=marked(param.jaxpr, default, done))
        if isinstance(param, core.Jaxpr):
            return marked(param, default, done)
        if isinstance(param, (tuple, list)) and any(
                isinstance(p, (core.ClosedJaxpr, core.Jaxpr)) for p in param):
            return type(param)(inside(p, default, done) for p in param)
        return param

    @jax.custom_vjp
    def call(diff, rest):
        return forward(diff, *rest)

    def call_fwd(diff, rest):
        diff, rest = jax.tree.map(lambda x: x.value, (diff, rest))  # symbolic_zeros' wrappers
        out = jax.vjp(lambda d: forward(d, *rest), diff, has_aux=has_aux)
        return (out[0], out[2]) if has_aux else out[0], out[1]

    def call_bwd(pull, ct):
        zero = lambda c: isinstance(c, SymbolicZero)  # noqa: E731
        if has_aux:
            ct, aux_ct = ct
            if not all(map(zero, jax.tree.leaves(aux_ct, is_leaf=zero))):
                raise TypeError(
                    f"{getattr(fn, '__name__', fn)}'s aux was given a cotangent: under "
                    "with_passes it comes from the forward pass and is not differentiated")
        ct = jax.tree.map(lambda c: jax.numpy.zeros(c.shape, c.dtype) if zero(c) else c,
                          ct, is_leaf=zero)
        closed, shape = jax.make_jaxpr(lambda p, c: p(c), return_shape=True)(pull, ct)
        flat = core.eval_jaxpr(marked(closed.jaxpr, "bwd", {}), closed.consts,
                               *jax.tree.leaves((pull, ct)))
        (grad,) = jax.tree.unflatten(jax.tree.structure(shape), flat)
        return grad, None

    call.defvjp(call_fwd, call_bwd, symbolic_zeros=True)
    return functools.wraps(fn)(lambda diff, *rest: call(diff, rest))


_enabled: bool | None = None  # read once per process: a span must stay cheap


def _tracing_enabled() -> bool:
    global _enabled
    if _enabled is None:
        try:
            from ..core.config import get_config

            _enabled = bool(get_config().enable_tracing)
        except Exception:
            _enabled = True
    return _enabled


def record_span(span_dict: dict) -> None:
    """Buffer one finished span. Never raises — tracing must not be able
    to fail the traced operation."""
    if not span_dict.get("trace_id") or not _tracing_enabled():
        return
    try:
        from ..core.worker import _global_worker

        if _global_worker is not None:
            _global_worker.task_events.record_span(span_dict)
            return
    except Exception:
        pass
    with _local_lock:
        if len(_local_spans) >= _LOCAL_MAX:
            del _local_spans[: _LOCAL_MAX // 4]
        _local_spans.append(span_dict)


def local_spans(trace_id: str | None = None) -> list[dict]:
    """Spans recorded in this process while no core worker was connected
    (standalone engines, unit tests)."""
    with _local_lock:
        out = list(_local_spans)
    if trace_id:
        out = [s for s in out if s["trace_id"] == trace_id]
    return out


@contextlib.contextmanager
def span(name: str, kind: str = "app", attrs: dict | None = None,
         root: bool = False):
    """Record a span around a code block. Opens a child of the current
    context (or a fresh root trace when there is none or ``root=True``)
    and installs itself as the current context for the duration, so
    anything submitted inside — tasks, actor calls, engine requests —
    chains under it. The block is also an ``annotate`` event, so it shows
    on a profiler capture taken meanwhile."""
    parent = None if root else current()
    if parent is None:
        ctx = TraceContext(new_trace_id(), new_span_id())
    else:
        ctx = parent.child()
    start = time.time()
    with use_context(ctx), annotate(name):
        try:
            yield ctx
        finally:
            record_span(make_span(name, kind, start, time.time(),
                                  ctx.trace_id, ctx.parent_id, ctx.span_id,
                                  attrs))
