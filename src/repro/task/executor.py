"""Executing task graphs: async dispatch in dependency order, fences
only at the sinks, and a rolling frame pipeline.

The concurrency model is the library's own (and the paper's: CUDA
streams become XLA async dispatch).  JAX dispatch is asynchronous — a
dispatched program runs on the devices while the host keeps going — so
the executor gets overlap not by threads but by *issue order*: it
dispatches every task of a graph in topological order **without
fencing**, and blocks only where the caller needs a materialized value.
Independent tasks — the gridding of frame ``f+2``, the FFT of ``f+1``,
the Newton/CG solve of ``f``, the crop of ``f-1`` — are all in flight
on the device queue at once; the per-frame host fence of the old
two-stage engine (the pipeline bubble) is gone.

``Executor``  runs one graph: validate, toposort, dispatch each task
              inside a ``repro.task.<name>`` profiler span, keep the
              latest tasks' host (dispatch) time in ``trace``.
``Pipeline``  the rolling form for streams: ``push`` one graph per
              frame/tick; at most ``inflight`` pushed steps stay
              unfenced — pushing past that retires (fences) the oldest,
              bounding device-buffer liveness while keeping the next
              frames' work behind the current one.

>>> g = TaskGraph()
>>> _ = g.add("double", lambda x: 2 * x, inputs=("x",), outputs=("d",))
>>> _ = g.add("inc", lambda d: d + 1, inputs=("d",), outputs=("out",))
>>> Executor().run(g, feeds={"x": 20})
{'d': 40, 'out': 41}
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Mapping, Sequence

import jax

from ..core.runtime import span
from .graph import TaskGraph

# Fault-injection hook on task dispatch (``repro.ft.inject`` installs
# it; this module never imports ft).  Called as ``args = TASK_HOOK(task,
# args)`` immediately before ``task.fn(*args)``: it may corrupt the
# args, sleep, or raise.  ``None`` (default) costs one attribute read.
TASK_HOOK = None


@dataclasses.dataclass(frozen=True)
class TaskRun:
    """One dispatched task: host-side cost, not device completion (the
    executor never fences per task — that is the point)."""

    name: str
    kind: str
    host_ms: float
    retries: int = 0    # re-dispatches this run needed (retry policy)


class Executor:
    """Dispatch a :class:`TaskGraph` in dependency order.

    ``run`` returns the produced values.  With ``fence=True`` (default)
    the returned values are materialized (``jax.block_until_ready``,
    inside a ``repro.task.fence`` span);
    ``fence=False`` leaves them in flight — the :class:`Pipeline` uses
    that to keep several frames on the device queue at once.

    ``retry`` takes a ``repro.ft.RestartPolicy``: a task raising a
    *transient* failure (``exc.transient`` truthy — e.g.
    ``ft.TransientFault`` — or an instance of ``retryable``) is
    re-dispatched up to ``max_restarts`` times with exponential backoff.
    Dispatch is topo-ordered and host-side, so retrying the failed task
    before anything downstream has been issued re-dispatches its whole
    downstream subgraph against the retried value; non-transient errors
    (including ``ft.DeviceLossFault``) propagate to the caller.

    >>> g = TaskGraph()
    >>> _ = g.add("one", lambda: 1, outputs=("a",))
    >>> ex = Executor()
    >>> ex.run(g)
    {'a': 1}
    >>> [r.name for r in ex.trace]
    ['one']

    ``trace`` keeps the last 1024 runs: a service's executor runs tasks
    for as long as the service lives.  Each dispatch is also a
    ``repro.task.<name>`` span in a profiler trace.
    """

    def __init__(self, *, retry=None, retryable=()):
        self.trace: deque[TaskRun] = deque(maxlen=1024)
        self.retry = retry
        self.retryable = tuple(retryable)
        self.retried = 0    # successful re-dispatches, lifetime

    def _dispatch(self, t, args):
        """One task through the injection hook + retry envelope."""
        tries = 0
        backoff = getattr(self.retry, "backoff_s", 0.0)
        while True:
            try:
                hook = TASK_HOOK
                a = args if hook is None else hook(t, args)
                return t.fn(*a), tries
            except Exception as e:  # noqa: BLE001 — policy decides
                transient = getattr(e, "transient", False) \
                    or isinstance(e, self.retryable)
                if self.retry is None or not transient \
                        or tries >= self.retry.max_restarts:
                    raise
                tries += 1
                self.retried += 1
                if backoff > 0:
                    time.sleep(backoff)
                    backoff *= getattr(self.retry, "backoff_mult", 1.0)

    def run(self, graph: TaskGraph, feeds: Mapping[str, Any] | None = None,
            *, outputs: Sequence[str] | None = None,
            fence: bool = True) -> dict:
        """Execute ``graph`` with ``feeds`` bound to the unproduced
        value names.  Returns every produced value, or only ``outputs``
        when given.  Raises the graph's validation errors
        (cycle / missing feed / cross-group race) before any task runs.
        """
        feeds = dict(feeds or {})
        order = graph.toposort(feeds=feeds.keys())
        values = feeds
        for t in order:
            args = [values[v] for v in t.inputs]
            t0 = time.perf_counter()
            with span(f"task.{t.name}"):
                res, tries = self._dispatch(t, args)
            self.trace.append(TaskRun(
                t.name, t.kind, (time.perf_counter() - t0) * 1e3,
                retries=tries))
            if len(t.outputs) == 1:
                values[t.outputs[0]] = res
            elif t.outputs:
                if not isinstance(res, (tuple, list)) \
                        or len(res) != len(t.outputs):
                    raise TypeError(
                        f"task {t.name!r} declares {len(t.outputs)} "
                        f"outputs but returned "
                        f"{type(res).__name__}")
                values.update(zip(t.outputs, res))
        produced = {v: values[v] for v in graph.values()}
        out = (produced if outputs is None
               else {v: values[v] for v in outputs})
        if not fence:
            return out
        with span("task.fence"):
            return jax.block_until_ready(out)


class Pipeline:
    """Rolling execution of a stream of graphs (one per frame/tick).

    ``push`` dispatches a graph unfenced and returns ``(values,
    retired)``: the step's in-flight values (feed them into the next
    frame's graph — JAX tracks the data dependency) plus any older
    steps that just left the ``inflight`` window, now fenced.  ``flush``
    retires everything left.  The window is the pipeline depth: 1
    degenerates to the fence-every-frame loop, 2 is the classic
    double-buffered overlap, 3+ keeps deeper stages of older frames
    concurrent with younger ones.

    >>> pipe = Pipeline(inflight=2)
    >>> g = TaskGraph()
    >>> _ = g.add("inc", lambda x: x + 1, inputs=("x",), outputs=("y",))
    >>> vals, done = pipe.push(g, {"x": 0}, tag="f0")
    >>> vals["y"], done                    # still inside the window
    (1, [])
    >>> for f in range(1, 3):
    ...     vals, done = pipe.push(g, {"x": vals["y"]}, tag=f"f{f}")
    >>> done                               # f0 was forced out and fenced
    [('f0', {'y': 1})]
    >>> [tag for tag, _ in pipe.flush()]
    ['f1', 'f2']

    With ``drop_failed=True`` a step whose dispatch raises is DROPPED —
    recorded in ``dropped`` and ``push`` returns ``(None, [])`` — so a
    stream keeps draining past a poisoned frame instead of deadlocking
    the window; the caller decides what stands in for the lost step.
    """

    def __init__(self, executor: Executor | None = None, *,
                 inflight: int = 2, drop_failed: bool = False):
        if inflight < 1:
            raise ValueError("Pipeline needs inflight >= 1")
        self.executor = executor or Executor()
        self.inflight = inflight
        self.drop_failed = drop_failed
        self.dropped: list[tuple] = []    # (tag, exception) per drop
        self._window: deque = deque()

    def __len__(self) -> int:
        return len(self._window)

    def push(self, graph: TaskGraph,
             feeds: Mapping[str, Any] | None = None, *,
             tag: Any = None,
             outputs: Sequence[str] | None = None) -> tuple[dict, list]:
        try:
            vals = self.executor.run(graph, feeds, outputs=outputs,
                                     fence=False)
        except Exception as e:  # noqa: BLE001 — opted in via drop_failed
            if not self.drop_failed:
                raise
            self.dropped.append((tag, e))
            return None, []
        self._window.append((tag, vals))
        retired = []
        while len(self._window) > self.inflight:
            retired.append(self._retire())
        return vals, retired

    def _retire(self) -> tuple:
        tag, vals = self._window.popleft()
        return tag, jax.block_until_ready(vals)

    def flush(self) -> list:
        """Fence and return every step still in the window, oldest
        first."""
        out = []
        while self._window:
            out.append(self._retire())
        return out
