"""The program's own instrumentation, on the CPU at a tiny size: host
spans (``repro.*`` profiler annotations) around the service's and the
stream's host work, device scopes (``jax.named_scope``) in the compiled
frame program, and the workload's stack counters."""

import glob
import os
import re
import tempfile

import jax
import numpy as np
import pytest

from repro.core import Environment
from repro.core.runtime import span
from repro.nlinv import phantom
from repro.nlinv.recon import Reconstructor
from repro.nlinv.stream import FrameStream
from repro.serve import NlinvStreamWorkload, ServeConfig, StreamScheduler

N, COILS = 16, 4


def _rec():
    return Reconstructor(Environment().subgroup(1), newton=2, cg_iters=3,
                         channel_sum="crop")


def _data(seed=0, frames=3):
    return phantom.make_dataset(n=N, ncoils=COILS, nspokes=7, frames=frames,
                                seed=seed)


def _open(sched, d, k):
    return sched.open(client=f"c{k}", grid=d["grid"], ncoils=COILS,
                      fov=d["fov"])


def _spans(fn):
    """Run ``fn`` under the profiler; its ``repro.*`` host events as
    (name, start, end, ids), sorted by start."""
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        pd = ProfileData.from_file(path)
        out = [(e.name[len("repro."):], e.start_ns, e.start_ns
                + e.duration_ns, dict(e.stats))
               for p in pd.planes if p.name.startswith("/host:")
               for line in p.lines for e in line.events
               if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(spans, child):
    """The innermost span that encloses ``child``, or None."""
    best = None
    for s in spans:
        if s is not child and s[1] <= child[1] and child[2] <= s[2] \
                and (best is None or s[1] >= best[1]):
            best = s
    return best


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_span_is_a_named_profiler_annotation():
    assert isinstance(span("x.y"), jax.profiler.TraceAnnotation)

    def one():
        with span("serve.tick", tick=4, width=2):
            pass

    assert [(n, ids) for n, _, _, ids in _spans(one)] == [
        ("serve.tick", {"tick": 4, "width": 2})]


def test_a_scheduler_tick_emits_nested_spans_with_ids():
    datas = [_data(seed=k) for k in range(2)]
    sched = StreamScheduler(NlinvStreamWorkload(_rec()),
                            ServeConfig(buckets=(2,)))
    ss = [_open(sched, d, k) for k, d in enumerate(datas)]

    def tick(f):
        for s, d in zip(ss, datas):
            assert sched.submit(s, (d["y"][f], d["masks"][f]))
        sched.tick()

    tick(0)                                  # compiles outside the trace
    got = _spans(lambda: tick(1))
    submits = _named(got, "serve.submit")
    assert [s[3] for s in submits] == [{"sid": s.sid} for s in ss]
    for sub in submits:
        assert [s[0] for s in got if _parent(got, s) is sub] == [
            "nlinv.upload"]
    (tick_span,) = _named(got, "serve.tick")
    assert tick_span[3] == {"tick": 1, "width": 2}
    inside = {s[0] for s in got if _parent(got, s) is tick_span}
    assert inside == {"task.stack", "task.solve", "task.damp", "task.fence",
                      "serve.health", "serve.deliver"}
    # the stack of a stable ready set is reused: no restack after tick 0
    assert not _named(got, "serve.restack")
    assert len(got) <= 12


def test_a_stream_run_emits_its_phases():
    d = _data(frames=1)
    fs = FrameStream(_rec())
    fs.run(d["y"][:1], d["masks"][:1], d["fov"])     # compiles
    got = _spans(lambda: fs.run(d["y"][:1], d["masks"][:1], d["fov"],
                                carry=fs.last_carry))
    assert [s[0] for s in got] == ["stream.prepare", "nlinv.upload",
                                   "stream.launch", "stream.wait",
                                   "stream.finish"]
    assert all(_parent(got, s) is None for s in got)
    assert _named(got, "stream.launch")[0][3] == {"frame": 0}
    assert _named(got, "stream.wait")[0][3] == {"frame": 0}


def test_the_frame_program_carries_its_scopes():
    rec = _rec()
    d = _data(frames=1)
    J, g = COILS, d["grid"]
    u = rec.init_carry(J, g)
    args = (rec.put_frame(np.asarray(d["y"][0])),
            rec.put_const(np.asarray(d["masks"][0])),
            rec.put_const(np.asarray(d["fov"])),
            rec.put_const(np.ones((g, g), np.float32)), u, u)
    ops = re.findall(r'op_name="([^"]*)"', rec.fn.lower(*args).compile()
                     .as_text())
    for scope in ("/nlinv.mask/", "/nlinv.newton/nlinv.cg/while/body/",
                  "/nlinv.newton/nlinv.cg/while/body/lib.fft/",
                  "/nlinv.newton/lib.fft/", "/nlinv.image/lib.fft/",
                  "/nlinv.image/"):
        assert any(scope in o for o in ops), scope


@pytest.mark.parametrize("width", [1, 2])
def test_restacks_count_membership_changes_only(width):
    datas = [_data(seed=k) for k in range(2)]
    wl = NlinvStreamWorkload(_rec())
    sched = StreamScheduler(wl, ServeConfig(buckets=(width, 2)))
    ss = [_open(sched, d, k) for k, d in enumerate(datas)]

    def tick(f):
        for s in ss:
            d = datas[s.sid]
            assert sched.submit(s, (d["y"][f % 3], d["masks"][f % 3]))
        sched.tick()

    tick(0)
    assert (wl.restacks, wl.spills) == (1, 0)
    for f in range(1, 3):
        tick(f)
    assert (wl.restacks, wl.spills) == (1, 0)
    assert sched.report()["aggregate"]["ft"]["restacks"] == 1
    sched.close(ss.pop())                    # a session leaves
    assert wl.spills == 1
    tick(3)
    assert (wl.restacks, wl.spills) == (2, 1)
