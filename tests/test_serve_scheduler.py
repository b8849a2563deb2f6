"""The serving layer (ISSUE 7): scheduler admission/backpressure/
bucketing on a stub workload, SlotPool reclamation, the double-buffer
helper, latency_stats guards, and batched-vs-sequential NLINV parity
through the real scheduler on 1 and 4 (subprocess) devices — including
mixed per-client frame phases — and the batched program's rows (each
the unbatched frame's answer, one row's working memory at any width)."""

import numpy as np
import pytest

from helpers import run_with_devices
from repro.nlinv.stream import DoubleBuffer, latency_stats
from repro.serve import (AdmissionError, ServeConfig, SlotPool,
                         StreamScheduler, Workload)


class StubWorkload(Workload):
    """Records every scheduler interaction; items pass through as
    results, and an item equal to "last" completes its session."""

    def __init__(self):
        self.opened, self.closed, self.steps = [], [], []

    def open_session(self, session):
        self.opened.append(session.sid)
        return {}

    def step(self, batch, width):
        self.steps.append((tuple(s.sid for s, _ in batch), width))
        return [(item, item == "last") for _, item in batch]

    def close_session(self, session):
        self.closed.append(session.sid)


# ---------------------------------------------------------------------------
# scheduler control plane (no device work)
# ---------------------------------------------------------------------------

def test_admission_concurrency_queue_and_reject():
    wl = StubWorkload()
    sched = StreamScheduler(wl, ServeConfig(max_concurrency=2, max_queue=1))
    a, b = sched.open("a"), sched.open("b")
    assert a.admitted and b.admitted and wl.opened == [a.sid, b.sid]
    c = sched.open("c")                    # queued: concurrency is full
    assert not c.admitted and len(sched.waiting) == 1
    with pytest.raises(AdmissionError):    # queue is full too
        sched.open("d")
    # closing an admitted session admits the queued one
    sched.close(a)
    assert c.admitted and wl.closed == [a.sid]


def test_backpressure_sheds_past_queue_depth():
    sched = StreamScheduler(StubWorkload(), ServeConfig(queue_depth=2))
    s = sched.open("a")
    assert sched.submit(s, 1) and sched.submit(s, 2)
    assert not sched.submit(s, 3)          # shed, not queued
    assert s.rejected == 1 and len(s.pending) == 2
    sched.tick()                           # frees a slot in the queue
    assert sched.submit(s, 3)


def test_tick_batches_ready_sessions_at_bucketed_width():
    wl = StubWorkload()
    sched = StreamScheduler(wl, ServeConfig(buckets=(1, 2, 4)))
    ss = [sched.open(f"c{i}") for i in range(3)]
    for s in ss:
        sched.submit(s, "x")
    assert sched.tick() == 3
    (sids, width), = wl.steps
    assert sids == tuple(s.sid for s in ss) and width == 4   # 3 -> bucket 4
    assert sched.tick() == 0               # nothing ready


def test_done_result_closes_session_and_refills_from_queue():
    wl = StubWorkload()
    sched = StreamScheduler(wl, ServeConfig(max_concurrency=1, max_queue=4))
    a = sched.open("a")
    b = sched.open("b")                    # waits for a's slot
    sched.submit(a, "last")
    sched.tick()
    assert a.done and wl.closed == [a.sid]
    assert b.admitted                      # refilled at close
    sched.submit(b, "x")
    assert sched.drain() == 1
    assert b.results == ["x"] and not b.done


def test_overcommit_rotates_so_no_client_starves():
    wl = StubWorkload()
    sched = StreamScheduler(wl, ServeConfig(buckets=(1, 2)))
    ss = [sched.open(f"c{i}") for i in range(4)]
    for s in ss:
        for _ in range(2):
            sched.submit(s, "x")
    sched.drain()
    served = [sid for sids, _ in wl.steps for sid in sids]
    assert all(served.count(s.sid) == 2 for s in ss)


def test_report_latency_slo_and_single_sample_guard():
    sched = StreamScheduler(StubWorkload(),
                            ServeConfig(budget_ms=1e6))
    s = sched.open("a")
    sched.submit(s, "x")
    sched.tick()
    rep = sched.report()
    row = rep["clients"]["a"]
    assert row["frames"] == 1
    # single-sample window: no NaN/interp jitter, SLO met
    assert row["jitter_ms"] == 0.0 and row["p95_ms"] == row["p50_ms"]
    assert row["slo"]["met"] == 1.0
    assert rep["aggregate"]["frames"] == 1 and rep["aggregate"]["ticks"] == 1


def test_report_fps_counts_host_time_between_ticks(monkeypatch):
    """Aggregate fps is frames over the time from the first submit to
    the last delivery, host time between ticks included."""
    import types

    from repro.serve import scheduler
    now = [0.0]
    monkeypatch.setattr(scheduler, "time",
                        types.SimpleNamespace(perf_counter=lambda: now[0]))

    class Slow(StubWorkload):
        def step(self, batch, width):
            now[0] += 0.010                # 10 ms per tick
            return super().step(batch, width)

    sched = StreamScheduler(Slow())
    s = sched.open("a")
    for i in range(4):
        sched.submit(s, i)
        sched.tick()
        now[0] += 0.040                    # 40 ms of host work between
    agg = sched.report()["aggregate"]
    # first submit at 0 ms, last delivery at 3 * 50 + 10 = 160 ms
    assert agg["frames"] == 4 and agg["fps"] == round(4 / 0.160, 2)
    assert agg["tick"]["mean_ms"] == pytest.approx(10.0)


def test_latency_stats_single_sample_guard():
    s = latency_stats([7.25])
    assert s["jitter_ms"] == 0.0
    assert s["p50_ms"] == s["p95_ms"] == 7.25
    assert latency_stats([])["jitter_ms"] == 0.0
    many = latency_stats([1.0, 2.0, 3.0, 10.0])
    assert many["p95_ms"] > many["p50_ms"] and many["jitter_ms"] > 0


def test_double_buffer_stage_take_discipline():
    log = []
    buf = DoubleBuffer(lambda f: (log.append(f), f)[1])
    with pytest.raises(RuntimeError):
        buf.take()                         # nothing staged
    buf.stage(0)
    assert buf.ready and log == [0]
    with pytest.raises(RuntimeError):
        buf.stage(1)                       # one slot only
    assert buf.take() == 0 and not buf.ready
    buf.stage(1)
    assert buf.take() == 1


# ---------------------------------------------------------------------------
# SlotPool reclamation (the serve/engine.py bug-sweep satellite)
# ---------------------------------------------------------------------------

def test_slot_pool_full_batch_exhaustion():
    pool = SlotPool(2)
    assert pool.assign() == 0 and pool.assign() == 1
    assert pool.available == 0 and pool.in_use == (0, 1)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.assign()


def test_slot_pool_mid_stream_completion_and_refill():
    pool = SlotPool(3)
    slots = [pool.assign() for _ in range(3)]
    pool.free(slots[1])                    # the middle request finishes
    assert pool.in_use == (0, 2)
    assert pool.assign() == 1              # lowest free slot is reused
    with pytest.raises(RuntimeError, match="not assigned"):
        pool.free(99)
    pool.free(0)
    with pytest.raises(RuntimeError, match="not assigned"):
        pool.free(0)                       # double free is loud


# ---------------------------------------------------------------------------
# batched-vs-sequential NLINV parity through the real scheduler
# ---------------------------------------------------------------------------

NLINV_PARITY = """
from repro.core import Environment
from repro.nlinv import phantom
from repro.nlinv.recon import Reconstructor
from repro.nlinv.stream import stream_movie
from repro.serve import NlinvStreamWorkload, ServeConfig, StreamScheduler

comm = Environment().subgroup({ndev})
K, F = 3, 4
datas = [phantom.make_dataset(n=16, ncoils=4, nspokes=7, frames=F, seed=s)
         for s in range(K)]
rec = Reconstructor(comm, newton=2, cg_iters=4, channel_sum="crop")
sched = StreamScheduler(NlinvStreamWorkload(rec, damping=0.9),
                        ServeConfig(max_concurrency=4, buckets=(1, 2, 4)))
ss = [sched.open(client=f"c{{k}}", grid=datas[k]["grid"], ncoils=4,
                 fov=datas[k]["fov"]) for k in range(K)]
# mixed frame phases: client 0 skips tick 2 entirely
skipped = [(0, 2)]
for f in range(F):
    for k in range(K):
        if (k, f) not in skipped:
            assert sched.submit(ss[k], (datas[k]["y"][f],
                                        datas[k]["masks"][f]))
    sched.tick()
sched.drain()
for k in range(K):
    frames = [f for f in range(F) if (k, f) not in skipped]
    sub = dict(datas[k], y=datas[k]["y"][frames],
               masks=datas[k]["masks"][frames])
    ref, _ = stream_movie(sub, comm=comm, newton=2, cg_iters=4, damping=0.9)
    assert len(ss[k].results) == len(frames)
    for i in range(len(frames)):
        a, b = np.asarray(ss[k].results[i]), np.asarray(ref[i])
        err = np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)
        check(f"client{{k}} frame{{i}} parity ({{err:.2e}})", err < 1e-5)
# plan bucketing: widths 2 and 4 (never 3) were compiled, and each
# bucket is a visible plan-cache entry keyed on its width
widths = {{key[3] for key in rec.plan_cache._plans
          if key[:2] == ("nlinv", "frame_batched")}}
check(f"bucketed widths {{sorted(widths)}}", widths == {{2, 4}})
"""


def _run_parity(ndev):
    out = run_with_devices(NLINV_PARITY.format(ndev=ndev), ndev)
    assert "FAIL" not in out


def test_scheduler_parity_1dev():
    _run_parity(1)


def test_scheduler_parity_4dev():
    _run_parity(4)


# ---------------------------------------------------------------------------
# the batched program: rows solved one after another, in place
# ---------------------------------------------------------------------------

BATCH_ROWS = """
import jax.numpy as jnp
from repro.core import Environment
from repro.nlinv import phantom
from repro.nlinv.operators import sobolev_weight
from repro.nlinv.recon import Reconstructor

comm = Environment().subgroup({ndev})
rec = Reconstructor(comm, newton=2, cg_iters=4, channel_sum="crop")
B = 2
datas = [phantom.make_dataset(n=16, ncoils=4, nspokes=7, frames=1, seed=s)
         for s in range(B)]
g = datas[0]["grid"]
fov, w = jnp.asarray(datas[0]["fov"]), jnp.asarray(sobolev_weight(g))
# distinct carries per row, so a row mix-up cannot pass
us = []
for b in range(B):
    u = rec.init_carry(4, g)
    us.append({{"rho": u["rho"] * (1.0 + 0.1 * b),
               "chat": u["chat"] + 0.01 * (b + 1)}})
stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)
y = jnp.stack([jnp.asarray(d["y"][0]) for d in datas])
m = jnp.stack([jnp.asarray(d["masks"][0]) for d in datas])
ub, imgb = rec.fn_batched(B)(y, m, fov, w, stack(us), stack(us))
rel = lambda a, b: (np.abs(np.asarray(a) - np.asarray(b)).max()
                    / max(np.abs(np.asarray(b)).max(), 1e-30))
for b in range(B):
    u1, img1 = rec.fn(y[b], m[b], fov, w, us[b], us[b])
    errs = [rel(imgb[b], img1)] + [rel(ub[k][b], u1[k]) for k in u1]
    check(f"row {{b}} matches the unbatched frame ({{max(errs):.2e}})",
          max(errs) < 1e-6)
"""


@pytest.mark.parametrize("ndev", [1, 4])
def test_batched_rows_match_unbatched_frame(ndev):
    """Each row of ``fn_batched(2)`` is the unbatched frame program's
    answer on that row, on one device and on a 4-device group."""
    out = run_with_devices(BATCH_ROWS.format(ndev=ndev), ndev)
    assert "FAIL" not in out


BATCH_TEMPS = """
import jax.numpy as jnp
from repro.core import Environment
from repro.nlinv.recon import Reconstructor

comm = Environment().subgroup({ndev})
rec = Reconstructor(comm, newton=2, cg_iters=4, channel_sum="crop")
S, c64, g, J = jax.ShapeDtypeStruct, jnp.complex64, 32, 4
temps = {{}}
for B in (1, 4):
    u = {{"rho": S((B, g, g), c64), "chat": S((B, J, g, g), c64)}}
    args = (S((B, J, g, g), c64), S((B, g, g), jnp.bool_),
            S((g, g), jnp.float32), S((g, g), jnp.float32), u, u)
    comp = rec.fn_batched(B, donate=True).lower(*args).compile()
    temps[B] = comp.memory_analysis().temp_size_in_bytes
check(f"width-4 temporaries {{temps[4]}} B against width 1's {{temps[1]}} B",
      temps[4] < 1.5 * temps[1])
"""


@pytest.mark.parametrize("ndev", [1, 4])
def test_batched_program_holds_one_rows_temporaries(ndev):
    """The rows run one after another and write back into the donated
    carry stack, so the solve's working memory is one row's: a width-4
    program needs about a width-1 program's temporaries (vectorized
    rows need about four times as much)."""
    out = run_with_devices(BATCH_TEMPS.format(ndev=ndev), ndev)
    assert "FAIL" not in out
