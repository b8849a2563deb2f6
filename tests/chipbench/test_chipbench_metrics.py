"""The trace-to-metric reducers and the work counts, on the CPU."""

import math
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, stats
from chipbench import trace as tr
from chipbench.work import nlinv as work


def _fft_sites(jaxpr, depth=0, out=None):
    """FFT equations of a jaxpr, by how many loops enclose them."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "fft":
            out[depth] = out.get(depth, 0) + 1
        loop = eqn.primitive.name in ("while", "scan")
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                _fft_sites(inner, depth + loop, out)
    return out


@pytest.mark.parametrize("newton,cg_iters", [(7, 20), (3, 5)])
def test_fft_batches_match_the_reference(newton, cg_iters):
    """work.fft_batches counts the plain reference's own FFTs: one per
    frame outside the loops, four per Newton step, four per CG
    iteration."""
    g, J = 16, 3
    c = jnp.zeros((J, g, g), jnp.complex64)
    p = jnp.zeros((g, g), jnp.complex64)
    f = jnp.zeros((g, g), jnp.float32)
    jaxpr = jax.make_jaxpr(reference.frame_fn(newton, cg_iters))(
        c, f, f, f, p, c, p, c)
    sites = _fft_sites(jaxpr.jaxpr)
    assert sites == {0: 1, 1: 4, 2: 4}
    runs = sites[0] + newton * sites[1] + newton * cg_iters * sites[2]
    assert work.fft_batches(newton, newton * cg_iters) == runs


def test_frame_work_at_the_papers_size():
    cfg = {"n": 384, "coils": 8, "newton": 7, "cg_iters": 20}
    w = work.frame_work(cfg, 140)
    n = 768 * 768
    assert w["fft_batches"] == 589
    assert w["bytes"] == 589 * 2 * 8 * n * 8
    assert w["flops"] == pytest.approx(589 * 5 * 8 * n * math.log2(n))
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    one = work.frame_least_seconds(cfg, 140, peak, 1)
    assert one == pytest.approx(w["bytes"] / 819e9)   # bandwidth-bound
    assert work.frame_least_seconds(cfg, 140, peak, 4) == pytest.approx(
        one / 4)


def _ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


# operations as a TPU trace names them: by their HLO text
HLO = {
    "%psum.3 = f32[] all-reduce(f32[] %x), channel_id=1, "
    "replica_groups={{0,1,2,3}}, to_apply=%add": ("psum.3", "all-reduce"),
    "%custom-call.1 = f32[8,768,768]{2,1,0:T(8,128)} custom-call(c64[8,768,"
    "768]{2,1,0:T(8,128)} %args_0_.1), custom_call_target=\"X64SplitLow\"":
        ("custom-call.1", "custom-call"),
    "%while.12 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
    "condition=%c, body=%b": ("while.12", "while"),
    "%all-gather-start.2 = (f32[2]{0}, f32[8]{0}) all-gather-start(f32[2]{0}"
    " %p), dimensions={0}": ("all-gather-start.2", "all-gather-start"),
    "%fusion.7 = c64[2,768,768]{2,1,0:T(8,128)(2,1)} fusion(c64[2,768,768]"
    "{2,1,0:T(8,128)(2,1)} %a), kind=kLoop, calls=%fused":
        ("fusion.7", "fusion"),
    "%cg_update_pallas.5 = (f32[1,1]{1,0}, c64[8]{0}) custom-call(f32[8]{0} "
    "%v), custom_call_target=\"tpu_custom_call\"":
        ("cg_update_pallas.5", "custom-call"),
    "psum_invariant.7": ("psum_invariant.7", "psum_invariant"),
}


@pytest.mark.parametrize("text", sorted(HLO))
def test_operations_are_classified_by_their_hlo_opcode(text):
    name, code = HLO[text]
    assert tr.op_name(text) == name
    assert tr.hlo_opcode(text) == code
    assert tr.Event(tr.op_name(text), 0.0, 1.0,
                    code=tr.hlo_opcode(text)).code == code
    assert tr.is_collective(code) == code.startswith(("all-", "reduce-s"))
    assert tr.is_container(code) == (code == "while")


def _hlo(text, start, dur):
    return tr.Event(tr.op_name(text), float(start), float(dur),
                    code=tr.hlo_opcode(text))


def _synthetic():
    """Device 0: compute 0-10, all-reduce 8-14 (4 exposed), compute
    20-30, a psum (an all-reduce by its opcode) 31-33, all exposed;
    device 1: compute 0-5; window 0-40 (ns)."""
    psum = next(t for t in HLO if t.startswith("%psum"))
    ops = {0: [_ev("fusion.1", 0, 10), _ev("all-reduce.3", 8, 6),
               _ev("fft.2", 20, 10), _hlo(psum, 31, 2)],
           1: [_ev("fusion.1", 0, 5)]}
    return tr.Trace(ops, {}, [_ev("chipbench.window", 0, 40),
                              _ev("chipbench.step", 0, 16),
                              _ev("chipbench.fetch", 16, 4)])


def test_interval_reducers():
    t = _synthetic()
    assert stats.busy(t, 0) == [(0, 14), (20, 30), (31, 33)]
    assert stats.busy_s(t) == pytest.approx((26 + 5) / 2 / 1e9)
    assert stats.traced_window_s(t) == pytest.approx(40 / 1e9)
    assert stats.exposed_collective_s(t, 0) == pytest.approx(6 / 1e9)
    assert stats.exposed_collective_s(t, 1) == 0
    b = stats.breakdown(t)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(15 / 1e9)]
    # device 0 idles 14-20 (host fetching), 33-40 (no span open), 30-31
    assert b["idle_gaps"] == [["none", pytest.approx(7 / 1e9)],
                              ["fetch", pytest.approx(6 / 1e9)],
                              ["none", pytest.approx(1 / 1e9)]]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


def test_collective_readers_count_psums():
    """Both collectives of the synthetic trace count, the one named
    ``psum`` too: 6 ns exposed on device 0, none on device 1, over 2
    frames; 2 all-reduces over 2 devices and 2 frames."""
    import importlib
    from types import SimpleNamespace
    ctx = SimpleNamespace(trace=_synthetic(), frames=2)
    assert stats.collectives_per_frame(ctx) == {"all-reduce": 0.5}
    read = importlib.import_module(
        "chipbench.metrics.collective_exposed_ms").read
    assert read(ctx) == pytest.approx(1e3 * 6e-9 / 2 / 2)
    by_code = stats.op_seconds(ctx.trace, lambda e: e.code)
    assert by_code["all-reduce"] == pytest.approx(8e-9)


def test_solve_device_ms_sums_every_program():
    """A frame program split in two programs counts whole."""
    import importlib
    from types import SimpleNamespace
    read = importlib.import_module("chipbench.metrics.solve_device_ms").read
    win = [_ev("chipbench.window", 0, 100)]
    one = tr.Trace({}, {0: [_ev("jit_frame(1)", 10, 60)]}, win)
    two = tr.Trace({}, {0: [_ev("jit_a(1)", 10, 30), _ev("jit_b(2)", 40, 30)]},
                   win)
    ctx = SimpleNamespace(trace=one, frames=3)
    whole = read(ctx)
    ctx.trace = two
    assert read(ctx) == pytest.approx(whole) == pytest.approx(60 / 1e6 / 3)


def test_reducers_on_a_recorded_cpu_trace():
    """A real (CPU) profiler trace: the window span is found, the
    operations fall inside it, and the FFT's share is read."""
    f = jax.jit(lambda x: jnp.fft.fft2(x) * 2.0 + 1.0)
    x = jnp.ones((4, 128, 128), jnp.complex64)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("chipbench.step"):
                    f(x).block_until_ready()
        jax.profiler.stop_trace()
        t = tr.load(d)
    lo, hi = t.window()
    assert hi > lo and t.span("step") is not None
    names = {e.name.split(".")[0] for e in t.ops[0]}
    assert "fft" in names
    busy = stats.busy_s(t)
    assert 0 < busy <= stats.traced_window_s(t)
    fft = sum(e.dur for e in t.ops[0] if e.name.startswith("fft"))
    assert fft > 0
    assert np.isfinite(busy)
