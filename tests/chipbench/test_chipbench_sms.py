"""The SMS-NLINV problem file on the CPU: its traffic, its plain
reference (``chipbench/reference_sms.py``), its work count
(``chipbench/work/sms_nlinv.py``), the readers of the mixing kernels, and
a tiny SMS cell through the unchanged harness."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, write_root

from chipbench import bench, reference, reference_sms
from chipbench import trace as tr
from chipbench.work import nlinv as work1
from chipbench.work import sms_nlinv as work

PROBLEM = bench.load_problem(REPO, "sms_nlinv")
TINY = {"name": "tiny-sms", "problem": "sms_nlinv", "n": 16, "grid": 32,
        "coils": 4, "slices": 3, "newton": 3, "cg_iters": 6,
        "channel_sum": "crop", "chips": 1,
        "assumed": {"spokes": 7, "damping": 0.9}, "reduced": []}
MIX = {"scanners": 2, "movie_frames": 3, "noise": 1e-4}
PAPER = {"n": 384, "coils": 8, "slices": 3, "newton": 7, "cg_iters": 20}


def test_traffic_shapes_and_seed():
    a = PROBLEM.make_traffic(TINY, MIX, 2**33 + 1)
    b = PROBLEM.make_traffic(TINY, MIX, 2**33 + 1)
    c = PROBLEM.make_traffic(TINY, MIX, 2**33 + 2)
    assert (a["grid"], a["coils"], a["slices"]) == (32, 4, 3)
    for x, y in zip(a["movies"], b["movies"], strict=True):
        assert x["y"].shape == (3, 3, 4, 32, 32) and x["y"].dtype == \
            np.complex64
        assert x["masks"].shape == (3, 3, 32, 32)
        assert np.array_equal(x["y"], y["y"])
    assert not np.array_equal(a["movies"][0]["y"], c["movies"][0]["y"])
    mv = a["movies"][0]
    # each partition has its own spokes, and the data live on them only
    assert not np.array_equal(mv["masks"][0, 0], mv["masks"][0, 1])
    assert not np.any(mv["y"] * ~mv["masks"][:, :, None])


def test_traffic_is_the_slice_encoded_model():
    """Without noise, partition q's data are its spokes of
    FFT(Sum_m Xi_qm rho_m c_mj), summed here slice by slice."""
    t = PROBLEM.make_traffic(TINY, dict(MIX, noise=0.0), 5)
    n, J, M = 16, 4, 3
    zs = PROBLEM.slice_heights(M)
    xi = reference_sms.phase_cycle(M)
    mv, q0 = t["movies"][0], n // 2
    for f in range(3):
        # the seed picks the cycle start: find the motion phase of frame f
        want = None
        for phase in range(3):
            img = np.zeros((M, J, 2 * n, 2 * n), np.complex64)
            for m, z in enumerate(zs):
                img[m, :, q0:q0 + n, q0:q0 + n] = (
                    PROBLEM.slice_phantom(n, phase / 3, z)[None]
                    * PROBLEM.slice_coils(n, J, z))
            k = np.zeros_like(img)
            for q in range(M):
                for m in range(M):
                    k[q] += xi[q, m] * np.fft.fftshift(np.fft.fft2(
                        np.fft.ifftshift(img[m], axes=(-2, -1)),
                        norm="ortho"), axes=(-2, -1))
            cand = k * mv["masks"][f][:, None]
            if np.allclose(cand, mv["y"][f], atol=1e-5):
                want = cand
        assert want is not None, f"frame {f} is no phase of the model"


def test_slice_coils_are_distinct_and_normalised():
    c = [PROBLEM.slice_coils(16, 4, z) for z in PROBLEM.slice_heights(3)]
    for ci in c:
        rss = np.sqrt((np.abs(ci) ** 2).sum(0))
        np.testing.assert_allclose(rss, 1.0, atol=1e-5)
    assert not np.allclose(c[0], c[2])


def test_reference_with_one_slice_is_the_single_slice_reference():
    """reference_sms with M = 1 (Xi = 1) replays reference.py's chain."""
    cfg = dict(TINY, slices=1)
    t = PROBLEM.make_traffic(cfg, dict(MIX, scanners=1), 9)
    mv = t["movies"][0]
    kw = dict(newton=3, cg_iters=6, damping=0.9, frames=3, device=None)
    one, its1 = reference.movie(mv["y"][:, 0], mv["masks"][:, 0], t["fov"],
                                **kw)
    sms, its = reference_sms.movie(mv["y"], mv["masks"], t["fov"], **kw)
    assert its == its1
    for a, b in zip(sms, one, strict=True):
        assert reference.rel_l2(a[0], b) < 1e-5


def _fft_sites(jaxpr, depth=0, out=None):
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


def test_work_counts_the_references_ffts():
    """The FFT batches of an SMS frame are the single-slice count, each
    batch M*J planes: the reference's own FFTs, one per frame outside
    the loops, four per Newton step, four per CG iteration."""
    M, J, g = 3, 2, 16
    c = jnp.zeros((M, J, g, g), jnp.complex64)
    p = jnp.zeros((M, g, g), jnp.complex64)
    f = jnp.zeros((g, g), jnp.float32)
    xi = jnp.asarray(reference_sms.phase_cycle(M))
    jaxpr = jax.make_jaxpr(reference_sms.frame_fn(7, 20))(
        c, jnp.zeros((M, g, g), jnp.float32), f, f, xi, p, c, p, c)
    assert _fft_sites(jaxpr.jaxpr) == {0: 1, 1: 4, 2: 4}
    w = work.frame_work(PAPER, 140)
    assert w["fft_batches"] == work1.fft_batches(7, 140) == 589
    n = 768 * 768
    fft_bytes = 589 * 2 * 24 * n * 8
    assert w["flops"] == pytest.approx(589 * 5 * 24 * n * np.log2(n))
    assert w["bytes"] == pytest.approx(
        fft_bytes + 140 * work.lincomb_bytes(PAPER, True)
        + 7 * work.lincomb_bytes(PAPER, False)
        + 147 * work.adjoint_bytes(PAPER))


def test_mixing_kernel_bytes_at_the_papers_size():
    """A call's least bytes are the coil stacks it streams, 24 planes of
    768 x 768 complex64 each: DG's form reads two, G's form one, the
    adjoint two; on 4 chips a stack holds 2 channels of each slice."""
    stack = 24 * 768 * 768 * 8
    assert work.stack_bytes(PAPER) == stack
    assert work.lincomb_bytes(PAPER, True) == 2 * stack
    assert work.lincomb_bytes(PAPER, False) == stack
    assert work.adjoint_bytes(PAPER) == 2 * stack
    assert work.adjoint_bytes(PAPER, chips=4) == 2 * stack / 4


def _ctx(ops, frames=2, chips=1):
    from types import SimpleNamespace
    cell = SimpleNamespace(cfg=dict(PAPER))
    trace = tr.Trace(ops, {}, [tr.Event("chipbench.window", 0.0, 1e9)])
    return SimpleNamespace(trace=trace, frames=frames, chips=chips,
                           cell=cell, peak={"hbm_bytes_per_s": 819e9})


def test_mix_readers_on_a_synthetic_trace():
    """2 frames of 7 Newton steps and 10 CG iterations each: 34 calls
    of each kernel, 14 of the forward ones in G's form; every call 1 ms."""
    from chipbench.metrics import sms_mix_device_ms, sms_mix_roofline
    ops = []
    for i in range(34):
        ops.append(tr.Event(f"sms_lincomb_pallas.{i % 14}", 2e6 * i, 1e6))
        ops.append(tr.Event(f"sms_adjoint_pallas.{i % 14}", 2e6 * i + 1e6,
                            1e6))
    ops.append(tr.Event("coil_lincomb_pallas.3", 9e8, 1e6))
    ctx = _ctx({0: ops})
    assert sms_mix_device_ms.read(ctx) == pytest.approx(68 / 2)
    nbytes = (14 * work.lincomb_bytes(PAPER, False)
              + 20 * work.lincomb_bytes(PAPER, True)
              + 34 * work.adjoint_bytes(PAPER))
    assert sms_mix_roofline.read(ctx) == pytest.approx(
        100 * nbytes / (68e-3 * 819e9))
    # nothing to read: no mixing kernel ran (the single-slice cells, or a
    # program without SMS)
    none = _ctx({0: [tr.Event("coil_lincomb_pallas.3", 0.0, 1e6)]})
    assert sms_mix_device_ms.read(none) is None
    assert sms_mix_roofline.read(none) is None


def _sms_root(tmp_path):
    root = write_root(tmp_path, cells=())
    cb = root / "chipbench"
    (cb / "configs" / "tiny-sms.json").write_text(json.dumps(TINY))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": "tiny-sms", "source": "tests",
                     "file": "chipbench/configs/tiny-sms.json",
                     "reduced": [], "why": "tiny"}]
    cells = {"sms.service": "tiny-service", "sms.stream": "tiny-stream"}
    b["workloads"] = [{"name": n, "config": "tiny-sms", "traffic": t,
                       "chips": 1, "why": "tiny"} for n, t in cells.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    for name in cells:
        (cb / "limits" / f"{name}.json").write_text(
            json.dumps({"image_rel_l2": 1e-3}))
    return root


@pytest.mark.parametrize("cell", ["sms.service", "sms.stream"])
def test_tiny_sms_cell_runs_correct(tmp_path, cell):
    r = bench.run(_sms_root(tmp_path), cell, 2**31 + 61, 0.2, False,
                  t_start=time.perf_counter(), check_device=False)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert 0 < r["checks"]["image_rel_l2"]["value"] < 1e-3


def test_bf16_control_fails_the_sms_limit():
    """The reference in bfloat16, in the program's place, reads above the
    SMS cell's limit (tiny size; the chip readings are in PERF.md)."""
    limits = [json.loads((REPO / "chipbench" / "limits" / f"{w}.json")
                         .read_text())["image_rel_l2"]
              for w in ("nlinv-sms3-paper768-1chip.service-k2",)]
    cfg = dict(TINY, newton=7, cg_iters=20, assumed={"spokes": 11,
                                                     "damping": 0.9})
    for seed in (1, 2**31 + 5):
        t = PROBLEM.make_traffic(cfg, dict(MIX, scanners=1), seed)
        ref, _ = PROBLEM.reference_movie(cfg, t, 0, 3, None)
        low, _ = PROBLEM.reference_movie(cfg, t, 0, 3, None, lowp=True)
        worst = PROBLEM.slice_gaps({0: dict(enumerate(low))},
                                   {0: dict(enumerate(ref))})
        assert len(worst) == 3 and min(worst) > max(limits), (seed, worst)


def test_a_program_without_slices_is_refused(monkeypatch):
    """The parent of SMS-NLINV has no ``Reconstructor(slices=)``: the
    problem refuses it before it makes any traffic (exit code 2)."""
    import repro.nlinv.recon as recon

    class OneSlice:
        def __init__(self, comm=None, axis="data", *, newton=7):
            pass

    monkeypatch.setattr(recon, "Reconstructor", OneSlice)
    with pytest.raises(bench.BenchError, match="no SMS-NLINV"):
        PROBLEM.make_traffic(TINY, MIX, 3)


def test_sms_cell_is_declared_with_its_metrics():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = "nlinv-sms3-paper768-1chip.service-k2"
    loaded = bench.load_cell(REPO, cell)
    assert loaded.cfg["slices"] == 3
    assert (loaded.mix["scanners"], loaded.mix["bucket"]) == (2, 2)
    assert {m["name"] for m in loaded.per_layer} == {"sms_mix_device_ms",
                                                     "sms_mix_roofline"}
    assert {m["name"] for m in loaded.end_to_end} == {
        "frames_per_s", "frame_latency_p50_ms", "setup_s"}
    for m in loaded.per_layer:
        bench.load_reader(REPO, m["name"])
    assert any(w["name"] == cell for w in b["workloads"])
