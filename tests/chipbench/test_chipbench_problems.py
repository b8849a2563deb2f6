"""A configuration names its problem file: a new problem is new files
only (a toy one, written into a fresh benchmark root, runs through the
unchanged harness), a missing one is refused, and the NLINV problem
hands the harness what the harness used to build itself."""

import json
import shutil
import textwrap
import time

import numpy as np
import pytest

from conftest import MIXES, REPO, TINY_CONFIG, write_root

from chipbench import bench, entries, reference
from chipbench import traffic as nlinv_traffic
from chipbench.work import nlinv as work

# carry c' = c / 2 + x_m, image 2 c'; the program in float32 on the
# device, the reference in float64 numpy
TOY = textwrap.dedent('''
    """Toy problem: a halving carry plus each frame, doubled."""
    import time

    import numpy as np

    SCALE = {scale!r}


    def make_traffic(cfg, mix, seed):
        rng = np.random.default_rng(seed)
        shape = (int(mix["movie_frames"]), int(cfg["n"]))
        return {{"movies": [rng.standard_normal(shape).astype(np.float32)
                            for _ in range(int(mix["scanners"]))]}}


    def describe(traffic):
        return f"{{traffic['movies'][0].shape[1:]}} floats"


    def reference_movie(cfg, traffic, scanner, frames, device, lowp=False):
        x = traffic["movies"][scanner].astype(np.float64)
        c, out = np.zeros(x.shape[1]), []
        for f in range(frames):
            c = 0.5 * c + x[f % len(x)]
            out.append(2.0 * c)
        return out, [int(cfg["work"])] * frames


    def frame_least_seconds(cfg, work, peak, chips):
        return float(cfg["least_s"]) * work / chips


    def _step():
        import jax

        @jax.jit
        def step(c, x):
            c = 0.5 * c + x
            return c, SCALE * 2.0 * c
        return step


    def service(cfg, mix, traffic, comm):
        import jax
        import jax.numpy as jnp
        from repro.serve import Workload
        step = _step()

        class Toy(Workload):
            def open_session(self, session):
                return jnp.zeros(session.meta["n"], jnp.float32)

            def step(self, batch, width):
                out = []
                for s, x in batch:
                    s.state, img = step(s.state, x)
                    out.append((jax.block_until_ready(img), False))
                return out

        opened = [{{"n": int(cfg["n"])}} for _ in traffic["movies"]]
        return Toy(), opened, lambda i, m: traffic["movies"][i][m]


    def stream(cfg, mix, traffic, comm):
        import jax
        import jax.numpy as jnp
        step = _step()

        def run(scanner, m, carry):
            t0 = time.perf_counter()
            c = jnp.zeros(int(cfg["n"]), jnp.float32) if carry is None \\
                else carry
            c, img = jax.block_until_ready(
                step(c, traffic["movies"][scanner][m]))
            return img, c, [(time.perf_counter() - t0) * 1e3]
        return run
''')
TOY_CONFIG = {"name": "toy", "problem": "toy", "n": 64, "work": 2,
              "least_s": 0.0625}
TOY_CELLS = {"toy.service": "tiny-service", "toy.stream": "tiny-stream"}
# float32 against float64 on values of order 1: a few 1e-7
TOY_LIMIT = 1e-5


def toy_root(root, scale=1.0):
    """A benchmark root of copied benchmark files plus the toy problem,
    its configuration, limits and BENCHMARK.json entries (the mixes are
    the tiny ones)."""
    write_root(root, cells=())
    cb = root / "chipbench"
    (cb / "problems" / "toy.py").write_text(TOY.format(scale=scale))
    (cb / "configs" / "toy.json").write_text(json.dumps(TOY_CONFIG))
    for name in TOY_CELLS:
        (cb / "limits" / f"{name}.json").write_text(
            json.dumps({"image_rel_l2": TOY_LIMIT}))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "toy", "source": "tests",
                         "file": "chipbench/configs/toy.json",
                         "reduced": [], "why": "toy"})
    b["workloads"] += [{"name": n, "config": "toy", "traffic": t,
                        "chips": 1, "why": "toy"}
                       for n, t in TOY_CELLS.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def _run(root, cell, seed=2**31 + 41):
    return bench.run(root, cell, seed, 0.2, False,
                     t_start=time.perf_counter(), check_device=False)


@pytest.mark.parametrize("cell", sorted(TOY_CELLS))
def test_new_problem_is_new_files(tmp_path, cell):
    r = _run(toy_root(tmp_path), cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 2
    assert r["checks"]["image_rel_l2"]["limit"] == TOY_LIMIT
    assert 0 < r["checks"]["image_rel_l2"]["value"] <= TOY_LIMIT
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("cell", sorted(TOY_CELLS))
def test_broken_toy_problem_is_not_correct(tmp_path, cell):
    """The toy's program with its answer scaled by 0.9 reads a gap of
    0.1 against its reference."""
    r = _run(toy_root(tmp_path, scale=0.9), cell)
    assert not r["correct"]
    assert r["checks"]["image_rel_l2"]["value"] == pytest.approx(0.1,
                                                                 rel=1e-4)


def test_config_naming_a_missing_problem_is_refused(tmp_path):
    """``load_cell`` raises with the path, and the command exits 2 with
    no result line."""
    for p in json.loads((REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    conf = tmp_path / "chipbench" / "configs" / "nlinv-paper768-1chip.json"
    conf.write_text(json.dumps(dict(json.loads(conf.read_text()),
                                    problem="no-such")))
    want = str(tmp_path / "chipbench" / "problems" / "no-such.py")
    with pytest.raises(bench.BenchError, match="no-such") as e:
        bench.load_cell(tmp_path, "nlinv-paper768-1chip.service-k2")
    assert want in str(e.value)
    from test_chipbench_harness import ARGS, _command
    r = _command(ARGS, tmp_path)
    assert r.returncode == 2, r.stderr
    assert want in r.stderr
    assert r.stdout.strip() == ""


def test_frame_roofline_reads_the_problems_least_time(tmp_path):
    """100 * least / (window / frames) = 100 * (0.0625 * 2 / 1) / (2 / 4)."""
    cell = bench.load_cell(toy_root(tmp_path), "toy.service")
    read = bench.load_reader(tmp_path, "frame_roofline")
    served = [entries.Served(0, f, 10.0) for f in range(4)]
    ctx = bench.Context(cell=cell, chips=1, peak={}, setup_s=1.0,
                        window_s=2.0, served=served, step_ms=[],
                        cg_iters=2)
    assert ctx.problem is cell.problem
    assert read(ctx) == 25.0


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_nlinv_problem_gives_what_the_harness_built(mix):
    """At tiny size: the problem's traffic, reference chain, least time
    and service items equal what the harness computed from
    ``traffic.make_traffic``, ``reference.movie`` and
    ``work.frame_least_seconds`` before problems had files."""
    problem = bench.load_problem(REPO, "nlinv")
    cfg, body = dict(TINY_CONFIG), MIXES[mix]
    seed = 2**31 + 43
    got = problem.make_traffic(cfg, body, seed)
    want = nlinv_traffic.make_traffic(cfg, body, seed)
    assert got.keys() == want.keys()
    assert np.array_equal(got["fov"], want["fov"])
    for a, b in zip(got["movies"], want["movies"], strict=True):
        assert np.array_equal(a["y"], b["y"])
        assert np.array_equal(a["masks"], b["masks"])
    frames = int(body["check_frames"]) + 1
    for i, mv in enumerate(want["movies"]):
        ref, its = reference.movie(mv["y"], mv["masks"], want["fov"],
                                   newton=int(cfg["newton"]),
                                   cg_iters=int(cfg["cg_iters"]),
                                   damping=float(cfg["assumed"]["damping"]),
                                   frames=frames, device=None)
        mine, mine_its = problem.reference_movie(cfg, got, i, frames, None)
        assert mine_its == its
        for a, b in zip(mine, ref, strict=True):
            assert np.array_equal(a, b)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for chips in (1, 4):
        assert problem.frame_least_seconds(cfg, 40, peak, chips) == \
            work.frame_least_seconds(cfg, 40, peak, chips)
    if body["entry"] == "service":
        from repro.core import Environment
        _, opened, item = problem.service(cfg, body, got,
                                          Environment().subgroup(1))
        assert len(opened) == len(want["movies"])
        for kw in opened:
            assert kw.keys() == {"grid", "ncoils", "fov"}
            assert (kw["grid"], kw["ncoils"]) == (want["grid"], want["coils"])
            assert np.array_equal(kw["fov"], want["fov"])
        for i, mv in enumerate(want["movies"]):
            for m in range(body["movie_frames"]):
                y, mask = item(i, m)
                assert np.array_equal(y, mv["y"][m])
                assert np.array_equal(mask, mv["masks"][m])
