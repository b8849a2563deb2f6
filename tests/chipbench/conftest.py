"""Fixtures for the benchmark's own tests: a tiny benchmark root, made of
files, that the harness drives on the CPU."""

import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_CONFIG = {
    "name": "tiny", "problem": "nlinv", "n": 16, "grid": 32, "coils": 4, "newton": 3,
    "cg_iters": 6, "channel_sum": "crop", "chips": 1,
    "precision": "complex64", "assumed": {"spokes": 7, "damping": 0.9},
    "reduced": [],
}
MIXES = {
    "tiny-service": {"entry": "service", "scanners": 2, "bucket": 2,
                     "movie_frames": 3, "check_frames": 2, "noise": 1e-4,
                     "trace_rounds": 2},
    "tiny-stream": {"entry": "stream", "scanners": 1, "movie_frames": 3,
                    "check_frames": 2, "noise": 1e-4, "trace_rounds": 3},
}
# the tiny program sits ~1e-5 from the reference on the CPU; a wrong
# frame sits at 1e-1 or more
TINY_LIMIT = 1e-3


def write_root(root: pathlib.Path, cells=(("tiny.service", "tiny-service"),
                                          ("tiny.stream", "tiny-stream")),
               chips: int = 1) -> pathlib.Path:
    """A benchmark root holding only files: BENCHMARK.json, a tiny
    configuration, traffic mixes, limits, the repository's metric
    readers, problems and peak table."""
    cb = root / "chipbench"
    for sub in ("configs", "traffic", "limits"):
        (cb / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "problems"):
        shutil.copytree(REPO / "chipbench" / sub, cb / sub,
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "chipbench" / "peaks.json", cb / "peaks.json")
    cfg = dict(TINY_CONFIG, chips=chips)
    (cb / "configs" / "tiny.json").write_text(json.dumps(cfg))
    for mix, body in MIXES.items():
        (cb / "traffic" / f"{mix}.json").write_text(json.dumps(body))
    for name, _ in cells:
        (cb / "limits" / f"{name}.json").write_text(
            json.dumps({"image_rel_l2": TINY_LIMIT}))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "tiny"}]
    bench["workloads"] = [{"name": n, "config": "tiny", "traffic": t,
                           "chips": chips, "why": "tiny"} for n, t in cells]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return write_root(tmp_path)
