"""The harness with the timed path broken underneath: every fault a cell
can have must come out as ``correct`` false (tiny size, on the CPU, the
look for a chip skipped)."""

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import REPO

from chipbench import bench

import repro.nlinv.recon as recon


def _run(root, cell):
    return bench.run(root, cell, 2**31 + 29, 0.2, False,
                     t_start=time.perf_counter(), check_device=False)


def _state_unchanged(f):
    def frame(y, m, fov, w, u, x):
        return u, f(y, m, fov, w, u, x)[1]
    return frame


def _answer_altered(f):
    def frame(y, m, fov, w, u, x):
        u2, img = f(y, m, fov, w, u, x)
        return u2, img * 0.9
    return frame


def _half_batch(rec, width):
    """Solve the first half of the rows and hand its results to all."""
    half = rec.fn_batched_orig(width // 2)
    tile = lambda a: jnp.concatenate([a] * (width // (width // 2)))

    def frame(y, m, fov, w, u, x):
        h = width // 2
        cut = lambda t: jax.tree.map(lambda a: a[:h], t)
        u2, img = half(y[:h], m[:h], fov, w, cut(u), cut(x))
        return jax.tree.map(tile, u2), tile(img)
    return frame


BATCHED = {"state_unchanged": lambda rec, width, f: _state_unchanged(f),
           "answer_altered": lambda rec, width, f: _answer_altered(f),
           "half_batch": lambda rec, width, f: _half_batch(rec, width)}
SINGLE = {"state_unchanged": _state_unchanged,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(BATCHED))
def test_service_fault_is_caught(tiny_root, monkeypatch, fault):
    orig = recon.Reconstructor.fn_batched

    def fn_batched(self, width, *, donate=False):
        return BATCHED[fault](self, width, orig(self, width, donate=False))

    monkeypatch.setattr(recon.Reconstructor, "fn_batched_orig",
                        lambda self, width: orig(self, width), raising=False)
    monkeypatch.setattr(recon.Reconstructor, "fn_batched", fn_batched)
    r = _run(tiny_root, "tiny.service")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", sorted(SINGLE))
def test_stream_fault_is_caught(tiny_root, monkeypatch, fault):
    monkeypatch.setattr(
        recon.Reconstructor, "fn_donate_carry",
        property(lambda self: SINGLE[fault](self._plan(donate=False).fn)))
    r = _run(tiny_root, "tiny.stream")
    assert not r["correct"], r["checks"]


def _coil4_run(tmp_path, broken: bool) -> dict:
    """One tiny coil-split service run in a process with 4 virtual
    devices; ``broken`` leaves the channel-sum all-reduce out."""
    code = textwrap.dedent(f"""
        import json, pathlib, sys, time
        sys.path[:0] = [{str(REPO)!r}, {str(REPO / 'tests' / 'chipbench')!r},
                        {str(REPO / 'src')!r}]
        from conftest import write_root
        from chipbench import bench
        from repro.core import env
        if {broken!r}:
            def local(self, x, window=None, *, extras=(), compute=None,
                      **kw):
                return x, tuple(extras), compute() if compute else None
            env.Communicator.allreduce_overlap = local
        root = write_root(pathlib.Path({str(tmp_path)!r}),
                          cells=(("tiny4.service", "tiny-service"),), chips=4)
        r = bench.run(root, "tiny4.service", 2**31 + 31, 0.2, False,
                      t_start=time.perf_counter(), check_device=False)
        print(json.dumps(r))
    """)
    environ = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=environ,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("broken", [False, True])
def test_exchange_left_out_is_caught(tmp_path, broken):
    """On 4 (virtual) devices the sound coil-split service is correct;
    with the channel-sum all-reduce left out it is not."""
    r = _coil4_run(tmp_path, broken)
    assert r["device"]["count"] == 4
    assert r["correct"] is not broken, r["checks"]
